"""Unified decoder stack covering all 10 assigned architectures.

One layer implementation, parameterized by ``cfg.mixer``:
  attention        dense llama-family, musicgen, chameleon, llama4-scout
  mla              deepseek-v2 (latent attention)
  ssm              mamba2 (no MLP when d_ff == 0)
  hybrid           hymba (parallel attention + SSM heads, mean-combined)
plus SwiGLU or capacity-MoE feed-forward.

Training/forward scans over stacked layer params (jax.lax.scan + remat) to
keep the HLO small and memory bounded; prefill/decode unroll the layer loop
so per-layer caches may have non-uniform shapes (hymba: 1k-window SWA layers
vs full-length global layers).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, common, mlp, ssm
from repro.sharding import rules as shrules


# ------------------------------------------------------------------ init


def init_layer(key, cfg):
    d = cfg.d_model
    dt = common.dtype_of(cfg)
    ks = common.split_keys(key, 6)
    params = {"ln1": jnp.ones((d,), dt)}
    axes = {"ln1": ("embed_unsharded",)}
    if cfg.mixer in ("attention", "hybrid"):
        params["attn"], axes["attn"] = attention.init_attention(ks[0], cfg)
    if cfg.mixer == "mla":
        params["mla"], axes["mla"] = attention.init_mla(ks[0], cfg)
    if cfg.mixer in ("ssm", "hybrid"):
        params["ssm"], axes["ssm"] = ssm.init_ssm(ks[1], cfg)
    if cfg.mixer == "hybrid":
        params["ln_ab"] = jnp.ones((d,), dt)
        params["ln_sb"] = jnp.ones((d,), dt)
        axes["ln_ab"] = axes["ln_sb"] = ("embed_unsharded",)
    if cfg.moe is not None:
        params["ln2"] = jnp.ones((d,), dt)
        axes["ln2"] = ("embed_unsharded",)
        params["moe"], axes["moe"] = mlp.init_moe(ks[2], cfg)
    elif cfg.d_ff > 0:
        params["ln2"] = jnp.ones((d,), dt)
        axes["ln2"] = ("embed_unsharded",)
        params["mlp"], axes["mlp"] = mlp.init_swiglu(ks[2], cfg)
    return params, axes


def init_params(cfg, key):
    """Returns (params, axes); layer params stacked (L, ...) for scan."""
    kemb, klayers, kout = jax.random.split(key, 3)
    dt = common.dtype_of(cfg)
    v, d = cfg.padded_vocab, cfg.d_model
    layer_keys = jax.random.split(klayers, cfg.num_layers)
    stacked = jax.vmap(lambda k: init_layer(k, cfg)[0])(layer_keys)
    _, layer_axes = init_layer(layer_keys[0], cfg)
    layer_axes = jax.tree.map(
        lambda a: ("layers",) + a,
        layer_axes,
        is_leaf=lambda a: isinstance(a, tuple) and all(
            isinstance(s, str) for s in a),
    )
    params = {
        "layers": stacked,
        "ln_f": jnp.ones((d,), dt),
        "embed": common.dense_init(kemb, (v, d), dt, in_axis_size=d),
    }
    axes = {
        "layers": layer_axes,
        "ln_f": ("embed_unsharded",),
        "embed": ("vocab", "embed_out"),
    }
    if not cfg.tie_embeddings:
        params["embed_in"] = common.dense_init(kout, (v, d), dt,
                                               in_axis_size=d)
        axes["embed_in"] = ("vocab_in", "embed_sharded")
    return params, axes


# ------------------------------------------------------------------ layer


def _mixer_forward(lp, cfg, x, positions, is_global):
    """Pre-norm mixer residual.  Returns (x', cacheables)."""
    h = common.rms_norm(x, lp["ln1"], cfg.norm_eps)
    caches = {}
    if cfg.mixer == "attention":
        out, kv = attention.attention_forward(lp["attn"], cfg, h, positions,
                                              is_global)
        caches["attn"] = kv
    elif cfg.mixer == "mla":
        out, kv = attention.mla_forward(lp["mla"], cfg, h, positions)
        caches["mla"] = kv
    elif cfg.mixer == "ssm":
        out, st = ssm.ssm_forward(lp["ssm"], cfg, h)
        caches["ssm"] = st
    elif cfg.mixer == "hybrid":
        a_out, kv = attention.attention_forward(lp["attn"], cfg, h, positions,
                                                is_global)
        s_out, st = ssm.ssm_forward(lp["ssm"], cfg, h)
        caches["attn"], caches["ssm"] = kv, st
        out = 0.5 * (
            common.rms_norm(a_out, lp["ln_ab"], cfg.norm_eps)
            + common.rms_norm(s_out, lp["ln_sb"], cfg.norm_eps)
        )
    else:
        raise ValueError(cfg.mixer)
    return x + out, caches


def _mlp_forward(lp, cfg, x):
    """Pre-norm FFN residual.  Returns (x', aux_loss)."""
    if cfg.moe is not None:
        h = common.rms_norm(x, lp["ln2"], cfg.norm_eps)
        out, aux = mlp.moe_apply(lp["moe"], cfg, h)
        return x + out, aux
    if cfg.d_ff > 0:
        h = common.rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp.swiglu(lp["mlp"], h), 0.0
    return x, 0.0


def layer_forward(lp, cfg, x, positions, is_global):
    x, caches = _mixer_forward(lp, cfg, x, positions, is_global)
    x, aux = _mlp_forward(lp, cfg, x)
    return x, aux, caches


# ---------------------------------------------------------------- forward


def _global_flags(cfg):
    if cfg.sliding_window and cfg.global_attn_layers:
        f = jnp.zeros((cfg.num_layers,), jnp.bool_)
        return f.at[jnp.array(cfg.global_attn_layers)].set(True)
    return jnp.ones((cfg.num_layers,), jnp.bool_)


def embed_tokens(params, cfg, tokens):
    table = params["embed"] if cfg.tie_embeddings else params["embed_in"]
    return jnp.take(table, tokens, axis=0)


def unembed(params, cfg, h):
    return jnp.einsum(
        "btd,vd->btv", h, params["embed"], preferred_element_type=jnp.float32
    )


def _maybe_gather_weights(lp, layer_specs):
    """FSDP: gather this layer's weights over the data axis (inside the scan
    body -> one small per-layer all-gather; re-gathered under remat)."""
    if layer_specs is None:
        return lp

    def one(w, spec):
        try:
            return jax.lax.with_sharding_constraint(w, spec)
        except (RuntimeError, ValueError, TypeError):
            return w

    return jax.tree.map(one, lp, layer_specs)


def forward(params, cfg, *, tokens=None, embeds=None, remat="full",
            unroll=False, compute_specs=None):
    """Full-sequence forward.  Returns (hidden, aux_loss).

    unroll=True replaces lax.scan with a python layer loop (and full, not
    query-blocked, attention).  Numerically identical; used by the dry-run's
    cost extrapolation because XLA's cost_analysis counts while-loop bodies
    once instead of x trip-count.

    compute_specs: optional pytree of PartitionSpecs ({"layers": ...}) giving
    weight layouts during compute (FSDP per-layer gather; sharding/rules.py).
    """
    x = embed_tokens(params, cfg, tokens) if embeds is None else embeds
    x = x.astype(common.dtype_of(cfg))
    x = shrules.constrain_batch(x)  # pin (B->batch axes, T, d) sharding
    t = x.shape[1]
    positions = jnp.arange(t, dtype=jnp.int32)
    layer_specs = None if compute_specs is None else compute_specs["layers"]

    def body(carry, xs):
        x, aux = carry
        lp, is_global = xs
        lp = _maybe_gather_weights(lp, layer_specs)
        x = shrules.constrain_batch(x)
        x, a, _ = layer_forward(lp, cfg, x, positions, is_global)
        return (shrules.constrain_batch(x), aux + a), None

    if remat == "full":
        body = jax.checkpoint(body, policy=None)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )

    flags = _global_flags(cfg)
    if unroll:
        aux = 0.0
        for i in range(cfg.num_layers):
            (x, aux), _ = body((x, aux), (_layer_slice(params, i), flags[i]))
    else:
        (x, aux), _ = lax.scan(body, (x, 0.0), (params["layers"], flags))
    return common.rms_norm(x, params["ln_f"], cfg.norm_eps), aux


# ------------------------------------------------------- prefill / decode


def _layer_slice(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


def _cache_len(cfg, layer_idx, seq_len):
    if cfg.sliding_window and layer_idx not in cfg.global_attn_layers:
        return min(cfg.sliding_window, seq_len)
    return seq_len


def init_cache(cfg, batch, seq_len):
    """Per-layer decode caches (list; shapes may differ per layer)."""
    dt = common.dtype_of(cfg)
    caches = []
    for i in range(cfg.num_layers):
        c = {}
        if cfg.mixer in ("attention", "hybrid"):
            c["attn"] = attention.init_kv_cache(
                cfg, batch, _cache_len(cfg, i, seq_len), dt
            )
        if cfg.mixer == "mla":
            c["mla"] = attention.init_mla_cache(cfg, batch, seq_len, dt)
        if cfg.mixer in ("ssm", "hybrid"):
            c["ssm"] = ssm.init_ssm_cache(cfg, batch, dt)
        caches.append(c)
    return caches


def layer_is_global(cfg, i) -> bool:
    return (not cfg.sliding_window) or (i in cfg.global_attn_layers)


def decode_embed(params, cfg, tokens):
    """Decode-step embedding.  tokens: (B,) int32 -> (B, 1, d)."""
    x = embed_tokens(params, cfg, tokens[:, None])
    return x.astype(common.dtype_of(cfg))


def _decode_tail(lp, cfg, x):
    """Shared FFN residual of one decode layer."""
    if cfg.moe is not None:
        hh = common.rms_norm(x, lp["ln2"], cfg.norm_eps)
        out, _ = mlp.moe_apply(lp["moe"], cfg, hh)
        return x + out
    if cfg.d_ff > 0:
        hh = common.rms_norm(x, lp["ln2"], cfg.norm_eps)
        return x + mlp.swiglu(lp["mlp"], hh)
    return x


def decode_layer(lp, cfg, c, x, pos, is_global):
    """One layer of decode_step.  Returns (x', new layer cache).

    The serving engine jits this per layer (layer-streaming paging);
    decode_step runs the identical python body under one jit.
    """
    h = common.rms_norm(x, lp["ln1"], cfg.norm_eps)
    nc = {}
    if cfg.mixer == "attention":
        out, nc["attn"] = attention.attention_decode(
            lp["attn"], cfg, c["attn"], h, pos, is_global
        )
    elif cfg.mixer == "mla":
        out, nc["mla"] = attention.mla_decode(lp["mla"], cfg, c["mla"], h,
                                              pos)
    elif cfg.mixer == "ssm":
        out, nc["ssm"] = ssm.ssm_decode(lp["ssm"], cfg, c["ssm"], h)
    elif cfg.mixer == "hybrid":
        a_out, nc["attn"] = attention.attention_decode(
            lp["attn"], cfg, c["attn"], h, pos, is_global
        )
        s_out, nc["ssm"] = ssm.ssm_decode(lp["ssm"], cfg, c["ssm"], h)
        out = 0.5 * (
            common.rms_norm(a_out, lp["ln_ab"], cfg.norm_eps)
            + common.rms_norm(s_out, lp["ln_sb"], cfg.norm_eps)
        )
    x = x + out
    return _decode_tail(lp, cfg, x), nc


def decode_layer_qkv(lp, cfg, x, pos):
    """Pre-norm and q/k/v of an attention layer (k post-rope).

    The serving engine runs an attention layer as three launches, this, a
    cache write that only moves data (``attention.decode_cache_write`` or
    ``paged_cache_write``) and ``decode_layer_attend``, so its dense and
    paged tiers share every launch that computes.
    """
    h = common.rms_norm(x, lp["ln1"], cfg.norm_eps)
    return attention._decode_qkv(lp["attn"], cfg, h, pos)


def decode_layer_attend(lp, cfg, x, q, ck, cv, keep):
    """Attention over a written cache view, its residual, then the FFN."""
    out = attention.decode_attend_out(lp["attn"], q, ck, cv, keep, x.dtype)
    return _decode_tail(lp, cfg, x + out)


def decode_finish(params, cfg, x):
    """Final norm + unembed of a decode step -> (B, V) logits."""
    h = common.rms_norm(x, params["ln_f"], cfg.norm_eps)
    return unembed(params, cfg, h)[:, 0]


def decode_step(params, cfg, caches, tokens, pos):
    """One decode step.  tokens: (B,) int32; pos: scalar int32 position.

    Returns (logits (B, V), new_caches).
    """
    x = decode_embed(params, cfg, tokens)
    new_caches = []
    for i in range(cfg.num_layers):
        lp = _layer_slice(params, i)
        x, nc = decode_layer(lp, cfg, caches[i], x, pos, layer_is_global(cfg, i))
        new_caches.append(nc)
    return decode_finish(params, cfg, x), new_caches


# ------------------------------------------------------------- paged decode


def init_paged_cache(cfg, batch, seq_len, *, block_tokens, pool_blocks=None,
                     map_all=True):
    """Paged decode state: one shared physical KV pool + per-layer tables.

    Returns {"pool": {"k","v"} (P, block_tokens, KV, dh),
             "tables": (L, B, n_logical) int32 (-1 = unmapped),
             "extra": per-layer list of non-paged state (ssm)}.

    map_all=True builds identity tables (every logical block resident) —
    the drop-in dense-cache replacement.  map_all=False starts fully
    unmapped; a host-side allocator (serving/paging.py) assigns slots.
    """
    if cfg.mixer not in ("attention", "hybrid"):
        raise NotImplementedError(
            f"paged KV supports attention/hybrid mixers, not {cfg.mixer!r} "
            "(MLA latent-cache paging is a ROADMAP follow-up)"
        )
    if seq_len % block_tokens:
        raise ValueError(
            f"seq_len={seq_len} not a multiple of block_tokens={block_tokens}"
        )
    n_logical = seq_len // block_tokens
    total = cfg.num_layers * batch * n_logical
    if pool_blocks is None:
        pool_blocks = total
    dt = common.dtype_of(cfg)
    pool = attention.init_paged_kv_pool(cfg, pool_blocks, block_tokens, dt)
    if map_all:
        if pool_blocks < total:
            raise ValueError(
                f"map_all needs pool_blocks >= {total}, got {pool_blocks}"
            )
        tables = jnp.arange(total, dtype=jnp.int32).reshape(
            cfg.num_layers, batch, n_logical
        )
    else:
        tables = jnp.full((cfg.num_layers, batch, n_logical), -1, jnp.int32)
    extra = []
    for _ in range(cfg.num_layers):
        e = {}
        if cfg.mixer == "hybrid":
            e["ssm"] = ssm.init_ssm_cache(cfg, batch, dt)
        extra.append(e)
    return {"pool": pool, "tables": tables, "extra": extra}


def decode_layer_paged(lp, cfg, pool, table, extra, x, pos, is_global):
    """Paged twin of decode_layer.  Returns (x', pool', extra')."""
    h = common.rms_norm(x, lp["ln1"], cfg.norm_eps)
    ne = {}
    if cfg.mixer == "attention":
        out, pool = attention.paged_attention_decode(
            lp["attn"], cfg, pool, table, h, pos, is_global
        )
    elif cfg.mixer == "hybrid":
        a_out, pool = attention.paged_attention_decode(
            lp["attn"], cfg, pool, table, h, pos, is_global
        )
        s_out, ne["ssm"] = ssm.ssm_decode(lp["ssm"], cfg, extra["ssm"], h)
        out = 0.5 * (
            common.rms_norm(a_out, lp["ln_ab"], cfg.norm_eps)
            + common.rms_norm(s_out, lp["ln_sb"], cfg.norm_eps)
        )
    else:
        raise NotImplementedError(cfg.mixer)
    x = x + out
    return _decode_tail(lp, cfg, x), pool, ne


def decode_step_paged(params, cfg, paged, tokens, pos):
    """One decode step over the paged cache (single-graph twin).

    paged: init_paged_cache state.  Tables pass through unchanged — slot
    assignment is host-side; in-graph work is scatter (new token) + gather
    (attention reads) against the shared pool.
    """
    x = decode_embed(params, cfg, tokens)
    pool = paged["pool"]
    new_extra = []
    for i in range(cfg.num_layers):
        lp = _layer_slice(params, i)
        x, pool, ne = decode_layer_paged(
            lp, cfg, pool, paged["tables"][i], paged["extra"][i], x, pos,
            layer_is_global(cfg, i),
        )
        new_extra.append(ne)
    logits = decode_finish(params, cfg, x)
    return logits, {"pool": pool, "tables": paged["tables"],
                    "extra": new_extra}


def prefill(params, cfg, tokens=None, embeds=None, unroll=False,
            compute_specs=None):
    """Prefill: forward pass + last-position logits (serving path).

    Cache materialization for the decode phase is the decode engine's job
    (serving/engine.py feeds tokens through decode_step for correctness at
    small scale); the compiled prefill graph is the roofline object here.
    """
    h, _ = forward(params, cfg, tokens=tokens, embeds=embeds, remat="none",
                   unroll=unroll, compute_specs=compute_specs)
    return unembed(params, cfg, h[:, -1:, :])[:, 0]


# ------------------------------------------------------------------ loss


def loss_fn(params, cfg, batch, remat="full", unroll=False,
            compute_specs=None):
    """Next-token CE (+ MoE aux + z-loss).  batch: tokens or embeds+labels."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    labels = batch.get("labels", tokens)
    h, aux = forward(params, cfg, tokens=tokens, embeds=embeds, remat=remat,
                     unroll=unroll, compute_specs=compute_specs)
    logits = unembed(params, cfg, h)  # fp32
    logits = shrules.constrain_batch(logits, None, "model")  # (B, T, V/mp)
    logits = logits[:, :-1]
    targets = labels[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    ce = jnp.sum((logz - ll) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    z_loss = 1e-4 * jnp.sum(jnp.square(logz) * mask) / jnp.maximum(
        jnp.sum(mask), 1.0
    )
    total = ce + z_loss + aux
    return total, {"loss": total, "ce": ce, "aux": aux, "z": z_loss}
