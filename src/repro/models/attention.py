"""Attention mixers: GQA (with optional sliding window + qk-norm) and
DeepSeek-V2 MLA (expanded for training, absorbed for decode).

Long-sequence forward passes block over queries (lax.scan over q-blocks) so
the (B, H, T, T) score tensor never materializes — peak attention memory is
(B, H, q_block, T) per layer under remat.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import common

NEG_INF = -1e30
Q_BLOCK = 512  # block queries above this sequence length (fp32-score budget)
UNROLL_BLOCKS = False  # dry-run cost mode: python loop over q-blocks so
                       # cost_analysis counts every block (see dryrun.py)


# --------------------------------------------------------------------- GQA


def init_attention(key, cfg):
    d, h, kv, dh = cfg.d_model, cfg.padded_heads, cfg.padded_kv_heads, cfg.hd
    dt = common.dtype_of(cfg)
    ks = common.split_keys(key, 4)
    params = {
        "wq": common.dense_init(ks[0], (d, h, dh), dt, in_axis_size=d),
        "wk": common.dense_init(ks[1], (d, kv, dh), dt, in_axis_size=d),
        "wv": common.dense_init(ks[2], (d, kv, dh), dt, in_axis_size=d),
        "wo": common.dense_init(ks[3], (h, dh, d), dt, in_axis_size=h * dh),
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _mask(q_pos, k_pos, is_global, window):
    """Causal (+ optional sliding-window) mask; is_global may be traced."""
    causal = q_pos[:, None] >= k_pos[None, :]
    if window:
        in_window = (q_pos[:, None] - k_pos[None, :]) < window
        keep = causal & (is_global | in_window)
    else:
        keep = causal
    return keep


def _attend(q, k, v, q_pos, k_pos, is_global, window):
    """q: (B,Tq,H,dh)  k,v: (B,Tk,KV,dh)  ->  (B,Tq,H,dh)."""
    b, tq, h, dh = q.shape
    kvh = k.shape[2]
    group = h // kvh
    scale = dh ** -0.5
    qg = q.reshape(b, tq, kvh, group, dh)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) * scale
    keep = _mask(q_pos, k_pos, is_global, window)
    scores = jnp.where(keep[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(b, tq, h, dh)


def attention_forward(params, cfg, x, positions, is_global=True):
    """Training/prefill attention.  Returns (out, (k, v)) — kv for caching."""
    b, t, _ = x.shape
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qk_norm:
        q = common.qk_head_norm(q, cfg.norm_eps)
        k = common.qk_head_norm(k, cfg.norm_eps)
    q = common.apply_rope(q, positions, cfg.rope_theta)
    k = common.apply_rope(k, positions, cfg.rope_theta)

    if t <= Q_BLOCK:
        out = _attend(q, k, v, positions, positions, is_global,
                      cfg.sliding_window)
    else:
        nb = t // Q_BLOCK
        qb = q.reshape(b, nb, Q_BLOCK, *q.shape[2:])
        pb = positions.reshape(nb, Q_BLOCK)
        if UNROLL_BLOCKS:
            outs = jnp.stack([
                _attend(qb[:, i], k, v, pb[i], positions, is_global,
                        cfg.sliding_window)
                for i in range(nb)
            ])
        else:
            def body(_, xs):
                qi, pi = xs
                o = _attend(qi, k, v, pi, positions, is_global,
                            cfg.sliding_window)
                return None, o

            _, outs = lax.scan(body, None, (qb.swapaxes(0, 1), pb))
        out = outs.swapaxes(0, 1).reshape(b, t, *q.shape[2:])
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), (k, v)


def init_kv_cache(cfg, batch, cache_len, dtype):
    kv = cfg.padded_kv_heads
    cache = {
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
    }
    if cfg.kv_quant:
        # int8 cache + per (token, head) scales: ~2x less HBM per decode
        # step read (the decode cells' dominant roofline term)
        cache["k"] = jnp.zeros((batch, cache_len, kv, cfg.hd), jnp.int8)
        cache["v"] = jnp.zeros((batch, cache_len, kv, cfg.hd), jnp.int8)
        cache["k_scale"] = jnp.zeros((batch, cache_len, kv), jnp.float32)
        cache["v_scale"] = jnp.zeros((batch, cache_len, kv), jnp.float32)
    else:
        cache["k"] = jnp.zeros((batch, cache_len, kv, cfg.hd), dtype)
        cache["v"] = jnp.zeros((batch, cache_len, kv, cfg.hd), dtype)
    return cache


def _quantize_kv(x):
    """(B, T, KV, dh) -> (int8 codes, (B, T, KV) scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-20)
    codes = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return codes, scale


def _dequantize_kv(codes, scale, dtype):
    return (codes.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _decode_qkv(params, cfg, x, pos):
    """Shared decode-side projections: q/k/v with qk-norm + rope applied.

    k comes back post-rope — both the dense and the paged cache store it
    that way, so a restored block never needs re-roping.
    """
    b = x.shape[0]
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if cfg.qk_norm:
        q = common.qk_head_norm(q, cfg.norm_eps)
        k = common.qk_head_norm(k, cfg.norm_eps)
    posv = jnp.full((b, 1), pos, jnp.int32)
    q = common.apply_rope(q, posv, cfg.rope_theta)
    k = common.apply_rope(k, posv, cfg.rope_theta)
    return q, k, v


def _decode_attend(q, ck, cv, keep, out_dtype):
    """GQA single-token attention over a gathered cache view.

    q: (B,1,H,dh); ck/cv: (B,S,KV,dh); keep broadcasts against the
    (B,KV,G,S) score tensor.  Masked slots hit NEG_INF before the softmax,
    so their probability underflows to exactly 0.0 — whatever bytes sit in
    an unmapped cache slot contribute exactly nothing to the output.
    """
    b, _, h, dh = q.shape
    kvh = ck.shape[2]
    group = h // kvh
    qg = q.reshape(b, kvh, group, dh)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qg, ck, preferred_element_type=jnp.float32
    ) * (dh ** -0.5)
    scores = jnp.where(keep, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    return jnp.einsum("bkgs,bskd->bkgd", probs, cv).reshape(b, 1, h, dh)


def attention_decode(params, cfg, cache, x, pos, is_global=True):
    """Single-token decode with (ring-buffered, for SWA) KV cache.

    x: (B, 1, d); pos: scalar int32 (current absolute position).
    """
    q, k, v = _decode_qkv(params, cfg, x, pos)  # k stored post-rope
    ck, cv, keep, new_cache = decode_cache_write(cfg, cache, k, v, pos, is_global)
    return decode_attend_out(params, q, ck, cv, keep, x.dtype), new_cache


def decode_cache_write(cfg, cache, k, v, pos, is_global=True):
    """Dense-cache half of ``attention_decode``: store the new token's k/v.

    Returns the attended view (ck, cv), its (B, 1, 1, S) keep mask and the
    new cache.  Pure data movement (plus the int8 dequantize when
    ``kv_quant``): the serving engine launches it between the shared
    ``_decode_qkv`` and ``decode_attend_out`` launches, so the dense and
    paged tiers differ only in exact moves.
    """
    cache_len = cache["k"].shape[1]
    slot = pos % cache_len  # ring buffer (identity when cache covers all pos)
    new_cache = {}
    if cfg.kv_quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        ckq = lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=1)
        cvq = lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=1)
        cks = lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, slot,
                                              axis=1)
        cvs = lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, slot,
                                              axis=1)
        new_cache.update(k=ckq, v=cvq, k_scale=cks, v_scale=cvs)
        ck = _dequantize_kv(ckq, cks, k.dtype)
        cv = _dequantize_kv(cvq, cvs, v.dtype)
    else:
        ck = lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        cv = lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        new_cache.update(k=ck, v=cv)
    spos = lax.dynamic_update_slice_in_dim(
        cache["slot_pos"], jnp.full((1,), pos, jnp.int32), slot, axis=0
    )
    new_cache["slot_pos"] = spos

    valid = (spos >= 0) & (spos <= pos)
    if cfg.sliding_window:
        in_win = (pos - spos) < cfg.sliding_window
        valid = valid & (is_global | in_win)
    keep = jnp.broadcast_to(valid, (k.shape[0], 1, 1, cache_len))
    return ck, cv, keep, new_cache


def decode_attend_out(params, q, ck, cv, keep, out_dtype):
    """Attention over a cache view, then the output projection."""
    out = _decode_attend(q, ck, cv, keep, out_dtype)
    return jnp.einsum("bthk,hkd->btd", out, params["wo"])


# --------------------------------------------------------------- paged GQA


def init_paged_kv_pool(cfg, pool_blocks, block_tokens, dtype):
    """Physical KV block pool shared by every layer and sequence.

    Slots are (block_tokens, KV, dh) tiles addressed by per-(layer, seq)
    block tables; a slot's contents are garbage until a table maps it.
    """
    if cfg.kv_quant:
        raise NotImplementedError(
            "paged KV does not support kv_quant (int8 cache); "
            "use the dense cache or disable kv_quant"
        )
    kv = cfg.padded_kv_heads
    return {
        "k": jnp.zeros((pool_blocks, block_tokens, kv, cfg.hd), dtype),
        "v": jnp.zeros((pool_blocks, block_tokens, kv, cfg.hd), dtype),
    }


def paged_attention_decode(params, cfg, pool, table, x, pos, is_global=True):
    """Single-token decode reading K/V through a block table.

    pool: {"k","v"} of (P, block_tokens, KV, dh); table: (B, n_logical)
    int32 physical slot ids, -1 = unmapped.  The block holding ``pos`` must
    be mapped (the host allocator guarantees it).  Writes the new token into
    its slot, then attends over the gathered logical view; unmapped or
    future slots mask to exactly zero probability, so stale pool contents
    never reach the output (decode_attend masks pre-softmax at NEG_INF).
    """
    q, k, v = _decode_qkv(params, cfg, x, pos)  # k stored post-rope
    ck, cv, keep, pool = paged_cache_write(cfg, pool, table, k, v, pos, is_global)
    return decode_attend_out(params, q, ck, cv, keep, x.dtype), pool


def paged_cache_write(cfg, pool, table, k, v, pos, is_global=True):
    """Paged twin of ``decode_cache_write``: store the new token's k/v in
    its slot and gather the logical view through the block table."""
    b = k.shape[0]
    bt = pool["k"].shape[1]
    n_logical = table.shape[1]
    kvh, dh = pool["k"].shape[2], pool["k"].shape[3]
    phys = table[jnp.arange(b), pos // bt]
    kp = pool["k"].at[phys, pos % bt].set(k[:, 0])
    vp = pool["v"].at[phys, pos % bt].set(v[:, 0])

    safe = jnp.maximum(table, 0)  # gather through slot 0 for unmapped rows
    ck = kp[safe].reshape(b, n_logical * bt, kvh, dh)
    cv = vp[safe].reshape(b, n_logical * bt, kvh, dh)
    t_idx = jnp.arange(n_logical * bt)  # logical slot index == position
    valid = jnp.repeat(table >= 0, bt, axis=1) & (t_idx <= pos)[None]
    if cfg.sliding_window:
        in_win = (pos - t_idx) < cfg.sliding_window
        valid = valid & (is_global | in_win[None])
    return ck, cv, valid[:, None, None, :], {"k": kp, "v": vp}


# --------------------------------------------------------------------- MLA


def init_mla(key, cfg):
    m = cfg.mla
    d, h = cfg.d_model, cfg.padded_heads
    dt = common.dtype_of(cfg)
    ks = common.split_keys(key, 6)
    qk_dim = m.qk_nope_dim + m.qk_rope_dim
    params = {
        "wdq": common.dense_init(ks[0], (d, m.q_lora_rank), dt),
        "wuq": common.dense_init(ks[1], (m.q_lora_rank, h, qk_dim), dt,
                                 in_axis_size=m.q_lora_rank),
        "wdkv": common.dense_init(ks[2], (d, m.kv_lora_rank), dt),
        "wkr": common.dense_init(ks[3], (d, m.qk_rope_dim), dt),
        "wukv": common.dense_init(
            ks[4], (m.kv_lora_rank, h, m.qk_nope_dim + m.v_head_dim), dt,
            in_axis_size=m.kv_lora_rank),
        "wo": common.dense_init(ks[5], (h, m.v_head_dim, d), dt,
                                in_axis_size=h * m.v_head_dim),
    }
    axes = {
        "wdq": ("embed", "lora"),
        "wuq": ("lora", "heads", "head_dim"),
        "wdkv": ("embed", "lora"),
        "wkr": ("embed", "head_dim"),
        "wukv": ("lora", "heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def mla_forward(params, cfg, x, positions, is_global=True):
    """Training/prefill MLA (expanded form). Returns (out, (c_kv, k_rope))."""
    m = cfg.mla
    b, t, _ = x.shape
    cq = jnp.einsum("btd,dr->btr", x, params["wdq"])
    q = jnp.einsum("btr,rhk->bthk", cq, params["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = common.apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    c_kv = jnp.einsum("btd,dr->btr", x, params["wdkv"])
    k_rope = jnp.einsum("btd,dr->btr", x, params["wkr"])[:, :, None, :]
    k_rope = common.apply_rope(k_rope, positions, cfg.rope_theta)
    kv = jnp.einsum("btr,rhk->bthk", c_kv, params["wukv"])
    k_nope, v = jnp.split(kv, [m.qk_nope_dim], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (*k_nope.shape[:3], m.qk_rope_dim))],
        axis=-1,
    )

    b_, t_ = x.shape[:2]
    if t_ <= Q_BLOCK:
        out = _attend_mha(q, k, v, positions, positions)
    else:
        nb = t_ // Q_BLOCK
        qb = q.reshape(b_, nb, Q_BLOCK, *q.shape[2:]).swapaxes(0, 1)
        pb = positions.reshape(nb, Q_BLOCK)
        if UNROLL_BLOCKS:
            outs = jnp.stack(
                [_attend_mha(qb[i], k, v, pb[i], positions)
                 for i in range(nb)]
            )
        else:
            def body(_, xs):
                qi, pi = xs
                return None, _attend_mha(qi, k, v, pi, positions)

            _, outs = lax.scan(body, None, (qb, pb))
        out = outs.swapaxes(0, 1).reshape(b_, t_, *q.shape[2:3], m.v_head_dim)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, (c_kv, k_rope[:, :, 0, :])


def _attend_mha(q, k, v, q_pos, k_pos):
    dh = q.shape[-1]
    scores = jnp.einsum(
        "bthd,bshd->bhts", q, k, preferred_element_type=jnp.float32
    ) * (dh ** -0.5)
    keep = q_pos[:, None] >= k_pos[None, :]
    scores = jnp.where(keep[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


def init_mla_cache(cfg, batch, cache_len, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_dim), dtype),
    }


def mla_decode(params, cfg, cache, x, pos, is_global=True):
    """Absorbed single-token MLA decode: attention in the latent space.

    The up-projections fold into the query/output (DeepSeek-V2 §2.1.2), so the
    cache stays (kv_lora + rope_dim) per token — this is why MLA decode reads
    ~9x fewer cache bytes than GQA at kv=128 heads.
    """
    m = cfg.mla
    b = x.shape[0]
    cq = jnp.einsum("btd,dr->btr", x, params["wdq"])
    q = jnp.einsum("btr,rhk->bthk", cq, params["wuq"])
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    posv = jnp.full((b, 1), pos, jnp.int32)
    q_rope = common.apply_rope(q_rope, posv, cfg.rope_theta)

    c_kv_new = jnp.einsum("btd,dr->btr", x, params["wdkv"])
    k_rope_new = jnp.einsum("btd,dr->btr", x, params["wkr"])[:, :, None, :]
    k_rope_new = common.apply_rope(k_rope_new, posv, cfg.rope_theta)[:, :, 0, :]

    c_kv = lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv_new, pos, axis=1)
    k_rope = lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope_new, pos, axis=1
    )

    wuk = params["wukv"][..., : m.qk_nope_dim]      # (r, h, nope)
    wuv = params["wukv"][..., m.qk_nope_dim:]       # (r, h, v)
    q_abs = jnp.einsum("bthk,rhk->bthr", q_nope, wuk)  # latent-space query
    scale = (m.qk_nope_dim + m.qk_rope_dim) ** -0.5
    scores = (
        jnp.einsum("bthr,bsr->bhts", q_abs, c_kv,
                   preferred_element_type=jnp.float32)
        + jnp.einsum("bthk,bsk->bhts", q_rope, k_rope,
                     preferred_element_type=jnp.float32)
    ) * scale
    t_idx = jnp.arange(c_kv.shape[1])
    scores = jnp.where((t_idx <= pos)[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    ctx = jnp.einsum("bhts,bsr->bthr", probs, c_kv)
    out = jnp.einsum("bthr,rhk->bthk", ctx, wuv)
    y = jnp.einsum("bthk,hkd->btd", out, params["wo"])
    return y, {"c_kv": c_kv, "k_rope": k_rope}
