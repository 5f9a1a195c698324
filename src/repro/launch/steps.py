"""Step functions (train / prefill / decode) + their sharded jit builders.

These are the objects the dry-run lowers and the launcher executes:

  * ``train_step``            — fwd+bwd+AdamW, optional microbatch accumulation
                                (per-microbatch grads reduce inside the scan —
                                latency-hiding-scheduler friendly).
  * ``train_step_compressed`` — same, but the pod-axis gradient exchange is
                                GPULZ-compressed inside shard_map(pod) —
                                the paper's communication use case.
  * ``prefill_step`` / ``decode_step`` — serving paths.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.models import model as model_lib, transformer
from repro.optim import adamw, grad_compress
from repro.sharding import rules


# ------------------------------------------------------------- train state


def init_train_state(cfg, traincfg, seed: int = 0):
    params = model_lib.init_params(cfg, seed)
    return {
        "params": params,
        "opt": adamw.init_opt_state(params),
        "step": jnp.zeros((), jnp.int32),
    }


def abstract_train_state(cfg, traincfg):
    return jax.eval_shape(functools.partial(init_train_state, cfg, traincfg))


def train_state_shardings(cfg, traincfg, mesh):
    axes = model_lib.param_axes(cfg)
    p_sh = rules.params_shardings(axes, mesh)
    ab = model_lib.abstract_params(cfg)
    if traincfg.zero_opt_state:
        o_sh = rules.zero_shardings(axes, ab, mesh)
    else:
        o_sh = p_sh
    return {
        "params": p_sh,
        "opt": {"m": o_sh, "v": o_sh},
        "step": NamedSharding(mesh, P()),
    }


def batch_shardings(cfg, shape, mesh):
    bs = rules.batch_spec(mesh, shape.global_batch)
    specs = model_lib.input_specs(cfg, shape)
    out = {}
    for k, s in specs.items():
        if k == "pos":
            out[k] = NamedSharding(mesh, P())
        else:
            out[k] = NamedSharding(mesh, P(*(list(bs) + [None] * (len(s.shape) - 1))))
    return out


# ------------------------------------------------------------------- train


def _compute_specs(cfg, traincfg):
    """FSDP per-layer weight-gather specs (None when FSDP is off)."""
    if not fsdp_decision(cfg, traincfg):
        return None
    axes = model_lib.param_axes(cfg)
    return {"layers": rules.compute_specs_tree(axes["layers"], drop_leading=1)}


def _grads_and_metrics(params, cfg, traincfg, batch):
    specs = _compute_specs(cfg, traincfg)
    loss_fn = lambda p, b: transformer.loss_fn(
        p, cfg, b, remat=traincfg.remat, unroll=traincfg.unroll_layers,
        compute_specs=specs,
    )
    if traincfg.microbatches > 1:
        m = traincfg.microbatches
        micro = jax.tree.map(
            lambda x: x.reshape(m, x.shape[0] // m, *x.shape[1:]), batch
        )

        def acc(carry, mb):
            g_acc, l_acc = carry
            (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, mb
            )
            g_acc = jax.tree.map(jnp.add, g_acc, g)
            return (g_acc, l_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
        (g, loss), _ = jax.lax.scan(acc, (zeros, 0.0), micro)
        g = jax.tree.map(lambda x: x / m, g)
        return g, {"loss": loss / m}
    (loss, metrics), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
    return g, metrics


def train_step(state, batch, *, cfg, traincfg):
    grads, metrics = _grads_and_metrics(state["params"], cfg, traincfg, batch)
    new_p, new_opt, opt_metrics = adamw.adamw_update(
        state["params"], grads, state["opt"], state["step"], traincfg
    )
    metrics = {**metrics, **opt_metrics}
    return (
        {"params": new_p, "opt": new_opt, "step": state["step"] + 1},
        metrics,
    )


def train_step_compressed(state, batch, *, cfg, traincfg, mesh):
    """Train step with GPULZ-compressed pod-axis gradient exchange.

    Per-pod gradients come from vmap over a pod-split batch dim (no cross-pod
    reduction in the backward pass); the only inter-pod traffic is the
    all-gather of the fixed-size compressed wire inside
    ``pod_exchange_compressed``.
    """
    n_pods = mesh.shape["pod"]

    def pod_grads(mb):
        return _grads_and_metrics(state["params"], cfg, traincfg, mb)

    # "auto" backend/decoder resolve per-platform inside the pipeline
    # (on TPU: the single-kernel fused-mono compressor + fused Pallas
    # decoder)
    lz_cfg = dataclasses.replace(
        grad_compress.GRAD_LZ,
        backend=traincfg.compression.lz_backend,
        decoder=traincfg.compression.lz_decoder,
    )
    batch_pods = jax.tree.map(
        lambda x: x.reshape(n_pods, x.shape[0] // n_pods, *x.shape[1:]), batch
    )
    batch_pods = jax.lax.with_sharding_constraint(
        batch_pods,
        jax.tree.map(
            lambda x: NamedSharding(
                mesh, P(*(("pod", "data") + (None,) * (x.ndim - 2)))
            ),
            batch_pods,
        ),
    )
    grad_stack, metrics = jax.vmap(pod_grads, spmd_axis_name="pod")(batch_pods)
    grads = grad_compress.pod_exchange_compressed(
        grad_stack, mesh,
        compress=traincfg.compression.grad_cross_pod,
        cfg=lz_cfg,
        ratio_cap=traincfg.compression.grad_ratio_cap,
        # error-bounded lossy gradients (optimizer state stays lossless:
        # adamw_update below sees only the reconstructed f32 gradients)
        lossy_eb=traincfg.compression.lossy_eb,
    )
    new_p, new_opt, opt_metrics = adamw.adamw_update(
        state["params"], grads, state["opt"], state["step"], traincfg
    )
    metrics = jax.tree.map(jnp.mean, metrics)
    metrics = {**metrics, **opt_metrics}
    return (
        {"params": new_p, "opt": new_opt, "step": state["step"] + 1},
        metrics,
    )


# ------------------------------------------------------------------ serve


def prefill_step(params, batch, *, cfg, unroll=False, compute_specs=None):
    return transformer.prefill(
        params, cfg, tokens=batch.get("tokens"), embeds=batch.get("embeds"),
        unroll=unroll, compute_specs=compute_specs,
    )


def decode_step(params, caches, batch, *, cfg):
    logits, caches = transformer.decode_step(
        params, cfg, caches, batch["tokens"], batch["pos"]
    )
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tok, caches


def paged_decode_step(params, paged, batch, *, cfg):
    logits, paged = transformer.decode_step_paged(
        params, cfg, paged, batch["tokens"], batch["pos"]
    )
    next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return next_tok, paged


# ----------------------------------------------------------- jit builders


FSDP_AUTO_THRESHOLD = 25e9  # params; above this, weights must shard on data


def fsdp_decision(cfg, traincfg) -> bool:
    if traincfg.fsdp == "on":
        return True
    if traincfg.fsdp == "off":
        return False
    return cfg.param_count(padded=True) > FSDP_AUTO_THRESHOLD


def _set_mesh_context(mesh, batch_axes=None, fsdp=True, seq_parallel=False):
    """Set the sharding rules up for ``mesh``; returns its Auto-axis view.

    The step builders use the Auto view: the sharding rules pin arrays
    through constraints and let the partitioner propagate the rest,
    whatever axis types the caller's mesh carries.
    """
    mesh = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    if batch_axes is None:
        batch_axes = rules.batch_axes(mesh)
    shards = 1
    for a in batch_axes:
        if a in mesh.shape:
            shards *= mesh.shape[a]
    rules.set_activation_batch_axes(batch_axes, data_shards=shards)
    rules.set_fsdp(fsdp)
    rules.set_seq_parallel(seq_parallel)
    return mesh


def _in_mesh(mesh, fn):
    """Trace ``fn`` with ``mesh`` as the context mesh, so bare-PartitionSpec
    sharding hints resolve against it; nothing process-global is set, so
    other code (a sharded codec dispatch over its own mesh) is unaffected."""

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return fn(*args, **kwargs)

    return run


def make_train_step(cfg, traincfg, mesh, shape, compressed: bool = False):
    """Returns (jitted_fn, state_shardings, batch_shardings)."""
    # compressed step vmaps over the pod axis (spmd_axis_name supplies it);
    # inner activation constraints then use "data" only.
    mesh = _set_mesh_context(
        mesh,
        batch_axes=("data",) if compressed else None,
        fsdp=fsdp_decision(cfg, traincfg),
        seq_parallel=traincfg.seq_parallel,
    )
    st_sh = train_state_shardings(cfg, traincfg, mesh)
    b_sh = batch_shardings(cfg, shape, mesh)
    if compressed:
        fn = functools.partial(
            train_step_compressed, cfg=cfg, traincfg=traincfg, mesh=mesh
        )
        fn = _in_mesh(mesh, fn)
        # shard_map handles its own specs; jit still pins the boundary
        jfn = jax.jit(
            fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None)
        )
    else:
        fn = _in_mesh(mesh, functools.partial(train_step, cfg=cfg, traincfg=traincfg))
        jfn = jax.jit(
            fn,
            in_shardings=(st_sh, b_sh),
            out_shardings=(st_sh, None),
            donate_argnums=(0,),
        )
    return jfn, st_sh, b_sh


def cache_shardings(cfg, batch_size, mesh):
    """KV/state caches: batch on data axis, heads on model axis."""
    bs = rules.batch_spec(mesh, batch_size)
    b0 = bs if len(bs) else P(None)

    def one(path_leaf):
        leaf = path_leaf
        nd = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        if nd >= 3:  # (B, T, heads-ish, ...) or (B, H, N, P)
            spec = list(b0) + [None, "model"] + [None] * (nd - 3)
            return NamedSharding(mesh, P(*spec))
        if nd >= 1:
            return NamedSharding(mesh, P(*([None] * nd)))
        return NamedSharding(mesh, P())

    return one


def make_decode_step(cfg, mesh, shape):
    mesh = _set_mesh_context(mesh)
    b = shape.global_batch
    ab_cache = model_lib.abstract_cache(cfg, b, shape.seq_len)
    sh_fn = cache_shardings(cfg, b, mesh)
    cache_sh = jax.tree.map(sh_fn, ab_cache)
    p_sh = rules.params_shardings(model_lib.param_axes(cfg), mesh)
    bs = rules.batch_spec(mesh, b)
    b_sh = {
        "tokens": NamedSharding(mesh, bs),
        "pos": NamedSharding(mesh, P()),
    }
    fn = _in_mesh(mesh, functools.partial(decode_step, cfg=cfg))
    jfn = jax.jit(
        fn,
        in_shardings=(p_sh, cache_sh, b_sh),
        out_shardings=(b_sh["tokens"], cache_sh),
        donate_argnums=(1,),
    )
    return jfn, p_sh, cache_sh, b_sh


def make_paged_decode_step(cfg, mesh, shape, *, block_tokens,
                           pool_blocks=None):
    """Compiled twin of decode over the paged (block-table) KV cache.

    The physical pool shards its KV-head axis on "model" (same head split as
    the dense cache); block tables are tiny int32 host-authored state and
    stay replicated.  Paged state is donated so the pool updates in place.
    """
    mesh = _set_mesh_context(mesh)
    b = shape.global_batch
    ab = model_lib.abstract_paged_cache(
        cfg, b, shape.seq_len, block_tokens=block_tokens,
        pool_blocks=pool_blocks,
    )
    pool_sh = NamedSharding(mesh, P(None, None, "model", None))
    repl = NamedSharding(mesh, P())
    paged_sh = {
        "pool": {"k": pool_sh, "v": pool_sh},
        "tables": repl,
        "extra": jax.tree.map(lambda _: repl, ab["extra"]),
    }
    p_sh = rules.params_shardings(model_lib.param_axes(cfg), mesh)
    bs = rules.batch_spec(mesh, b)
    b_sh = {
        "tokens": NamedSharding(mesh, bs),
        "pos": NamedSharding(mesh, P()),
    }
    fn = _in_mesh(mesh, functools.partial(paged_decode_step, cfg=cfg))
    jfn = jax.jit(
        fn,
        in_shardings=(p_sh, paged_sh, b_sh),
        out_shardings=(b_sh["tokens"], paged_sh),
        donate_argnums=(1,),
    )
    return jfn, p_sh, paged_sh, b_sh


def make_prefill_step(cfg, mesh, shape, unroll=False):
    mesh = _set_mesh_context(mesh)
    p_sh = rules.params_shardings(model_lib.param_axes(cfg), mesh)
    b_sh = batch_shardings(cfg, shape, mesh)
    specs = {"layers": rules.compute_specs_tree(
        model_lib.param_axes(cfg)["layers"], drop_leading=1)}
    fn = _in_mesh(mesh, functools.partial(prefill_step, cfg=cfg, unroll=unroll,
                                          compute_specs=specs))
    logits_sh = NamedSharding(mesh, rules.batch_spec(mesh, shape.global_batch))
    jfn = jax.jit(fn, in_shardings=(p_sh, b_sh), out_shardings=logits_sh)
    return jfn, p_sh, b_sh
