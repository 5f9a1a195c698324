"""Cross-pod gradient exchange with GPULZ compression (the paper's
inter-node-communication use case, in-graph).

Topology assumption: the pod axis is the slow link (inter-pod DCN/ICI).
Within a pod, gradients reduce in bf16 (XLA-inserted collectives).  Across
pods, each leaf is:

  1. quantized to uint16 codes with a per-leaf symmetric scale
     (+ optional error-feedback accumulator),
  2. GPULZ-compressed in-graph through the pipeline's batched entry point
     (``pipeline.compress_many_chunks`` — all slabs in one dispatch, symbols
     ARE the codes, S=2), pinned pod-local via ``sharding.batch.shard_vmap``
     (shard_map over the pod axis: each pod compresses the shard it already
     owns), into a buffer **capped at the raw-int16 size** so the exchange is
     never worse than 2 bytes/element (2x smaller than bf16+fp32-master
     exchanges, more when the codes compress),
  3. all-gathered over the pod axis (the only inter-pod traffic),
  4. decoded in-graph (tables parsed straight from the received blob) and
     averaged.

When the compressed stream does not fit the cap (incompressible gradients)
the raw uint16 codes are sent instead, signalled by a one-hot flag — the
exchange stays fixed-shape either way, which is what fixed-topology
collectives require.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import format as fmt, pipeline
from repro.core.pipeline import LZSSConfig

# backend/decoder default to "auto": the in-graph compress emits through
# the single-kernel fused-mono compressor (Kernels I+II+III in ONE Pallas
# launch) and the decode dispatches the fused Pallas decoder on TPU;
# unfused xla / xla-parallel elsewhere (core/pipeline.py registry).
# Resolution happens at dispatch time, so importing this module never
# initializes the JAX platform.
GRAD_LZ = LZSSConfig(symbol_size=2, window=32, chunk_symbols=2048,
                     backend="auto")
MIN_COMPRESS_SIZE = 65_536  # leaves below this exchange raw (graph economy)


def quantize_u16(x):
    """Symmetric uint16 quantization.  Returns (codes int32 in [0,65535], scale)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32)), 1e-30) / 32767.0
    codes = jnp.clip(jnp.round(x32 / scale), -32767, 32767).astype(jnp.int32)
    return codes + 32768, scale


def dequantize_u16(codes, scale):
    return (codes.astype(jnp.float32) - 32768.0) * scale


def _pad_to_chunks(codes_flat, c):
    n = codes_flat.shape[0]
    nc = -(-n // c)
    pad = nc * c - n
    return jnp.pad(codes_flat, (0, pad)), nc


SLAB_SYMBOLS = 1 << 24  # 16M symbols (32 MB) per slab: int32-offset safe,
                        # and slabs compress in parallel (vmap)


def _slab_geometry(n: int, cfg: LZSSConfig):
    c = cfg.chunk_symbols
    slab = min(SLAB_SYMBOLS, -(-n // c) * c)
    slab = -(-slab // c) * c
    n_slabs = -(-n // slab)
    return slab, n_slabs


def _cap_bytes(slab: int, ratio_cap: float) -> int:
    """Wire budget per slab: raw-int16 bytes / ratio_cap (>= 1 B/elem)."""
    return max(slab, int(slab * 2 / max(ratio_cap, 1.0)))


def _compress_slabs(padded, cfg, ratio_cap):
    """(n_slabs, slab) int32 codes -> ((n_slabs, cap) u8 payloads, used_lz).

    One batched pipeline dispatch compresses every slab.  Budget =
    2/ratio_cap bytes/element.  If a slab's LZSS container fits, its exchange
    is lossless w.r.t. the uint16 codes; otherwise it degrades to the codes'
    high bytes (int8 precision — error feedback recommended, see
    CompressionConfig).
    """
    n_slabs, slab = padded.shape
    c = cfg.chunk_symbols
    cap = _cap_bytes(slab, ratio_cap)
    blobs, totals = pipeline.compress_many_chunks(
        padded.reshape(n_slabs, slab // c, c), cfg,
        jnp.full((n_slabs,), slab * 2, jnp.int32),
    )
    used_lz = totals <= cap
    if cap >= slab * 2:  # budget fits raw u16: lossless fallback
        fb = jnp.stack(
            [padded & 0xFF, padded >> 8], axis=2
        ).reshape(n_slabs, -1)[:, :cap]
    else:                # tight budget: int8 fallback (high bytes)
        fb = jnp.pad(padded >> 8, ((0, 0), (0, max(0, cap - slab))))[:, :cap]
    payload = jnp.where(
        used_lz[:, None], blobs[:, :cap].astype(jnp.int32), fb
    )
    return payload.astype(jnp.uint8), used_lz


def lossy_grad_config(eb: float, cfg: LZSSConfig = GRAD_LZ) -> LZSSConfig:
    """The error-bounded gradient exchange config (``lossy-fz``, S=4).

    Gradients are f32 element streams to the lossy frontend; the configured
    ``cfg.backend`` becomes the *inner* lossless stage.  Optimizer state and
    checkpoints never use this — they stay lossless (the lossy-gradients /
    lossless-state split of ``CompressionConfig.lossy_eb``).
    """
    inner = "auto" if cfg.backend in ("lossy-fz", "sharded") else cfg.backend
    return dataclasses.replace(
        cfg, symbol_size=4, backend="lossy-fz", decoder="auto",
        lossy_eb=float(eb), lossy_inner=inner,
    )


def _lossy_method_params(lcfg: LZSSConfig) -> tuple:
    """The static (mode, inner_method) pin — known from the config alone,
    so the in-graph decode needs no host-side header parse."""
    mode = (
        fmt.LOSSY_MODE_QUANT if float(lcfg.lossy_eb) > 0.0
        else fmt.LOSSY_MODE_LOSSLESS
    )
    inner = pipeline.container_method(
        pipeline.resolve_backend(lcfg.lossy_inner)
    )
    return (mode, inner)


def _compress_slabs_lossy(g_padded, codes_padded, lcfg, ratio_cap):
    """(n_slabs, slab) f32 grads -> ((n_slabs, cap) u8 payloads, used_lz).

    Same wire budget as the u16 path (2/ratio_cap bytes/element), but a slab
    that fits carries an error-bounded lossy-fz container: max |g' - g| <= eb
    per element (exact for non-finite elements).  A slab whose container
    exceeds the budget degrades to the u16 quantization codes (used_lz=False
    — error scale/2, NOT eb-bounded), keeping the exchange fixed-shape.
    """
    n_slabs, slab = g_padded.shape
    c = lcfg.chunk_symbols
    cap = _cap_bytes(slab, ratio_cap)
    bits = lax.bitcast_convert_type(g_padded.astype(jnp.float32), jnp.int32)
    blobs, totals = pipeline.compress_many_chunks(
        bits.reshape(n_slabs, slab // c, c), lcfg,
        jnp.full((n_slabs,), slab * 4, jnp.int32),
    )
    used_lz = totals <= cap
    if cap >= slab * 2:  # budget fits raw u16 codes
        fb = jnp.stack(
            [codes_padded & 0xFF, codes_padded >> 8], axis=2
        ).reshape(n_slabs, -1)[:, :cap]
    else:                # tight budget: int8 fallback (high bytes)
        fb = jnp.pad(
            codes_padded >> 8, ((0, 0), (0, max(0, cap - slab)))
        )[:, :cap]
    payload = jnp.where(
        used_lz[:, None], blobs[:, :cap].astype(jnp.int32), fb
    )
    return payload.astype(jnp.uint8), used_lz


def _decompress_slabs_lossy(payload, used_lz, slab, lcfg, scale):
    """Inverse of _compress_slabs_lossy -> (n_slabs, slab) f32 gradients."""
    n_slabs, cap = payload.shape
    c = lcfg.chunk_symbols
    nc = slab // c
    # method-2 containers carry no per-chunk tables (held zero); the decode
    # hook reads everything it needs from the blob at static offsets
    zeros = jnp.zeros((n_slabs, nc), jnp.int32)
    syms = pipeline.decompress_many_chunks(
        payload, zeros, zeros,
        symbol_size=4, chunk_symbols=c, n_chunks=nc, decoder="lossy-fz",
        chunks_per_block=lcfg.chunks_per_block,
        method_params=_lossy_method_params(lcfg),
    ).reshape(n_slabs, -1)
    g_lz = lax.bitcast_convert_type(syms, jnp.float32)
    # fallback slabs carry u16 quantization codes (same wire layout as the
    # legacy path's fallback branches)
    p32 = payload.astype(jnp.int32)
    if cap >= slab * 2:
        pairs = p32[:, : slab * 2].reshape(n_slabs, -1, 2)
        codes_fb = pairs[..., 0] | (pairs[..., 1] << 8)
    else:
        hi = jnp.pad(p32, ((0, 0), (0, max(0, slab - cap))))[:, :slab]
        codes_fb = (hi << 8) | 128
    g_fb = dequantize_u16(codes_fb, scale)
    return jnp.where(used_lz[:, None], g_lz, g_fb)


def _decompress_slabs(payload, used_lz, slab, cfg):
    """Inverse of _compress_slabs -> (n_slabs, slab) int32 codes."""
    n_slabs, cap = payload.shape
    c = cfg.chunk_symbols
    nc = slab // c
    # The container's header + tables always fit inside the cap prefix
    # (48 + 8*nc << slab <= cap), so the payload buffer parses in place —
    # no worst-case zero-padding; the section gathers are bounds-checked.
    p32 = payload.astype(jnp.int32)
    n_tokens, payload_sizes = jax.vmap(
        lambda b: fmt.parse_tables_jax(b, nc)
    )(p32)
    syms_lz = pipeline.decompress_many_chunks(
        payload, n_tokens, payload_sizes,
        symbol_size=2, chunk_symbols=c, n_chunks=nc, decoder=cfg.decoder,
        chunks_per_block=cfg.chunks_per_block,
    ).reshape(n_slabs, -1)
    if cap >= slab * 2:  # lossless raw-u16 fallback
        pairs = p32[:, : slab * 2].reshape(n_slabs, -1, 2)
        syms_raw = pairs[..., 0] | (pairs[..., 1] << 8)
    else:                # int8 fallback: centre of the low byte
        hi = jnp.pad(p32, ((0, 0), (0, max(0, slab - cap))))[:, :slab]
        syms_raw = (hi << 8) | 128
    return jnp.where(used_lz[:, None], syms_lz, syms_raw)


def compress_leaf(g, cfg: LZSSConfig = GRAD_LZ, ratio_cap: float = 2.0,
                  lossy_eb=None):
    """Gradient leaf -> fixed-size wire format.

    Returns dict: payload (uint8, 2/ratio_cap bytes/elem), used_lz (bool per
    slab), scale (f32).  Large leaves are slab-split (int32-offset safety +
    parallel compression); slabs whose LZSS container exceeds the budget
    degrade to int8 precision (used_lz=False).

    ``lossy_eb`` (``CompressionConfig.lossy_eb``) switches fitting slabs to
    the error-bounded ``lossy-fz`` path at the SAME wire budget: max
    |g' - g| <= eb per element instead of the u16 quantization's scale/2.
    Fallback slabs still carry the u16 codes either way.
    """
    n = g.size
    codes, scale = quantize_u16(g.reshape(-1))
    slab, n_slabs = _slab_geometry(n, cfg)
    padded = jnp.pad(codes, (0, n_slabs * slab - n)).reshape(n_slabs, slab)
    if lossy_eb is None:
        payload, used_lz = _compress_slabs(padded, cfg, ratio_cap)
    else:
        gp = jnp.pad(
            g.reshape(-1).astype(jnp.float32), (0, n_slabs * slab - n)
        ).reshape(n_slabs, slab)
        payload, used_lz = _compress_slabs_lossy(
            gp, padded, lossy_grad_config(lossy_eb, cfg), ratio_cap
        )
    return {
        "payload": payload.reshape(-1),
        "used_lz": used_lz,
        "scale": scale,
    }


def decompress_leaf(wire, shape, cfg: LZSSConfig = GRAD_LZ,
                    ratio_cap: float = 2.0, lossy_eb=None):
    """Inverse of compress_leaf -> fp32 gradient leaf."""
    n = 1
    for s in shape:
        n *= s
    slab, n_slabs = _slab_geometry(n, cfg)
    cap = _cap_bytes(slab, ratio_cap)
    payload = wire["payload"].reshape(n_slabs, cap)
    if lossy_eb is not None:
        g = _decompress_slabs_lossy(
            payload, wire["used_lz"], slab,
            lossy_grad_config(lossy_eb, cfg), wire["scale"],
        ).reshape(-1)[:n]
        return g.reshape(shape)
    codes = _decompress_slabs(
        payload, wire["used_lz"], slab, cfg
    ).reshape(-1)[:n]
    return dequantize_u16(codes, wire["scale"]).reshape(shape)


def pod_exchange_compressed(grad_stack, mesh, compress: bool = True,
                            cfg: LZSSConfig = GRAD_LZ,
                            ratio_cap: float = 2.0, lossy_eb=None):
    """Average pod-stacked gradients; the pod-axis collective carries only
    compressed bytes.

    ``grad_stack`` leaves have a leading (n_pods,) dim sharded over "pod"
    (produced by vmap-ing the grad computation over a pod-split batch).  Each
    pod's slice is compressed *where it lives* — ``sharding.batch.shard_vmap``
    pins the per-pod compression inside ``shard_map(pod)``, so the
    partitioner cannot choose to replicate the raw gradient first and
    compress everywhere (which would put uncompressed bytes on the slow
    inter-pod links).  The fixed-size wire is then replicated across the pod
    axis (an all-gather of compressed bytes — the only inter-pod traffic),
    and every pod decodes all slices locally and averages.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.sharding import batch as shbatch

    n_pods = mesh.shape["pod"]
    rep = lambda x: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim)))
    )
    # per-pod view: compression must stay pod-local, so a sharded batch
    # config (mesh= / "sharded" keys) resolves to its single-device inner
    # dispatch here — nesting shard_map(pod) inside shard_map would be wrong
    local_cfg = shbatch.unsharded(cfg)

    def exchange_leaf(g):
        shape = g.shape[1:]
        size = 1
        for s in shape:
            size *= s
        if not compress or size < MIN_COMPRESS_SIZE:
            return jnp.mean(rep(g).astype(jnp.float32), axis=0).astype(g.dtype)
        wire = shbatch.shard_vmap(
            lambda x: compress_leaf(x, local_cfg, ratio_cap, lossy_eb),
            mesh, "pod",
        )(g)
        wire = jax.tree.map(rep, wire)  # <- compressed pod all-gather

        def average(wire):
            acc = 0.0
            for k in range(n_pods):
                wk = jax.tree.map(lambda x: x[k], wire)
                acc = acc + decompress_leaf(
                    wk, shape, local_cfg, ratio_cap, lossy_eb
                )
            return (acc / n_pods).astype(g.dtype)

        # every device decodes its full replica of the wire; the explicit
        # map keeps the partitioner from splitting the decode, which it
        # cannot do for a Mosaic kernel
        return jax.shard_map(
            average, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False
        )(wire)

    return jax.tree.map(exchange_leaf, grad_stack)
