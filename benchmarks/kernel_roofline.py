"""Roofline analysis for the GPULZ Pallas kernel itself (per TPU kind).

The matching kernel is VPU (vector-unit) work — equality compares and integer
doubling recurrences, no MXU formulation exists (DESIGN.md §2).  Terms:

  compute:  ops/symbol = W * (c_eq + 4*levels + c_sel)  on 8x128 int lanes
            VPU peak ~= 8*128 lanes * 4 ALUs * 0.94 GHz ~= 3.85e12 op/s
  memory:   fused Kernel I streams x once (4 B/sym as i32) and writes
            len/off (8 B/sym): ~12 B/sym  ->  819/12e-9 = 68 G sym/s bound
  => compute-bound everywhere; the S-knob (multi-byte symbols) divides the
     per-BYTE cost by S — exactly the paper's throughput argument.

The unfused XLA pipeline (paper workflow (c)) additionally materializes the
equality/run-length intermediates in HBM each of the W iterations; its
bytes/symbol come from cost_analysis of compress_chunks, giving the
fused-vs-unfused comparison (paper Fig. 4 (c) vs (d)) quantitatively.
"""

from __future__ import annotations


from benchmarks.common import emit
from repro.launch.roofline import DRYRUN_DEVICE_KIND, device_peaks

# Vector-unit integer throughput per chip, keyed like DEVICE_PEAKS.  Not a
# published figure: derived as 8x128 lanes x 4 ALUs x clock (0.94 GHz on
# v5e).
VPU_OPS = {"TPU v5 lite": 3.85e12}


def levels_for(window: int) -> int:
    cap = min(window, 255)
    k = 0
    while (1 << k) < cap:
        k += 1
    return k


def kernel_ops_per_symbol(window: int) -> float:
    """Fused-kernel vector ops per symbol (matching phase; selection ~O(1))."""
    return window * (2 + 4 * levels_for(window) + 5)


def analytic(run_xla_comparison: bool = True, device_kind=DRYRUN_DEVICE_KIND):
    hbm_bw = device_peaks(device_kind).hbm_bw  # raises for an unknown kind
    vpu_ops = VPU_OPS[device_kind]
    print(f"# kernel_roofline ({device_kind}): name,us_per_call,derived")
    for w in (32, 64, 128, 255):
        ops = kernel_ops_per_symbol(w)
        sym_s = vpu_ops / ops
        for s in (1, 2, 4):
            gbs = sym_s * s / 1e9
            emit(f"kernel/analytic/W{w}/S{s}", 0.0,
                 f"{gbs:.2f}GB/s-compute-bound")
        mem_bound = hbm_bw / 12 / 1e9
        emit(f"kernel/analytic/W{w}/mem-bound", 0.0,
             f"{mem_bound:.1f}Gsym/s (not binding: {sym_s/1e9:.2f}G compute)")

    if not run_xla_comparison:
        return
    # unfused XLA pipeline bytes/flops per symbol via cost_analysis
    import jax
    import jax.numpy as jnp
    from repro.core import lzss

    nc, c = 64, 2048
    cfg = lzss.LZSSConfig(symbol_size=2, window=64, chunk_symbols=c)
    lowered = jax.jit(
        lambda x: lzss.compress_chunks(x, cfg)
    ).lower(jax.ShapeDtypeStruct((nc, c), jnp.int32))
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    n = nc * c
    flops_sym = cost.get("flops", 0) / n
    bytes_sym = cost.get("bytes accessed", 0) / n
    emit("kernel/xla-unfused/W64/flops-per-symbol", 0.0, f"{flops_sym:.0f}")
    emit("kernel/xla-unfused/W64/bytes-per-symbol", 0.0, f"{bytes_sym:.0f}")
    fused_bytes = 12.0
    emit("kernel/fused-vs-unfused/hbm-reduction", 0.0,
         f"{bytes_sym / fused_bytes:.0f}x")


if __name__ == "__main__":
    analytic()
