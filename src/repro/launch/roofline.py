"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), all in seconds-per-step:
    compute    = HLO_FLOPs / (chips x peak)
    memory     = HLO_bytes / (chips x hbm_bw)
    collective = collective_bytes / (chips x link_bw)

The peaks come from ``DEVICE_PEAKS``, keyed by ``jax.Device.device_kind``;
a kind missing from the table is an error, never a default.

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (per-device on
the SPMD-partitioned module; x chips = global).  collective_bytes is parsed
from ``compiled.as_text()``: the sum of operand sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute instruction.
"""

from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    bf16_flops: float  # FLOP/s per chip
    int8_ops: float  # OP/s per chip
    hbm_bytes: float  # bytes of HBM per chip
    hbm_bw: float  # bytes/s per chip
    link_bw: float  # bytes/s per ICI link


# Published per-chip peaks.  TPU v5e: Google Cloud documentation, "TPU
# v5e" — 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600
# Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s each).
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12,
        int8_ops=393e12,
        hbm_bytes=16e9,
        hbm_bw=819e9,
        link_bw=1600e9 / 8 / 4,
    ),
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"  # the production meshes' chip


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks for one ``device_kind``; raises for a kind not in the table."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes by collective kind (+ 'total', 'count')."""
    out = {k: 0 for k in _COLLECTIVES}
    count = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # match "<result> = <shape> <op>(<operand shapes...>)"
        m = re.match(r"%?[\w.\-]+\s*=\s*(.+)$", s)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for k in _COLLECTIVES:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                op = k
                break
        if op is None:
            continue
        if re.search(rf"\b{op}-done\(", rhs):
            continue  # counted at -start
        # operand shapes are inside the call parens
        call = rhs.split("(", 1)
        operands = call[1] if len(call) > 1 else ""
        nbytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(operands)
        )
        if nbytes == 0:  # fall back to result shape
            nbytes = sum(
                _shape_bytes(d, dims)
                for d, dims in _SHAPE_RE.findall(call[0])[:1]
            )
        out[op] += nbytes
        count += 1
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    out["count"] = count
    return out


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    model_flops: float           # 6ND / 2ND analytic, global
    coll_breakdown: dict
    device_kind: str = DRYRUN_DEVICE_KIND

    @property
    def peaks(self) -> DevicePeaks:
        return device_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.bf16_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_device / self.peaks.link_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achieved-model-FLOPs fraction of peak if the step ran at its
        dominant-term time (the dry-run analogue of MFU)."""
        t = self.bound_s
        if t <= 0:
            return 0.0
        return self.model_flops / (self.chips * self.peaks.bf16_flops * t)

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N*D train, 2*N*D inference (N active)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    # decode: one token per sequence (+ cache attention, excluded from 2ND)
    return 2.0 * n_active * shape.global_batch


def from_compiled(arch, shape_name, mesh_name, chips, cost, hlo_text,
                  model_flops) -> Roofline:
    coll = collective_bytes(hlo_text)
    return Roofline(
        arch=arch,
        shape=shape_name,
        mesh=mesh_name,
        chips=chips,
        flops_per_device=float(cost.get("flops", 0.0)),
        bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        coll_bytes_per_device=float(coll["total"]),
        model_flops=model_flops,
        coll_breakdown=coll,
    )
