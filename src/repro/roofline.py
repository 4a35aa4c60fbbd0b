"""Roofline analysis from compiled XLA artifacts.

Per-device HLO FLOPs / bytes from the compiled program's HLO text plus
per-device collective bytes parsed out of the (SPMD-partitioned) HLO.  Three
terms, each against the peaks of the target chip (:data:`PEAKS`, keyed by
``jax.Device.device_kind``):

    compute    = HLO_FLOPs / peak bf16 FLOP/s
    memory     = HLO_bytes / HBM bandwidth
    collective = ring-model link bytes / link bandwidth

Estimated step time = max(terms) (classic roofline).  Collective byte model
per op (g = participating group size, sz = per-device result bytes):
    all-gather         sz * (g-1)/g
    reduce-scatter     sz * (g-1)          (operand is g * result)
    all-reduce         2 * sz * (g-1)/g    (RS + AG phases)
    all-to-all         sz * (g-1)/g
    collective-permute sz
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional


@dataclass(frozen=True)
class ChipPeaks:
    """Published peak rates of one chip."""

    flops_bf16: float            # FLOP/s
    hbm_bw: float                # bytes/s
    link_bw: float               # bytes/s per chip-to-chip link
    hbm_bytes: float             # device memory
    source: str


#: peaks per chip, keyed by ``jax.Device.device_kind``.  A kind missing here
#: is an error (:func:`peaks_for`), never a default.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12, hbm_bw=819e9, link_bw=50e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip "
               "interconnect (taken as 4 links of 50 GB/s)"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    """The peak table entry of ``device_kind``; raises for a kind with no
    recorded peaks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates recorded for device kind "
                       f"{device_kind!r}; known kinds: {sorted(PEAKS)}"
                       ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_RE = re.compile(
    r"(?P<dtype>\w+)\[(?P<dims>[\d,]*)\][^=]*?=\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_TUPLE_COLLECTIVE_RE = re.compile(
    r"=\s*\((?P<types>[^)]*)\)\s*"
    r"(?P<op>all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_GROUPS_V1_RE = re.compile(r"replica_groups=\{(?P<body>[^}]*(?:\{[^}]*\}[^}]*)*)\}")
_GROUPS_V2_RE = re.compile(r"replica_groups=\[(?P<ng>\d+),(?P<gs>\d+)\]")
_TYPE_RE = re.compile(r"(?P<dtype>\w+)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    bpe = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * bpe


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_V2_RE.search(line)
    if m:
        return int(m.group("gs"))
    m = _GROUPS_V1_RE.search(line)
    if m:
        body = m.group("body")
        first = body.split("}")[0].lstrip("{")
        ids = [x for x in first.split(",") if x.strip() != ""]
        if ids:
            return len(ids)
    return default


@dataclass
class CollectiveOp:
    op: str
    result_bytes: int
    group_size: int
    line: str

    @property
    def link_bytes(self) -> float:
        g, sz = max(self.group_size, 1), self.result_bytes
        if g <= 1:
            return 0.0
        if self.op == "all-gather":
            return sz * (g - 1) / g
        if self.op == "reduce-scatter":
            return sz * (g - 1)
        if self.op == "all-reduce":
            return 2.0 * sz * (g - 1) / g
        if self.op == "all-to-all":
            return sz * (g - 1) / g
        return float(sz)  # collective-permute


def parse_collectives(hlo_text: str, n_devices: int) -> list[CollectiveOp]:
    ops: list[CollectiveOp] = []
    for line in hlo_text.splitlines():
        if "-start" in line:  # avoid double counting async pairs (-start/-done)
            continue
        m = _COLLECTIVE_RE.search(line)
        if m:
            sz = _shape_bytes(m.group("dtype"), m.group("dims"))
            ops.append(CollectiveOp(m.group("op"), sz,
                                    _group_size(line, n_devices), line.strip()[:160]))
            continue
        m = _TUPLE_COLLECTIVE_RE.search(line)
        if m:
            sz = sum(_shape_bytes(t.group("dtype"), t.group("dims"))
                     for t in _TYPE_RE.finditer(m.group("types")))
            ops.append(CollectiveOp(m.group("op"), sz,
                                    _group_size(line, n_devices), line.strip()[:160]))
    return ops


@dataclass
class Roofline:
    flops: float                 # per-device HLO flops
    hbm_bytes: float             # per-device bytes accessed
    collective_bytes: float      # per-device link bytes (ring model)
    n_devices: int
    peaks: ChipPeaks
    collectives: list[CollectiveOp] = field(default_factory=list)
    model_flops: float = 0.0     # 6*N*D useful flops (per device)
    histogram: dict = field(default_factory=dict)      # op@group -> stats
    by_computation: dict = field(default_factory=dict)  # hot-spot breakdown

    @property
    def compute_s(self) -> float:
        return self.flops / self.peaks.flops_bf16

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / self.peaks.link_bw

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return (self.model_flops / self.flops) if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """MODEL_FLOPS / (step_s * peak) — the MFU-style score."""
        if self.step_s <= 0:
            return 0.0
        return self.model_flops / (self.step_s * self.peaks.flops_bf16)

    def summary(self) -> dict:
        return {
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "step_s": self.step_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "n_collectives": len(self.collectives),
        }


def analyze(compiled, hlo_text: Optional[str] = None, n_devices: int = 1,
            model_flops_global: float = 0.0, *,
            device_kind: str) -> Roofline:
    """Build a Roofline from a compiled executable, against the peaks of
    ``device_kind`` (the chip the program was compiled for).

    Primary source: our HLO-text analyzer (``repro.hlo_analysis``) over
    ``compiled.as_text()`` — it applies while-loop trip-count multipliers
    that ``cost_analysis()`` lacks, and extracts per-collective link bytes.
    ``cost_analysis()`` is kept as a cross-check (recorded by callers).
    """
    from repro import hlo_analysis as ha
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hc = ha.analyze_hlo(text, n_devices)
    cols = [CollectiveOp(op, rb, g, "") for (op, rb, g, lb, mult) in hc.collectives
            for _ in range(max(int(mult), 1))] if len(hc.collectives) < 512 else []
    return Roofline(
        flops=hc.flops,
        hbm_bytes=hc.bytes,
        collective_bytes=hc.link_bytes,
        n_devices=n_devices,
        peaks=peaks_for(device_kind),
        collectives=cols,
        model_flops=model_flops_global / max(n_devices, 1),
        histogram=hc.collective_histogram(),
        by_computation=hc.by_computation,
    )


def model_flops_train(n_params_active: int, n_tokens: int) -> float:
    """6*N*D: fwd 2ND + bwd 4ND."""
    return 6.0 * n_params_active * n_tokens


def model_flops_infer(n_params_active: int, n_tokens: int) -> float:
    return 2.0 * n_params_active * n_tokens
