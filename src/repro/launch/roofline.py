"""Roofline analysis from compiled dry-run artifacts.

Hardware model: the per-chip peaks of :data:`PEAKS`, keyed by the
``device_kind`` JAX reports. The dry-run targets a TPU v5e pod
(:data:`TARGET_KIND`).

``compiled.cost_analysis()`` on a GSPMD-partitioned module reports PER-DEVICE
flops / bytes (verified empirically), so the three terms are

    compute    = flops / peak
    memory     = bytes_accessed / hbm_bw
    collective = collective_bytes / link_bw

collective_bytes is not in cost_analysis: we parse the partitioned HLO and sum
the *result* bytes of every collective op (per-device received bytes — the
bytes that traverse the links into a chip, the right operand for a per-link
roofline; async start/done pairs counted once).
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM B/s per chip
    link_bw: float    # B/s per ICI link


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# TPU v5e (reported as "TPU v5 lite"): Google Cloud documentation, "TPU v5e"
# — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s ICI per chip over 4 links.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=1600e9 / 8 / 4),
}
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; a device not in :data:`PEAKS` is an
    error, not a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; have {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

# "%name = <result-type(s)> <op>(...)" — op must directly precede '('.
_COLL_RE = re.compile(
    r"=\s+(?P<ty>[^=]*?)\s+(?P<op>" + "|".join(_COLL_OPS) +
    r")(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def shape_bytes(type_expr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_expr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device result bytes per collective kind (+ op counts)."""
    out: dict[str, float] = {op: 0.0 for op in _COLL_OPS}
    counts: dict[str, int] = {op: 0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # async pair: count the -start only
        m = _COLL_RE.search(line)
        if not m:
            continue
        out[m.group("op")] += shape_bytes(m.group("ty"))
        counts[m.group("op")] += 1
    out_total = {f"{k}_bytes": v for k, v in out.items() if v}
    out_total.update({f"{k}_count": float(c) for k, c in counts.items() if c})
    out_total["total_bytes"] = sum(v for k, v in out.items())
    return out_total


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    device_kind: str = TARGET_KIND

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / peaks(self.device_kind).flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / peaks(self.device_kind).hbm_bw

    @property
    def t_collective(self) -> float:
        return (self.collective_bytes_per_device
                / peaks(self.device_kind).link_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    def as_dict(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "device_kind": self.device_kind,
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
        }


def model_flops(spec, shape, n_chips: int) -> float:
    """MODEL_FLOPS = 6·N·tokens (train) / 2·N·tokens (inference), N = active
    params — the 'useful' flops yardstick for the whole job."""
    cfg = spec.config
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def useful_ratio(spec, shape, flops_per_device: float, n_chips: int) -> float:
    total_hlo = flops_per_device * n_chips
    mf = model_flops(spec, shape, n_chips)
    return mf / total_hlo if total_hlo else 0.0
