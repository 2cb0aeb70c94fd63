"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Three terms per (arch x shape x mesh), all *per chip per step, in seconds*:

    compute    = HLO_FLOPs / peak_FLOPs
    memory     = HLO_bytes / HBM_bw
    collective = collective_bytes / link_bw

``compiled.cost_analysis()`` reports per-device FLOPs / bytes-accessed of the
post-SPMD module (verified empirically), so no further division by chip count
is needed.  ``collective_bytes`` is not in cost_analysis: we parse the
post-partitioning HLO text and sum the *operand* bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.  Operand sizes
are derived from the result shape and the replica-group size:

    all-reduce / all-to-all / collective-permute:  operand == result
    all-gather:     operand == result / group_size
    reduce-scatter: operand == result * group_size

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM; the network
term prices bytes on the shared ``repro.sim.network.TPU_V5E_ICI`` link
model (alpha-beta with alpha = 0: the roofline charges pure bandwidth,
per-message latency belongs to the event simulator in ``repro.sim``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.sim.network import TPU_V5E_ICI

HW = {
    "peak_flops": 197e12,             # bf16 / chip
    "hbm_bw": 819e9,                  # B/s
    "ici_bw": TPU_V5E_ICI.beta_Bps,   # B/s per link (sim.network model)
}


def hw_with_ici(ici) -> Dict[str, float]:
    """HW table with a calibrated interconnect bandwidth.

    ``ici`` is a :class:`repro.sim.network.LinkModel` (e.g. the output of
    a ``sim/calibrate.py`` fit on measured collective times) or a plain
    bytes/s float.  Pass the result as ``roofline_from_compiled(..., hw=)``
    to price the collective term on measured rather than datasheet
    bandwidth — the ICI constant is a fit input, not a hardcode.
    """
    beta = getattr(ici, "beta_Bps", None)
    if beta is None:
        beta = float(ici)
    if beta <= 0:
        raise ValueError(f"ici bandwidth must be positive, got {beta}")
    return dict(HW, ici_bw=beta)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of a shape string like 'bf16[16,256]{1,0}' or a tuple."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^=]*?\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z0-9\-]+)\(",
)
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_op: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_op.values())

    def summary(self) -> str:
        parts = [f"{op}x{self.counts[op]}:{self.bytes_by_op[op]/1e6:.1f}MB"
                 for op in sorted(self.counts)]
        return " ".join(parts) if parts else "none"


def collective_ops(hlo_text: str) -> List[Tuple[str, str]]:
    """``(collective, result shape)`` per collective instruction, with
    async ``-start`` forms folded into their base op — e.g.
    ``("collective-permute", "u8[1,4096]{1,0}")``.  What a check reads to
    see which dtype crosses devices."""
    out = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m or m.group(2).endswith("-done"):
            continue
        for c in _COLLECTIVES:
            if m.group(2) == c or m.group(2).startswith(c + "-"):
                out.append((c, m.group(1)))
                break
    return out


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {}
    by: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        base = None
        for c in _COLLECTIVES:
            if op == c or op.startswith(c + "-"):   # e.g. all-gather-start
                base = c
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue                                 # counted at -start
        result = _shape_bytes(shape_str)
        g = 1
        mg = _GROUPS_RE.search(line)
        if mg:
            g = len(mg.group(1).split(","))
        else:
            mi = _GROUPS_IOTA_RE.search(line)
            if mi:
                g = int(mi.group(2))
        if base == "all-gather":
            operand = result // max(g, 1)
        elif base == "reduce-scatter":
            operand = result * max(g, 1)
        else:
            operand = result
        counts[base] = counts.get(base, 0) + 1
        by[base] = by.get(base, 0) + operand
    return CollectiveStats(counts, by)


@dataclasses.dataclass
class Roofline:
    flops: float                 # per device
    bytes_accessed: float        # per device
    collective_bytes: float      # per device
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float           # 6 N D (useful math, global)
    chips: int

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs (remat/redundancy waste detector)."""
        total_hlo = self.flops * self.chips
        return self.model_flops / total_hlo if total_hlo else 0.0

    @property
    def mfu_upper_bound(self) -> float:
        """Model-FLOPs utilisation if the step ran at the roofline bound."""
        denom = self.bound_s * self.chips * HW["peak_flops"]
        return self.model_flops / denom if denom else 0.0


def roofline_from_compiled(compiled, model_flops: float, chips: int,
                           hw: Dict[str, float] = HW) -> Roofline:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    nbytes = float(ca.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    return Roofline(
        flops=flops,
        bytes_accessed=nbytes,
        collective_bytes=float(stats.total_bytes),
        compute_s=flops / hw["peak_flops"],
        memory_s=nbytes / hw["hbm_bw"],
        collective_s=stats.total_bytes / hw["ici_bw"],
        model_flops=model_flops,
        chips=chips,
    )


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS = 6 N D (dense) or 6 N_active D (MoE); D = tokens/step.

    Audio (enc-dec) processes enc_len + dec_len tokens per example, not
    ``seq_len`` (the raw audio length) — mirrors Model.batch_spec.
    """
    n = cfg.active_param_count()
    if cfg.family == "audio":
        enc_len = shape.seq_len // cfg.encoder_downsample
        dec_len = min(cfg.decoder_len_cap, max(shape.seq_len // 8, 16))
        tokens_per_ex = enc_len + dec_len
    else:
        tokens_per_ex = shape.seq_len
    if shape.kind == "train":
        tokens = shape.global_batch * tokens_per_ex
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * tokens_per_ex
        return 2.0 * n * tokens       # forward only
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
