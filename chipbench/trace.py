"""Reduction of a profiler trace to what the per-layer metrics read.

``jax.profiler.trace`` writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  On a TPU each chip is a plane ``/device:TPU:<i>`` whose line
``XLA Ops`` holds one event per operation that ran, named by its HLO
instruction (``%fusion.187 = ... fusion(...)``), and whose line
``XLA Modules`` holds one event per program run (``jit_train_step(...)``).
Where no such plane exists (the CPU backend, in the tests) the operations
are the host events that carry an ``hlo_op`` stat.  The benchmark's own
host spans (``bench.batch``, ``bench.dispatch``, ``bench.fetch``) are the
host events of those names, on the same clock.

An operation's scope path (``.../comm.encode/...``) is its ``tf_op`` stat
where the event carries one; otherwise, for an operation of the step
program, it is looked up by instruction name in that program's compiled
HLO text, whose instructions carry ``metadata={op_name="..."}``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_NAMES = ("bench.batch", "bench.dispatch", "bench.fetch")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                     r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
_OP_NAME = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass
class Op:
    device: int
    name: str
    scope: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: List[Op]
    spans: List[Span]
    scope_method: str       # "tf_op stat", "HLO op_name" or "none"
    # asynchronous ops (``Async XLA Ops``: a copy or collective from its
    # start to its done), which run beside the ops above
    async_ops: List[Op] = dataclasses.field(default_factory=list)


def hlo_scopes(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(module name, {instruction name: op_name}) of compiled HLO text."""
    module, names = "", {}
    for line in hlo_text.splitlines():
        m = _HLO_MODULE.match(line)
        if m and not module:
            module = m.group(1)
            continue
        m = _HLO_OP.match(line)
        if m:
            names.setdefault(m.group(1), m.group(2))
    return module, names


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except Exception:  # noqa: BLE001 - an event whose stats do not read
        return {}


def load(path: str, hlo_text: Optional[str] = None) -> Trace:
    """Read the device operations and the benchmark's host spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    module, by_name = hlo_scopes(hlo_text) if hlo_text else ("", {})
    ops: List[Op] = []
    async_ops: List[Op] = []
    spans: List[Span] = []
    used = set()

    def scope_of(name: str, st: dict, mod: str) -> str:
        tf_op = st.get("tf_op")
        if isinstance(tf_op, str) and tf_op:
            used.add("tf_op stat")
            return tf_op
        if name in by_name and (not mod or not module or mod == module):
            used.add("HLO op_name")
            return by_name[name]
        return ""

    device_planes = [(int(m.group(1)), p) for p in data.planes
                     if (m := _DEVICE_PLANE.match(p.name))]
    for idx, plane in device_planes:
        lines = {line.name: line for line in plane.lines}
        runs = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                       e.name.split("(")[0])
                      for e in (lines["XLA Modules"].events
                                if "XLA Modules" in lines else ()))
        for e in (lines["Async XLA Ops"].events
                  if "Async XLA Ops" in lines else ()):
            async_ops.append(Op(idx, _OP_NAME.match(e.name).group(1), "",
                                float(e.start_ns), float(e.duration_ns)))
        r = 0
        for e in sorted(lines["XLA Ops"].events if "XLA Ops" in lines
                        else (), key=lambda e: e.start_ns):
            while r < len(runs) and runs[r][1] <= e.start_ns:
                r += 1
            mod = runs[r][2] if r < len(runs) and runs[r][0] <= e.start_ns \
                else ""
            name = _OP_NAME.match(e.name).group(1)
            ops.append(Op(idx, name, scope_of(name, _stats(e), mod),
                          float(e.start_ns), float(e.duration_ns)))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in SPAN_NAMES:
                    spans.append(Span(e.name, float(e.start_ns),
                                      float(e.duration_ns)))
                elif not device_planes:
                    st = _stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        ops.append(Op(int(st.get("device_ordinal", 0)),
                                      e.name, scope_of(
                                          e.name, st,
                                          st.get("hlo_module", "")),
                                      float(e.start_ns),
                                      float(e.duration_ns)))
    ops.sort(key=lambda o: (o.start_ns, -o.dur_ns))
    spans.sort(key=lambda s: s.start_ns)
    return Trace(ops, spans, " and ".join(sorted(used)) or "none",
                 async_ops)


def union_ns(intervals: Sequence[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals inside [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> List[Tuple[float, float]]:
    """The idle stretches of [lo, hi) between the intervals, in order."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_ns(ops: Sequence[Op]) -> List[float]:
    """Each op's time minus the time of the ops nested inside it (a
    ``while`` op spans the ops of its body); ``ops`` in start order."""
    own = [o.dur_ns for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ns <= o.start_ns:
            stack.pop()
        if stack and o.end_ns <= ops[stack[-1]].end_ns:
            own[stack[-1]] -= o.dur_ns
        stack.append(i)
    return own


def has_scope(op: Op, scope: str) -> bool:
    """True when ``scope`` is one whole component of the op's scope path."""
    return scope in op.scope.split("/")


@dataclasses.dataclass
class Window:
    """What a per-layer metric reads: the trace of ``steps`` steps between
    ``lo_ns`` and ``hi_ns``, the chips the cell holds, their peaks and the
    counts the benchmark made (``flops_per_step``, ``wire_bytes``,
    ``elems``, ``itemsize``, ``bits``, ``neighbors``, ``workers_per_chip``,
    ``wire``)."""
    trace: Trace
    lo_ns: float
    hi_ns: float
    steps: int
    chips: int
    peak: dict
    counts: dict

    @property
    def seconds(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    def devices(self) -> List[int]:
        return sorted({o.device for o in self.trace.ops})

    def op_seconds(self, pred=lambda op: True,
                   asynchronous: bool = False) -> Dict[int, float]:
        """Per device: seconds inside the window in which an op that
        satisfies ``pred`` ran (of the asynchronous ops, if asked)."""
        ops = self.trace.async_ops if asynchronous else self.trace.ops
        return {d: union_ns([(o.start_ns, o.end_ns) for o in ops
                             if o.device == d and pred(o)],
                            self.lo_ns, self.hi_ns) / 1e9
                for d in self.devices()}

    def span_seconds(self, name: str) -> float:
        """Host seconds inside the window spent in the spans ``name``."""
        return sum(max(0.0, min(s.end_ns, self.hi_ns)
                       - max(s.start_ns, self.lo_ns))
                   for s in self.trace.spans if s.name == name) / 1e9
