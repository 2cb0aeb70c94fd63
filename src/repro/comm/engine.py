"""CommEngine: one pluggable communication engine for decentralized SGD.

Every decentralized algorithm in this repo reduces its communication to the
same primitive — *one gossip round*: encode the local model, circulate the
payload along the topology (``jnp.roll`` on the stacked worker axis, which is
one ``collective-permute`` on the production mesh), decode each neighbor
against the local reference, and accumulate the weighted consensus step

    X_{k+1/2}[i] = x_i + sum_{o != 0} w_o * (xhat_{i+o} - xhat_self)     (*)

``CommEngine`` owns that round end-to-end and exposes the three seams the
paper's algorithm zoo (and every future scaling PR) plugs into:

* **codec** — what rides on the wire: ``FullPrecisionWire`` (D-PSGD baseline;
  (*) then collapses to the circulant ``X W``), ``MoniquaWire`` (Algorithm 1's
  bit-packed modulo residue, no scales, no extra state), ``QSGDWire``
  (Alistarh et al. 2017 scale+codes, the obvious external comparison), or
  the *stateful* error-feedback family — ``EFQSGDWire`` and ``OneBitWire``
  (1-bit Adam-style warmup + sign codes) — which carry a per-worker
  ``WireState`` pytree (EF residual + warmup counter) as an explicit
  jit-safe carry through ``mix``/``pair_average``; see ``docs/codecs.md``.
* **topology** — any circulant :class:`~repro.core.topology.Topology`; the
  weights are static so they compile into the mixing (and into the fused
  kernel's unrolled reduction).
* **backend** — ``"jnp"`` lowers everywhere (pure jnp, used by the CPU
  convergence experiments), ``"pallas"`` uses the fused TPU kernels
  (``kernels/moniqua_encode.py`` + ``kernels/moniqua_decode_reduce.py``),
  ``"auto"`` picks Pallas on TPU.  Both Moniqua backends draw stochastic
  rounding from the same counter-based hash of (seed, element index), so they
  agree **bit-exactly** in interpret mode — the parity contract
  ``tests/test_engine.py`` enforces.

Results are uniform: ``mix`` always returns a :class:`MixResult`
(``x``, ``state``, ``health`` — ``state == {}`` for stateless wires,
``health is None`` with telemetry off) and ``pair_average`` a
:class:`PairResult`; callers use attribute access, never tuple-arity
branching.

Gossip path (``path=``): ``"bucketed"`` flattens the whole stacked pytree
into one contiguous per-worker staging buffer (``comm/bucket.py``) so a
round is one encode launch, one packed roll per offset, one fused
decode-reduce, and one scatter back to leaves; ``"per_leaf"`` keeps the
leaf-by-leaf round as the parity reference; ``"auto"`` (default) picks per
(layout, codec) from a memoized crossover table seeded by the committed
``BENCH_comm_fusion.json`` — bucketing wins exactly when the per-leaf
tile-grid pad amplification (dozens of sub-tile biases each padded to
256x1024) dwarfs the bucketed single pad, which the committed data shows
only for many-small-leaf models on the Moniqua wire.  Stateful (EF) wires
always bucket: their canonical residual lives in the flat domain.

Staged rounds: ``round_plan(X)`` returns a :class:`RoundPlan` exposing one
gossip round as three separable phases per chunk — ``encode_chunk(i)``,
``permute(i)``, ``decode_reduce(i)`` — over the ``BucketLayout.chunks(K)``
partition (slot-aligned, so per-tensor scales never straddle a chunk).
``RoundPlan.run()`` software-pipelines them in the skewed order
encode(t) / permute(t-1) / decode-reduce(t-2), so chunk t's
collective-permute is issued while t+1 encodes and t-1 reduces — the
ROADMAP's overlap item.  Because every codec hashes *global* element
indices (``idx_base`` = chunk offset) and chunk boundaries stay on
values-per-byte segment boundaries, the pipelined round is **bit-exact**
against the barrier round (``chunks=1``) for every wire — outputs, payload
bits, and post-round WireState (``tests/test_overlap.py``).

Step-level overlap: ``mix_stale`` (stateless Moniqua only) applies the
*previous* round's payloads to the current model and immediately encodes
the result for the next round, carrying ``(packed, ref, B, valid)`` across
steps — one-round-stale mixing, so the decode-reduce of round k can hide
behind the forward pass of step k+1.  Staleness-tolerance for decentralized
SGD with quantized updates (PAPERS.md) covers this delay-1 schedule.

Bytes accounting is trace-time bookkeeping: ``mix(..., ledger=...)`` records
payload-bytes-per-worker into a :class:`~repro.comm.gossip.BytesLedger`, and
``bytes_per_round`` returns the same number without running anything — the
input to the analytic network model in ``benchmarks/``.  Payload bytes are
path-independent (the vpb row alignment makes the bucketed payload equal
the per-leaf sum exactly), so ``path="auto"`` never changes the ledger.

Tile staging (``CommEngine.staging``): the stateless Moniqua round on
the bucketed path, with one tier, one chunk and no presence mask, stages
the bucket in the codec kernels' tile shape ``[n, R, 1024]``
(``BucketLayout.flatten_tiles``) and launches each kernel once over all
workers; qsgd and the EF wires, chunked, tiered, masked and stale rounds
and the telemetry keep the ``[n, D]`` buffer, where the ``kernels/ops.py``
stacked wrappers tile each worker's slice separately.

Sharded meshes: either way each worker is encoded on its own, so the
only cross-worker traffic in a round is the packed collective-permute of
the payload, and — because every worker hashes the same (seed, element)
pairs — stochastic rounding uses Supp.-C shared randomness exactly:
identical models encode to identical payloads on every worker.

Elastic rounds (``presence=``): ``mix``/``mix_stale``/``pair_average``
accept a per-worker presence mask.  A dead edge (either endpoint absent)
contributes *identity* — the receiving worker keeps its own value in that
edge's weight, which is exactly the renormalized doubly-stochastic
``Topology.with_presence`` matrix applied in the quantized-difference
domain — and an absent worker's model AND its EF ``WireState`` residual
pass through a missed round untouched.  The mask is normalized host-side:
``presence=None`` or all-ones takes *literally today's code path*, so the
full-presence round is bit-exact by construction for every wire, backend,
path, and tier (``tests/test_elastic.py``); each distinct partial mask is
a separate trace (documented recompile — elastic benches run eager).
Tiered engines take a per-NODE mask (length ``n_inter``): an absent node
keeps its intra-tier average but drops out of the inter-shard gossip — the
"uplink partition" failure mode.  See ``docs/elasticity.md``.

Wall-clock prediction: the byte counts this engine produces feed the
event-driven simulator (``repro.sim``), which prices them under explicit
link/compute models per named scenario — see ``docs/simulator.md``.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm import bucket, gossip
from repro.comm.gossip import BytesLedger
from repro.core import modulo
from repro.core.quantizers import (QuantSpec, ef_qsgd_encode_segmented,
                                   onebit_decode_segmented,
                                   onebit_encode_segmented,
                                   onebit_payload_bytes, packed_last_dim,
                                   qsgd_decode, qsgd_decode_segmented,
                                   qsgd_encode, qsgd_encode_segmented,
                                   qsgd_payload_bytes)
from repro.core.topology import (HierarchicalTopology, Topology,
                                 normalize_mask)
from repro.kernels import ops as kops
from repro.kernels.moniqua_encode import (DEFAULT_BLOCK_COLS,
                                          DEFAULT_BLOCK_ROWS)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

PyTree = Any

WIRES = ("full", "moniqua", "qsgd", "ef_qsgd", "onebit")
BACKENDS = ("auto", "jnp", "pallas")
PATHS = ("bucketed", "per_leaf", "auto")


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return backend


# ---------------------------------------------------------------------------
# Uniform round results.
# ---------------------------------------------------------------------------

class MixResult(NamedTuple):
    """What one gossip round returns — always the same three fields.

    ``x`` is the mixed model ``X_{k+1/2}``; ``state`` is the post-round
    WireState carry (``{}`` for stateless wires — thread it back into the
    next ``mix`` for EF wires, or the gossip carry for ``mix_stale``);
    ``health`` is the round-health dict (``None`` unless the engine was
    built with ``telemetry=True``).  Use attribute access: the fields are
    uniform precisely so call sites never branch on arity again.
    """
    x: Any
    state: dict = {}
    health: Optional[dict] = None


class PairResult(NamedTuple):
    """What one ``pair_average`` edge exchange returns (AD-PSGD primitive):
    both updated endpoints plus their post-exchange WireState carries
    (``{}`` for stateless wires)."""
    xi: Any
    xj: Any
    state_i: dict = {}
    state_j: dict = {}


# ---------------------------------------------------------------------------
# Wire codecs: what one worker broadcasts per round.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FullPrecisionWire:
    """Identity codec: the raw model rides the wire (D-PSGD / D2 baseline)."""
    name = "full"

    def payload_bytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        return int(np.prod(shape, dtype=np.int64)) * itemsize


@dataclasses.dataclass(frozen=True)
class MoniquaWire:
    """Algorithm 1's packed modulo residue: ``bits/8`` bytes/param, no scales."""
    spec: QuantSpec = QuantSpec()
    name = "moniqua"

    def payload_bytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        if not shape:
            return 1
        inner = int(np.prod(shape[:-1], dtype=np.int64))
        return inner * packed_last_dim(shape[-1], self.spec.bits)


@dataclasses.dataclass(frozen=True)
class QSGDWire:
    """Scale+codes codec: packed codes + one f32 max-norm scale per tensor."""
    spec: QuantSpec = QuantSpec()
    name = "qsgd"

    def payload_bytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        return qsgd_payload_bytes(shape, self.spec.bits)


@dataclasses.dataclass(frozen=True)
class EFQSGDWire:
    """Error-feedback QSGD (Tang et al. 2019 style): quantize ``x + residual``
    with the scale+codes wire, keep ``residual' = x + residual - decode(sent)``
    per worker.  Stateful: pays one f32 residual buffer per worker (Θ(nd)
    graph-wide) — the memory axis ``BENCH_memory_overhead.json`` prices
    against Moniqua's zero-extra-state wire."""
    spec: QuantSpec = dataclasses.field(default_factory=QuantSpec)
    name = "ef_qsgd"
    stateful = True

    def payload_bytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        return qsgd_payload_bytes(shape, self.spec.bits)


@dataclasses.dataclass(frozen=True)
class OneBitWire:
    """1-bit Adam-style compressed wire: full-precision gossip for the first
    ``warmup`` rounds, then 1-bit sign codes of the compensated value (with
    per-segment cluster-mean levels) and an error-feedback residual.  The
    carried step counter is the ``need_reset``-style hook: crossing it flips
    the round's codec inside the jitted step (a ``jnp.where`` select — see
    ``RoundPlan.decode_reduce``), and checkpointing the counter resumes the
    schedule bit-identically."""
    spec: QuantSpec = dataclasses.field(
        default_factory=lambda: QuantSpec(bits=1, stochastic=False))
    warmup: int = 16
    name = "onebit"
    stateful = True

    def payload_bytes(self, shape: Tuple[int, ...], itemsize: int = 4) -> int:
        """Steady-state (post-warmup) bytes; warmup rounds ship f32
        (``warmup_payload_bytes``) — accounting reports the steady state."""
        return onebit_payload_bytes(shape)

    def warmup_payload_bytes(self, shape: Tuple[int, ...],
                             itemsize: int = 4) -> int:
        return int(np.prod(shape, dtype=np.int64)) * 4 if shape else 4


def make_wire(name: str, spec: Optional[QuantSpec] = None, warmup: int = 16):
    spec = spec or QuantSpec()
    if name == "full":
        return FullPrecisionWire()
    if name == "moniqua":
        return MoniquaWire(spec)
    if name == "qsgd":
        return QSGDWire(spec)
    if name == "ef_qsgd":
        return EFQSGDWire(spec)
    if name == "onebit":
        # the sign path is 1 bit by construction; keep the caller's
        # stochastic/nearest choice but pin the packable width
        return OneBitWire(dataclasses.replace(spec, bits=1), warmup=warmup)
    raise ValueError(f"unknown wire codec {name!r}; one of {WIRES}")


# ---------------------------------------------------------------------------
# Auto path selection: per-(layout, codec) crossover from committed bench data.
# ---------------------------------------------------------------------------

def _tile_padded(elems: int) -> int:
    """Elements after padding a flat segment to the Pallas encode tile grid
    (same accounting as ``benchmarks/bench_comm_fusion.py``)."""
    rows = -(-elems // DEFAULT_BLOCK_COLS)
    return -(-rows // DEFAULT_BLOCK_ROWS) * DEFAULT_BLOCK_ROWS \
        * DEFAULT_BLOCK_COLS


# measured crossover when BENCH_comm_fusion.json is absent (derived from the
# same committed data: moniqua buckets win only where per-leaf tile padding
# amplifies ~30x over bucketed; qsgd/full buckets lose on every measured model)
_FALLBACK_CROSSOVER = {"moniqua": 9.8, "qsgd": float("inf"),
                       "full": float("inf")}


@functools.lru_cache(maxsize=1)
def _crossover_table() -> Dict[str, float]:
    """Per-wire pad-amplification threshold above which bucketing wins.

    Seeded from the committed ``BENCH_comm_fusion.json``: each measured
    model has a pad-amplification ratio (per-leaf tile-padded elements /
    bucketed tile-padded elements) and a bucketed-vs-per-leaf speedup per
    codec.  The threshold is the geometric mean of the worst winning and
    best losing ratio — ``inf`` when bucketing never won, ``1.0`` when it
    never lost.  Falls back to the hardcoded equivalents when the file is
    missing (fresh checkout before benches ran).
    """
    try:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        with open(os.path.join(root, "BENCH_comm_fusion.json")) as f:
            data = json.load(f)
        ratios = {o["model"]: (o["tile_padded_elems_per_leaf_path"]
                               / o["tile_padded_elems_bucketed"])
                  for o in data["overhead"]}
        wire_of = {"moniqua-1bit": "moniqua", "moniqua-8bit": "moniqua",
                   "qsgd-8bit": "qsgd", "fp32": "full"}
        wins: Dict[str, list] = {}
        losses: Dict[str, list] = {}
        for row in data["table"]:
            wire = wire_of.get(row["codec"])
            if wire is None or row["model"] not in ratios:
                continue
            side = wins if row["speedup_x"] >= 1.0 else losses
            side.setdefault(wire, []).append(ratios[row["model"]])
        table = dict(_FALLBACK_CROSSOVER)
        for wire in ("moniqua", "qsgd", "full"):
            w, l = wins.get(wire), losses.get(wire)
            if not w:
                table[wire] = float("inf")
            elif not l:
                table[wire] = 1.0
            else:
                table[wire] = math.sqrt(max(l) * min(w))
        return table
    except Exception:
        return dict(_FALLBACK_CROSSOVER)


@functools.lru_cache(maxsize=4096)
def _auto_bucketed_slots(slots: Tuple[bucket.LeafSlot, ...],
                         padded_elems: int, codec_name: str) -> bool:
    """``path="auto"`` decision for one contiguous slot window: bucket
    exactly when the window's per-leaf pad amplification clears the
    measured crossover for the wire.  Operates on a slot census (not a
    whole layout) so a *shard* of the buffer resolves on its own leaves —
    a shard holding two fused embedding slabs should not inherit the
    bucketing verdict of the whole model's bias census."""
    per_leaf = sum(_tile_padded(s.padded_size) for s in slots)
    ratio = per_leaf / max(_tile_padded(padded_elems), 1)
    return ratio >= _crossover_table().get(codec_name, float("inf"))


def _auto_bucketed(layout: bucket.BucketLayout, codec_name: str) -> bool:
    return _auto_bucketed_slots(layout.slots, layout.padded_elems,
                                codec_name)


# ---------------------------------------------------------------------------
# The engine.
# ---------------------------------------------------------------------------

def _leaf_seed(base_seed: jax.Array, leaf_idx: int) -> jax.Array:
    """Distinct deterministic hash seed per pytree leaf (both backends)."""
    return jnp.asarray(base_seed, jnp.uint32) ^ jnp.uint32(
        (leaf_idx * 0x9E3779B1) & 0xFFFFFFFF)


def _neighbor_weights_of(topo: Topology) -> Tuple[float, ...]:
    return tuple(w for o, w in zip(topo.offsets, topo.weights)
                 if o % topo.n != 0)


# ---------------------------------------------------------------------------
# Elastic rounds: presence masks.
# ---------------------------------------------------------------------------

def _normalize_presence(presence, n: int) -> Optional[Tuple[int, ...]]:
    """Host-side presence normalization: ``None`` or all-ones collapses to
    ``None`` — the caller then takes literally today's (unmasked) code
    path, which is the whole full-presence bit-exactness argument.  A
    partial mask comes back as a static 0/1 tuple (it compiles into the
    trace; distinct masks retrace)."""
    if presence is None:
        return None
    vals = normalize_mask(presence, n)
    if all(vals):
        return None
    return vals


def _alive_cols(presence: Tuple[int, ...], offset: int,
                ndim: int = 2) -> jax.Array:
    """Bool ``[n, 1, ..]`` mask: worker ``i`` True iff both endpoints of
    its edge to ``i + offset`` showed up (``_roll`` indexing: row ``i``
    of ``_roll(x, o)`` is ``x[i + o]``)."""
    pb = jnp.asarray(presence, jnp.bool_)
    pb = pb.reshape((-1,) + (1,) * (ndim - 1))
    return jnp.logical_and(pb, gossip._roll(pb, offset))


def _present_cols(presence: Tuple[int, ...], ndim: int = 2) -> jax.Array:
    pb = jnp.asarray(presence, jnp.bool_)
    return pb.reshape((-1,) + (1,) * (ndim - 1))


def _masked_circulant(x: jax.Array, topo: Topology,
                      presence: Tuple[int, ...]) -> jax.Array:
    """Full-precision elastic mix on one stacked leaf: identity plus the
    weighted diffs of the edges that survived the mask — the
    ``with_presence`` matrix applied without materializing it."""
    f = x.astype(jnp.float32)
    acc = None
    for o, w in zip(topo.offsets, topo.weights):
        if o % topo.n == 0:
            continue
        alive = _alive_cols(presence, o, x.ndim)
        t = jnp.where(alive, gossip._roll(f, o) - f, 0.0) * w
        acc = t if acc is None else acc + t
    if acc is None:
        return x
    return (f + acc).astype(x.dtype)


def _dropped_edge_count(presence: Tuple[int, ...], topo: Topology) -> int:
    """Directed gossip edges the mask killed (health counter; static)."""
    n = topo.n
    return sum(1
               for o in topo.neighbor_offsets()
               for i in range(n)
               if not (presence[i] and presence[(i + o) % n]))


@dataclasses.dataclass
class RoundPlan:
    """One gossip round, staged: per-chunk encode / permute / decode-reduce.

    Built by :meth:`CommEngine.round_plan`.  The three phase methods are
    separable and chunk-indexed so a caller (or :meth:`run`) can interleave
    them; each is bit-exact per chunk against the barrier round's math on
    the same window because

    * chunk windows cover whole leaf slots (``BucketLayout.chunks``), so
      per-tensor codec statistics (qsgd scales, onebit lo/hi levels) see
      exactly the segments the whole-buffer round sees;
    * encode kernels hash *global* element indices (``idx_base`` = the
      chunk's buffer offset; qsgd additionally strides its worker axis by
      the whole-buffer width), so every element draws the same rounding
      uniform regardless of chunking;
    * chunk offsets are values-per-byte aligned, so the chunk payloads are
      byte-exact windows of the whole-buffer payload;
    * the decode-reduce accumulation order per element is identical.

    ``run()`` executes the software pipeline: at tick t it issues
    encode(t), permute(t-1), decode_reduce(t-2) — so the permute of chunk
    t-1 (the round's only cross-worker traffic) is in flight between the
    codec work of its neighbors.  With ``chunks=1`` the skew degenerates to
    the barrier round (encode, permute, reduce back-to-back) — the parity
    reference ``tests/test_overlap.py`` pins.
    """
    engine: "CommEngine"
    layout: bucket.BucketLayout
    chunks: Tuple[bucket.BucketChunk, ...]
    flat: jax.Array
    backend: str
    theta: Any = None
    B: Any = None
    seed: Optional[jax.Array] = None
    residual: Optional[jax.Array] = None
    step: Optional[jax.Array] = None
    # shard plans (TieredPlan stage B): ``flat`` is the owned-shard window
    # of the buffer starting at element ``base``, and the gossip runs on
    # ``topo`` (the inter tier) instead of the engine's topology.  Chunk
    # offsets stay *global* — they are the encode kernels' idx_base — so
    # windows are sliced at ``c.offset - base``.  Defaults reproduce the
    # single-tier whole-buffer round exactly.
    base: int = 0
    topo: Optional[Topology] = None
    # elastic rounds: normalized partial presence mask over the plan's
    # worker axis (None = everyone present = exactly the unmasked math).
    # Encode and permute are unchanged — presence only gates which decoded
    # neighbor diffs enter the reduction (a dead edge contributes identity)
    # and, for EF wires, which rows update their residual.
    presence: Optional[Tuple[int, ...]] = None

    def __post_init__(self):
        if self.topo is None:
            self.topo = self.engine.gossip_topo

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    def _win(self, arr: jax.Array, c: bucket.BucketChunk) -> jax.Array:
        off = c.offset - self.base
        return jax.lax.slice_in_dim(arr, off, off + c.size, axis=1)

    # -- phase 1: encode one chunk -----------------------------------------
    def encode_chunk(self, i: int) -> Tuple[jax.Array, ...]:
        """Encode chunk ``i`` of the staging buffer; returns the wire-specific
        payload tuple (plus, for EF wires, the compensated value ``v`` that
        the decode-reduce phase needs to close the residual)."""
        c = self.chunks[i]
        eng = self.engine
        name = eng.codec.name
        with obs_trace.chunk_phase("comm.encode", i, self.num_chunks):
            if name == "full":
                return (self._win(self.flat, c),)
            if name == "moniqua":
                return (kops.moniqua_encode_chunk(
                    self.flat, c.offset - self.base, c.size, self.B,
                    eng.codec.spec, self.seed, backend=self.backend,
                    idx_base=c.offset, worker_axes=eng.worker_axes),)
            if name == "qsgd":
                packed, scales = qsgd_encode_segmented(
                    self._win(self.flat, c), eng.codec.spec, self.seed,
                    c.segment_sizes, idx_base=c.offset,
                    idx_stride=self.layout.padded_elems)
                return (packed, scales)
            # EF wires: compensate with the residual window before encoding
            v = self._win(self.flat, c) + self._win(self.residual, c)
            if name == "ef_qsgd":
                packed, scales = ef_qsgd_encode_segmented(
                    v, eng.codec.spec, self.seed, c.segment_sizes, c.offset)
                return (packed, scales, v)
            packed, lo, hi = onebit_encode_segmented(
                v, self.seed, c.segment_sizes, c.offset,
                eng.codec.spec.stochastic)
            return (packed, lo, hi, v)

    # -- phase 2: circulate one chunk's payload ----------------------------
    def permute(self, i: int, enc: Tuple[jax.Array, ...]):
        """Roll chunk ``i``'s payload along the worker axis — the round's
        only cross-worker traffic (one collective-permute per offset on a
        mesh).  The EF wires' local ``v`` never rides the wire."""
        eng = self.engine
        name = eng.codec.name
        with obs_trace.chunk_phase("comm.permute", i, self.num_chunks):
            if name == "full":
                # the raw wire reduces over ALL offsets (self included, where
                # _roll no-ops) — exactly gossip.mix's circulant
                return tuple(gossip._roll(enc[0], o)
                             for o in self.topo.offsets)
            offsets = self.topo.neighbor_offsets()
            if name == "moniqua":
                return jnp.stack([gossip._roll(enc[0], o) for o in offsets])
            n_payload = 2 if name in ("qsgd", "ef_qsgd") else 3
            return tuple(tuple(gossip._roll(p, o) for p in enc[:n_payload])
                         for o in offsets)

    # -- phase 3: decode neighbors, accumulate the consensus step ----------
    def decode_reduce(self, i: int, enc: Tuple[jax.Array, ...], nbrs):
        """Decode chunk ``i``'s circulated payloads against the local window
        and apply (*) on it.  Stateless wires return the mixed window;
        stateful (EF) wires return ``(mixed window, new residual window)``.
        """
        c = self.chunks[i]
        eng = self.engine
        name = eng.codec.name
        spec = getattr(eng.codec, "spec", None)
        seg = c.segment_sizes
        p = self.presence

        def gate(o, t):
            # elastic: a dead edge's decoded diff never enters the
            # reduction — the receiver keeps its own value in that weight
            return t if p is None else jnp.where(_alive_cols(p, o), t, 0.0)

        with obs_trace.chunk_phase("comm.decode_reduce", i, self.num_chunks):
            if name == "full":
                if p is None:
                    out = None
                    for w, r in zip(self.topo.weights, nbrs):
                        t = r * w
                        out = t if out is None else out + t
                    return out.astype(enc[0].dtype)
                # masked raw wire: identity plus the gated neighbor diffs
                # (NOT a re-weighted sum of windows — summing w_o-scaled
                # copies of the local window would put an absent row one
                # ulp off identity)
                win = enc[0]
                f = win.astype(jnp.float32)
                out = f
                for o, w, r in zip(self.topo.offsets, self.topo.weights,
                                   nbrs):
                    if o % self.topo.n == 0:
                        continue
                    out = out + jnp.where(_alive_cols(p, o),
                                          r.astype(jnp.float32) - f,
                                          0.0) * w
                return out.astype(win.dtype)
            offsets = self.topo.neighbor_offsets()
            weights = _neighbor_weights_of(self.topo)
            if name == "moniqua":
                if p is None:
                    return kops.moniqua_decode_reduce_chunk(
                        enc[0], nbrs, self.flat, c.offset - self.base,
                        c.size, self.B, weights, spec,
                        backend=self.backend, worker_axes=eng.worker_axes)
                # masked: one fused decode-reduce per surviving offset
                # (single weight), recombined as win + sum of gated diffs
                win = self._win(self.flat, c).astype(jnp.float32)
                out = win
                for k, (o, w) in enumerate(zip(offsets, weights)):
                    mixed_o = kops.moniqua_decode_reduce_chunk(
                        enc[0], nbrs[k:k + 1], self.flat,
                        c.offset - self.base, c.size, self.B, (w,), spec,
                        backend=self.backend, worker_axes=eng.worker_axes)
                    out = out + gate(o, mixed_o.astype(jnp.float32) - win)
                return out.astype(self._win(self.flat, c).dtype)
            if name == "qsgd":
                win = self._win(self.flat, c)
                packed, scales = enc
                d_self = qsgd_decode_segmented(packed, scales, spec, seg)
                acc = None
                for (p_o, s_o), o, w in zip(nbrs, offsets, weights):
                    t = gate(o, qsgd_decode_segmented(p_o, s_o, spec, seg)
                             - d_self) * w
                    acc = t if acc is None else acc + t
                return (win.astype(jnp.float32) + acc).astype(win.dtype)
            if name == "ef_qsgd":
                win = self._win(self.flat, c)
                packed, scales, v = enc
                d_self = qsgd_decode_segmented(packed, scales, spec, seg)
                acc = None
                for (p_o, s_o), o, w in zip(nbrs, offsets, weights):
                    t = gate(o, qsgd_decode_segmented(p_o, s_o, spec, seg)
                             - d_self) * w
                    acc = t if acc is None else acc + t
                out, res = win + acc, v - d_self
                if p is not None:
                    # an absent worker's model and EF residual pass
                    # through the missed round untouched
                    here = _present_cols(p)
                    rwin = self._win(self.residual, c)
                    out = jnp.where(here, out, win)
                    res = jnp.where(here, res, rwin)
                return out, res
            # onebit: fp32 gossip during warmup, sign codes + EF after; the
            # warm/quantized switch is a jnp.where select, NOT lax.cond —
            # cond bodies compile as separate XLA computations whose fusion
            # choices depend on buffer width, breaking the chunked-vs-
            # barrier bitwise contract at the ulp level.
            win = self._win(self.flat, c)
            rwin = self._win(self.residual, c)
            packed, lo, hi, v = enc
            warm_p = self.step < eng.codec.warmup
            out_warm = (gossip.mix(win, self.topo) if p is None
                        else _masked_circulant(win, self.topo, p))
            d_self = onebit_decode_segmented(packed, lo, hi, seg)
            acc = None
            for (p_o, lo_o, hi_o), o, w in zip(nbrs, offsets, weights):
                t = gate(o, onebit_decode_segmented(p_o, lo_o, hi_o, seg)
                         - d_self) * w
                acc = t if acc is None else acc + t
            out = jnp.where(warm_p, out_warm, win + acc)
            res = jnp.where(warm_p, rwin, v - d_self)
            if p is not None:
                here = _present_cols(p)
                out = jnp.where(here, out, win)
                res = jnp.where(here, res, rwin)
            return out, res

    # -- the software pipeline ---------------------------------------------
    def run(self):
        """Execute the full round through the skewed pipeline.

        Returns the mixed flat buffer (stateless wires) or
        ``(mixed flat buffer, new flat residual)`` (stateful wires).  With
        one chunk this is exactly the barrier round.
        """
        K = self.num_chunks
        stateful = self.engine.stateful
        enc: Dict[int, Any] = {}
        nbr: Dict[int, Any] = {}
        outs: list = [None] * K
        ress: list = [None] * K
        for t in range(K + 2):
            if t < K:
                enc[t] = self.encode_chunk(t)
            if 0 <= t - 1 < K:
                nbr[t - 1] = self.permute(t - 1, enc[t - 1])
            if 0 <= t - 2 < K:
                r = self.decode_reduce(t - 2, enc.pop(t - 2), nbr.pop(t - 2))
                if stateful:
                    outs[t - 2], ress[t - 2] = r
                else:
                    outs[t - 2] = r
        out = outs[0] if K == 1 else jnp.concatenate(outs, axis=1)
        if stateful:
            res = ress[0] if K == 1 else jnp.concatenate(ress, axis=1)
            return out, res
        return out


@dataclasses.dataclass
class TieredPlan:
    """One two-tier gossip round on the flat bucket (hierarchical engines).

    Three stages on the ``[n, D]`` staging buffer viewed as
    ``[n_inter, n_intra, D]`` (worker ``w = g * n_intra + j``):

    1. **Intra reduce** (fast axis, full precision): the intra tier's
       circulant mix along the node axis — with the default fully-connected
       intra tier this is exactly the node mean, i.e. the reduce phase of a
       reduce-scatter.  Skipped at the *Python* level when ``n_intra == 1``
       (no multiply-by-1.0 rides into the graph), which is the whole
       trivial-tier bit-exactness argument.
    2. **Inter shard gossip** (slow axis, quantized): worker ``j`` owns the
       slot-aligned shard window ``layout.shard(n_intra, j)`` and gossips
       *only that window* across nodes on the inter topology — one
       :class:`RoundPlan` per shard with ``base`` = the shard offset and
       ``topo`` = the inter tier, so the encode hashes global element
       indices and every RoundPlan guarantee (chunk pipelining, per-tensor
       scales, WireState math) carries over unchanged.  Each shard plan
       sub-chunks its own slots (``BucketChunk.chunks``): ``chunks=K``
       pipelining composes per shard, and a shard whose *own* leaf census
       resolves ``path="auto"`` to per-leaf degenerates to slot-granular
       chunks (per-leaf on a flat window == one chunk per slot).
    3. **All-gather** (fast axis): the mixed shards concatenate back to the
       full buffer and broadcast across the intra axis — every worker in a
       node leaves the round with the same model, like D-PSGD after an
       exact node-local average.

    With ``n_intra == 1`` stages 1 and 3 are identity reshapes and stage 2
    is one whole-buffer RoundPlan on the inter topology — byte- and
    bit-identical to the single-tier staged round (``tests/
    test_hierarchical.py`` pins this for all five wires, both backends,
    WireState carries included).

    Stateful (EF) wires keep their residual in the *owned-shard domain*:
    one ``[n_inter, padded_elems]`` f32 buffer — row ``g``, window ``j``
    is worker ``(g, j)``'s residual for the shard it encodes — i.e.
    ``n_intra``-fold smaller than the single-tier ``[n, padded_elems]``
    state, which is the memory half of the hierarchy headline.
    """
    engine: "CommEngine"
    layout: bucket.BucketLayout
    flat: jax.Array                    # [n, D] staging buffer
    backend: str
    chunks: int = 1                    # per-shard sub-chunk count K
    theta: Any = None
    B: Any = None
    seed: Optional[jax.Array] = None
    residual: Optional[jax.Array] = None   # [n_inter, D] owned-shard EF state
    step: Optional[jax.Array] = None
    # elastic rounds: per-NODE presence over the inter tier (length
    # n_inter).  An absent node keeps its intra average but drops out of
    # the inter shard gossip — the "uplink partition" failure mode; its
    # owned-shard residual rows pass through untouched.
    presence: Optional[Tuple[int, ...]] = None

    @property
    def topo(self) -> HierarchicalTopology:
        return self.engine.topo

    def intra_reduce(self) -> jax.Array:
        """Stage 1: the intra tier's circulant mix along the node axis;
        returns ``[n_inter, n_intra, D]``.  Pure reshape when trivial."""
        intra = self.topo.intra
        g, k = self.topo.n_inter, self.topo.n_intra
        stage = self.flat.reshape(g, k, self.flat.shape[-1])
        if k == 1:
            return stage
        with obs_trace.named_phase("comm.intra_reduce"):
            out = None
            for o, w in zip(intra.offsets, intra.weights):
                t = (jnp.roll(stage, -o, axis=1) if o % k else stage) * w
                out = t if out is None else out + t
            return out.astype(stage.dtype)

    def shard_plan(self, j: int, z: jax.Array) -> RoundPlan:
        """Stage 2 for shard ``j``: the owner rows' window as a RoundPlan
        over ``n_inter`` node-workers on the inter topology."""
        shard = self.layout.shard(self.topo.n_intra, j)
        k = self.chunks
        if not self.engine._shard_bucketed(shard):
            # this shard's own census says per-leaf: slot-granular chunks
            k = max(k, len(shard.slots))
        zj = jax.lax.slice_in_dim(z[:, j, :], shard.offset,
                                  shard.offset + shard.size, axis=1)
        res = None
        if self.residual is not None:
            res = jax.lax.slice_in_dim(self.residual, shard.offset,
                                       shard.offset + shard.size, axis=1)
        return RoundPlan(engine=self.engine, layout=self.layout,
                         chunks=shard.chunks(k), flat=zj,
                         backend=self.backend, theta=self.theta, B=self.B,
                         seed=self.seed, residual=res, step=self.step,
                         base=shard.offset, topo=self.topo.inter,
                         presence=self.presence)

    def run(self):
        """Execute the tiered round.  Returns the mixed ``[n, D]`` buffer
        (stateless wires) or ``(mixed buffer, new [n_inter, D] residual)``
        (stateful wires)."""
        eng = self.engine
        g, k = self.topo.n_inter, self.topo.n_intra
        stateful = eng.stateful
        z = self.intra_reduce()
        if not self.topo.inter.neighbor_offsets():
            # single node: the round is the intra average alone
            out = z
            res = self.residual
        else:
            outs, ress = [], []
            for j in range(k):
                if self.layout.shard(k, j).size == 0:
                    continue        # more workers than slots: empty window
                plan = self.shard_plan(j, z)
                r = plan.run()
                if stateful:
                    outs.append(r[0])
                    ress.append(r[1])
                else:
                    outs.append(r)
            # stage 3a: concatenate the mixed shards (they cover [0, D)
            # slot-aligned, in order) back into the full node buffer
            full = outs[0] if len(outs) == 1 else jnp.concatenate(outs,
                                                                  axis=1)
            out = full[:, None, :]
            res = None
            if stateful:
                res = (ress[0] if len(ress) == 1
                       else jnp.concatenate(ress, axis=1))
        # stage 3b: all-gather — broadcast each node's mixed model across
        # the intra axis (identity reshape when n_intra == 1)
        D = self.flat.shape[-1]
        out = jnp.broadcast_to(out, (g, k, D)).reshape(g * k, D)
        if stateful:
            return out, res
        return out


@dataclasses.dataclass(frozen=True)
class CommEngine:
    """One gossip round, end-to-end: codec x topology x backend + accounting.

    Static (hashable) configuration only — per-round dynamics (``theta``, the
    PRNG key, the ledger, WireState) are call arguments, so an engine can be
    constructed freely inside a jitted step function.

    ``path`` selects the gossip data path: ``"bucketed"`` stages the whole
    stacked pytree in one flat buffer (one encode launch, one packed roll
    per offset, one fused decode-reduce), ``"per_leaf"`` gossips leaf by
    leaf (the parity reference), and ``"auto"`` (default) picks per
    (layout, codec) from the measured crossover table (module docstring).
    Both paths draw the same stochastic-rounding uniforms per element
    (global counter indices), so they are bit-exact against each other for
    the Moniqua wire.

    ``topo`` may be a :class:`~repro.core.topology.HierarchicalTopology`,
    which turns every ``mix`` into a two-tier round (:class:`TieredPlan`):
    full-precision reduce-scatter/all-gather on the fast intra-node axis,
    quantized gossip of each worker's owned shard on the slow inter-node
    axis.  Tiered rounds always run in the staged flat-bucket domain
    (``path`` then governs per-*shard* launch granularity via the shard's
    own leaf census), and with a trivial intra tier (``n_intra == 1``)
    they are bit-exact against the single-tier bucketed round on the
    inter topology — payloads, outputs, and WireState.

    ``chunks`` sets the default chunk count for the staged round
    (``round_plan``): the bucketed flat buffer is split into that many
    slot-aligned windows and the phases software-pipelined.  ``chunks=1``
    is the barrier round; any K is bit-exact against it.

    ``telemetry`` (static, default off) attaches a round-health dict
    (``repro.obs.metrics``) to the returned :class:`MixResult`: consensus
    inf-distance and theta headroom, the modulo alias sentinel, EF residual
    norm, warmup indicator, payload bits/param.  The telemetry is purely
    observational — computed from the round's own flat buffer / payload /
    state with pure jnp, feeding nothing back into the mix — so the mixed
    output (and payload and WireState) is bit-exact with the flag on or
    off, and the health values themselves are identical across backends,
    gossip paths, and chunk counts (always evaluated on the canonical flat
    buffer with the jnp reference encode, which is bitwise equal to the
    Pallas and per-leaf payloads by the parity contracts).  When off, the
    flag is a Python-level branch: the telemetry graph is never traced,
    hence dead-code-free under jit.

    ``worker_axes`` names the mesh axes the stacked worker dim is sharded
    over (``()`` = not sharded).  With it the Moniqua codec kernels run
    under ``shard_map`` on those axes (``kernels/ops.py``), so each device
    encodes and decode-reduces its own workers and only the packed payload
    crosses devices; the caller enters the mesh with ``jax.set_mesh``.
    """
    topo: Any                     # Topology | HierarchicalTopology
    codec: Any = dataclasses.field(default_factory=MoniquaWire)
    backend: str = "auto"
    path: str = "auto"
    chunks: int = 1
    telemetry: bool = False
    worker_axes: Tuple[str, ...] = ()

    def __post_init__(self) -> None:
        if self.path not in PATHS:
            raise ValueError(f"unknown path {self.path!r}; one of {PATHS}")
        if int(self.chunks) < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")
        if self.worker_axes and self.tiered:
            raise ValueError("sharded codec kernels (worker_axes) are "
                             "single-tier only")

    # -- hierarchy plumbing ------------------------------------------------
    @property
    def tiered(self) -> bool:
        """True when the topology is two-tier (every mix is a TieredPlan)."""
        return isinstance(self.topo, HierarchicalTopology)

    @property
    def gossip_topo(self) -> Topology:
        """The tier whose edges carry *quantized* payloads: the inter tier
        of a hierarchy, or the whole (flat) topology."""
        return self.topo.inter if self.tiered else self.topo

    # -- persistent per-worker codec state (WireState) ---------------------
    @property
    def stateful(self) -> bool:
        """True for wires carrying per-worker state (EF residuals) across
        rounds; their ``mix`` takes a ``state`` carry and the returned
        ``MixResult.state`` must be threaded into the next round — like
        ``theta``, and checkpointed like params (``checkpoint/ckpt.py``
        serializes it inside trainer state)."""
        return bool(getattr(self.codec, "stateful", False))

    def init_wire_state(self, X: PyTree) -> dict:
        """Fresh ``WireState`` for a stacked pytree (``{}`` for stateless
        wires).  Accepts abstract ``ShapeDtypeStruct`` trees — only shapes
        are read, so trainers can build it under ``jax.eval_shape``.

        The residual lives in the *flat bucket domain* ``[n, padded_elems]``
        (one f32 per row-aligned element): both the bucketed and the
        per-leaf gossip paths read and write the same canonical buffer,
        which is what lets them produce bit-identical post-round state.

        Tiered engines shard the residual into the owned-shard domain:
        one ``[n_inter, padded_elems]`` buffer where row ``g``, window
        ``j`` is worker ``(g, j)``'s residual for the shard it encodes —
        ``n_intra``-fold smaller than the single-tier state (and identical
        to it when the intra tier is trivial).
        """
        if not self.stateful:
            return {}
        layout = self.layout(X)
        rows = (self.topo.n_inter if self.tiered else layout.n_workers)
        return {"residual": jnp.zeros((rows, layout.padded_elems),
                                      jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    def wire_state_bytes(self, X: PyTree) -> int:
        """Per-worker bytes of persistent codec state (Tables 1-2 memory
        column): 0 for full/moniqua/qsgd, residual + counter for EF wires.
        Tiered engines only persist each worker's owned shard, so the
        per-worker residual shrinks ``n_intra``-fold (reported as the
        exact per-worker average; shard windows are slot-aligned)."""
        if not self.stateful or not jax.tree.leaves(X):
            return 0
        elems = self.layout(X).padded_elems
        if self.tiered:
            elems = -(-elems // self.topo.n_intra)
        return elems * 4 + 4

    # -- gossip path resolution --------------------------------------------
    def resolved_path(self, X: PyTree,
                      shard: Optional[bucket.BucketChunk] = None) -> str:
        """The concrete path (``"bucketed"``/``"per_leaf"``) this engine
        takes for ``X``: the configured one, or — under ``"auto"`` — the
        measured per-(layout, codec) crossover.  Stateful wires always
        bucket (their canonical residual lives in the flat domain).

        With ``shard`` (a :meth:`~repro.comm.bucket.BucketLayout.shard`
        window), ``"auto"`` resolves on the *shard's own leaf census*, not
        the whole model's: a tiered round only encodes the window a worker
        owns, so the pad-amplification that decides bucketing must be the
        window's.  On a tiered engine ``"per_leaf"`` means slot-granular
        launches over the shard window (one chunk per slot).
        """
        if self.path != "auto":
            return self.path
        if self.stateful:
            return "bucketed"
        if shard is not None:
            return ("bucketed" if _auto_bucketed_slots(
                shard.slots, max(shard.size, 1), self.codec.name)
                else "per_leaf")
        layout = self.layout(X)
        return ("bucketed" if _auto_bucketed(layout, self.codec.name)
                else "per_leaf")

    def _use_bucketed(self, X: PyTree) -> bool:
        return self.resolved_path(X) == "bucketed"

    def staging(self, X: PyTree, presence=None) -> str:
        """The staging a :meth:`mix` round of ``X`` takes: ``"tiles"`` (the
        bucket staged in the codec kernels' tile shape ``[n, R, 1024]``,
        each kernel launched once over all workers), ``"flat"`` (the
        ``[n, D]`` bucket, :class:`RoundPlan`/:class:`TieredPlan`) or
        ``"per_leaf"``.  Tiles take the stateless Moniqua wire on the
        bucketed path with one tier, one chunk and no presence mask;
        segment statistics (qsgd, EF wires), chunk windows, tiers and
        masks live in the ``[n, D]`` domain."""
        if not self.tiered and not self._use_bucketed(X):
            return "per_leaf"
        if (self.codec.name == "moniqua" and not self.tiered
                and self.chunks == 1
                and _normalize_presence(presence, self.topo.n) is None):
            return "tiles"
        return "flat"

    def _shard_bucketed(self, shard: bucket.BucketChunk) -> bool:
        return self.resolved_path(None, shard=shard) == "bucketed"

    # -- the staged round --------------------------------------------------
    def round_plan(self, X: PyTree, theta=None,
                   key: Optional[jax.Array] = None,
                   state: Optional[dict] = None,
                   chunks: Optional[int] = None,
                   presence=None) -> RoundPlan:
        """Stage one gossip round on the flat bucket: returns a
        :class:`RoundPlan` whose ``encode_chunk``/``permute``/
        ``decode_reduce`` phases the caller can interleave (or just
        ``run()``).  ``chunks`` overrides the engine default K.

        The plan always works in the bucketed flat domain; a mixed-dtype
        tree on the raw wire has no bucketed round (f32 staging would
        change the mixing arithmetic) and raises here — ``mix`` handles
        that case by falling back to the per-leaf circulant.

        Tiered engines stage per owned shard instead (one RoundPlan per
        shard inside :class:`TieredPlan`); use :meth:`tiered_plan` / ``mix``.
        """
        if self.tiered:
            raise ValueError(
                "a tiered engine stages per owned shard; use "
                "tiered_plan()/mix() instead of round_plan()")
        layout = self.layout(X)
        if self.codec.name == "full" and not layout.uniform_dtype:
            raise ValueError(
                "no staged round for a mixed-dtype tree on the full wire "
                "(f32 staging would change the mixing arithmetic); "
                "use mix(), which falls back to the per-leaf circulant")
        if self.stateful:
            self._check_wire_state(state)
        k = self.chunks if chunks is None else int(chunks)
        backend = resolve_backend(self.backend)
        flat = layout.flatten(X)
        B = None
        seed = None
        residual = None
        step = None
        if self.codec.name != "full":
            self._require_key(key)
            seed = kops._key_to_seed(key)
        if self.codec.name == "moniqua":
            if theta is None:
                raise ValueError("MoniquaWire needs the a-priori bound theta")
            B = modulo.b_theta(theta, self.codec.spec.delta)
        if self.stateful:
            flat = flat.astype(jnp.float32)
            residual, step = state["residual"], state["step"]
        return RoundPlan(engine=self, layout=layout, chunks=layout.chunks(k),
                         flat=flat, backend=backend, theta=theta, B=B,
                         seed=seed, residual=residual, step=step,
                         presence=_normalize_presence(presence,
                                                      self.gossip_topo.n))

    def tiered_plan(self, X: PyTree, theta=None,
                    key: Optional[jax.Array] = None,
                    state: Optional[dict] = None,
                    chunks: Optional[int] = None,
                    presence=None) -> TieredPlan:
        """Stage one two-tier round (hierarchical engines): intra reduce,
        per-shard inter gossip, all-gather.  ``chunks`` is the per-shard
        sub-chunk count K (pipelined inside each shard's RoundPlan).
        """
        if not self.tiered:
            raise ValueError("tiered_plan needs a HierarchicalTopology "
                             "engine; use round_plan() on flat topologies")
        layout = self.layout(X)
        if self.codec.name == "full" and not layout.uniform_dtype:
            raise ValueError(
                "no tiered round for a mixed-dtype tree on the full wire "
                "(f32 staging would change the mixing arithmetic); stage "
                "the tree in one dtype or use a flat topology")
        if self.stateful:
            self._check_wire_state(state)
        k = self.chunks if chunks is None else int(chunks)
        backend = resolve_backend(self.backend)
        flat = layout.flatten(X)
        B = None
        seed = None
        residual = None
        step = None
        if self.codec.name != "full":
            self._require_key(key)
            seed = kops._key_to_seed(key)
        if self.codec.name == "moniqua":
            if theta is None:
                raise ValueError("MoniquaWire needs the a-priori bound theta")
            B = modulo.b_theta(theta, self.codec.spec.delta)
        if self.stateful:
            flat = flat.astype(jnp.float32)
            residual, step = state["residual"], state["step"]
        return TieredPlan(engine=self, layout=layout, flat=flat,
                          backend=backend, chunks=max(k, 1), theta=theta,
                          B=B, seed=seed, residual=residual, step=step,
                          presence=_normalize_presence(presence,
                                                       self.topo.n_inter))

    # -- the tentpole primitive --------------------------------------------
    def mix(self, X: PyTree, theta=None, key: Optional[jax.Array] = None,
            ledger: Optional[BytesLedger] = None,
            state: Optional[dict] = None, presence=None) -> MixResult:
        """One gossip round on stacked models (leaves ``[n, ...]``).

        Returns a :class:`MixResult`: ``.x`` is ``X_{k+1/2}`` (with the
        full-precision codec exactly the circulant ``X W`` of
        ``gossip.mix``), ``.state`` the post-round WireState (``{}`` for
        stateless wires; stateful wires require the ``state`` carry from
        :meth:`init_wire_state` and the caller must thread ``.state`` into
        the next round), ``.health`` the round-health dict when the engine
        has ``telemetry=True`` (else ``None``).  ``ledger`` (if given) is
        credited at trace time with payload-bytes * n_neighbors per round.

        ``presence`` (elastic rounds): per-worker 0/1 mask — per NODE
        (length ``n_inter``) on tiered engines.  Dead edges contribute
        identity (module docstring); ``None``/all-ones is bit-exact
        today's round.
        """
        if self.stateful:
            self._check_wire_state(state)
        if self.tiered:
            return self._mix_tiered(X, theta, key, ledger, state, presence)
        presence = _normalize_presence(presence, self.topo.n)
        offsets = self.topo.neighbor_offsets()
        if not offsets or not jax.tree.leaves(X):
            # single worker or empty pytree: nothing on the wire
            return self._empty_round(X, state)
        if ledger is not None:
            self._record(X, ledger)
        if self.codec.name == "moniqua" and theta is None:
            raise ValueError("MoniquaWire needs the a-priori bound theta")
        if self.stateful:
            Xm, new_state = self._mix_stateful(X, state, key, presence)
            health = (self._round_health(X, theta, key, new_state, presence)
                      if self.telemetry else None)
            return MixResult(Xm, new_state, health)
        layout = self.layout(X)
        full_mixed_dtype = (self.codec.name == "full"
                            and not layout.uniform_dtype)
        if self.staging(X, presence) == "tiles":
            Xm = self._mix_tiles(X, theta, key)
        elif self._use_bucketed(X) and not full_mixed_dtype:
            Xm = layout.unflatten(
                self.round_plan(X, theta=theta, key=key,
                                presence=presence).run())
        elif self.codec.name == "full":
            if presence is None:
                Xm = gossip.mix(X, self.topo)
            else:
                Xm = jax.tree.map(
                    lambda l: _masked_circulant(l, self.topo, presence), X)
        else:
            backend = resolve_backend(self.backend)
            self._require_key(key)
            base_seed = kops._key_to_seed(key)
            leaves, td = jax.tree.flatten(X)
            if self.codec.name == "moniqua":
                # global counter indices: leaf i's elements hash
                # (seed, layout.offset_i + e), the SAME pairs the bucketed
                # one-shot encode hashes — the bucketed-vs-per-leaf parity
                out = [self._mix_leaf(l, theta, base_seed, backend,
                                      idx_base=layout.offsets[i],
                                      presence=presence)
                       for i, l in enumerate(leaves)]
            else:
                out = [self._mix_leaf(l, theta, _leaf_seed(base_seed, i),
                                      backend, presence=presence)
                       for i, l in enumerate(leaves)]
            Xm = jax.tree.unflatten(td, out)
        health = (self._round_health(X, theta, key, None, presence)
                  if self.telemetry else None)
        return MixResult(Xm, {}, health)

    def _mix_tiles(self, X: PyTree, theta, key: Optional[jax.Array]
                   ) -> PyTree:
        """The tile-staged Moniqua round (:meth:`staging` ``"tiles"``): the
        kernels read and write the ``[n, R, 1024]`` staging buffer in
        place, and the packed ``[n, R, 1024 / vpb]`` payload's roll is the
        only cross-worker traffic.  Same per-element math, uniforms and
        payload bytes as the ``[n, D]`` round (``tests/test_engine.py``)."""
        self._require_key(key)
        spec = self.codec.spec
        backend = resolve_backend(self.backend)
        layout = self.layout(X)
        B = modulo.b_theta(theta, spec.delta)
        # on a worker mesh each device stages its own workers' leaves
        buf = kops.on_workers(layout.flatten_tiles, self.worker_axes, 1, X)
        with obs_trace.named_phase("comm.encode"):
            packed = kops.moniqua_encode_tiles(
                buf, B, spec, kops._key_to_seed(key), backend=backend,
                worker_axes=self.worker_axes)
        with obs_trace.named_phase("comm.permute"):
            nbrs = [gossip._roll(packed, o)
                    for o in self.topo.neighbor_offsets()]
        with obs_trace.named_phase("comm.decode_reduce"):
            out = kops.moniqua_decode_reduce_tiles(
                packed, nbrs, buf, B, self._neighbor_weights(), spec,
                backend=backend, worker_axes=self.worker_axes)
        return kops.on_workers(layout.unflatten_tiles, self.worker_axes, 1,
                               out)

    def _mix_tiered(self, X: PyTree, theta, key: Optional[jax.Array],
                    ledger: Optional[BytesLedger],
                    state: Optional[dict], presence=None) -> MixResult:
        """Tiered engines' round: stage and run a :class:`TieredPlan`.

        Tiered rounds always stage through the flat bucket — the intra
        reduce-scatter/all-gather is a whole-buffer operation, so there is
        no per-leaf variant to resolve to (``path`` only affects how stage
        2 sub-chunks each shard).
        """
        if not jax.tree.leaves(X) or self.topo.n == 1:
            return self._empty_round(X, state)
        if self.codec.name == "moniqua" and theta is None:
            raise ValueError("MoniquaWire needs the a-priori bound theta")
        if ledger is not None:
            self._record(X, ledger)
        plan = self.tiered_plan(X, theta=theta, key=key, state=state,
                                presence=presence)
        layout = plan.layout
        if self.stateful:
            out, res = plan.run()
            new_state = {"residual": res, "step": state["step"] + 1}
            Xm = layout.unflatten(out.astype(layout.stage_dtype))
            health = (self._round_health(X, theta, key, new_state,
                                         plan.presence)
                      if self.telemetry else None)
            return MixResult(Xm, new_state, health)
        Xm = layout.unflatten(plan.run())
        health = (self._round_health(X, theta, key, None, plan.presence)
                  if self.telemetry else None)
        return MixResult(Xm, {}, health)

    def _empty_round(self, X: PyTree, state: Optional[dict]) -> MixResult:
        """Degenerate round (single worker / empty pytree): same MixResult
        shape as the main path, nothing on the wire."""
        health = obs_metrics.round_health_zero() if self.telemetry else None
        carry = state if (state is not None) else {}
        return MixResult(X, carry, health)

    def _check_wire_state(self, state: Optional[dict]) -> None:
        if not isinstance(state, dict) or "residual" not in state:
            raise ValueError(
                f"{self.codec.name} wire is stateful: pass "
                "state=engine.init_wire_state(X) and thread the returned "
                "MixResult.state carry across rounds")

    # -- step-level overlap: one-round-stale mixing ------------------------
    def init_gossip_carry(self, X: PyTree) -> dict:
        """Fresh carry for :meth:`mix_stale` (stateless Moniqua only).

        Holds the payload of the *previous* round — the packed residue, the
        reference buffer it was encoded from, the modulo base ``B`` it was
        encoded under, and a validity flag (the first round has nothing to
        decode).  Accepts abstract shapes (build under ``eval_shape``).
        """
        if self.stateful or self.codec.name != "moniqua":
            raise ValueError(
                "one-round-stale overlap needs the stateless moniqua wire "
                f"(got {self.codec.name!r})")
        if self.tiered:
            raise ValueError(
                "one-round-stale overlap is single-tier only: a tiered "
                "round's payloads are per owned shard, not whole-buffer")
        layout = self.layout(X)
        vpb = self.codec.spec.values_per_byte
        return {"packed": jnp.zeros((layout.n_workers,
                                     layout.padded_elems // vpb), jnp.uint8),
                "ref": jnp.zeros((layout.n_workers, layout.padded_elems),
                                 jnp.float32),
                "B": jnp.zeros((), jnp.float32),
                "valid": jnp.zeros((), jnp.bool_)}

    def mix_stale(self, X: PyTree, carry: dict, theta=None,
                  key: Optional[jax.Array] = None,
                  ledger: Optional[BytesLedger] = None,
                  presence=None) -> MixResult:
        """One-round-stale gossip: apply the PREVIOUS round's payloads to
        this round's model, then encode the mixed result for the next round.

        The returned ``MixResult.state`` is the new carry (thread it like
        WireState).  Step k's model moves by the consensus delta computed
        from round k-1's payloads — decoded against the *reference they
        were encoded from*, under the *B they were encoded under* — so a
        trainer can issue the next forward pass while the previous round's
        decode-reduce is still in flight.  Delay-1 staleness is covered by
        the asynchronous-decentralized-SGD analyses in PAPERS.md; the first
        round (``valid`` unset) applies no delta.

        ``presence`` (elastic): this round's mask gates which of last
        round's payloads are applied — a dead edge's delta is dropped
        (identity), an absent worker applies nothing.  Everyone still
        re-encodes (an absent worker's payload is masked by the round in
        which it is absent, not the round after).
        """
        if self.stateful or self.codec.name != "moniqua":
            raise ValueError(
                "mix_stale needs the stateless moniqua wire "
                f"(got {self.codec.name!r})")
        if self.tiered:
            raise ValueError(
                "mix_stale is single-tier only: a tiered round's payloads "
                "are per owned shard, not whole-buffer")
        if not isinstance(carry, dict) or "packed" not in carry:
            raise ValueError(
                "pass carry=engine.init_gossip_carry(X) and thread the "
                "returned MixResult.state across steps")
        offsets = self.topo.neighbor_offsets()
        if not offsets or not jax.tree.leaves(X):
            return self._empty_round(X, carry)
        if theta is None:
            raise ValueError("MoniquaWire needs the a-priori bound theta")
        if ledger is not None:
            self._record(X, ledger)
        presence = _normalize_presence(presence, self.topo.n)
        backend = resolve_backend(self.backend)
        self._require_key(key)
        seed = kops._key_to_seed(key)
        spec = self.codec.spec
        layout = self.layout(X)
        weights = self._neighbor_weights()
        flat = layout.flatten(X).astype(jnp.float32)
        # decode round k-1 against its own reference/B, apply the delta late
        with obs_trace.named_phase("comm.decode_reduce"):
            p_nbrs = jnp.stack([gossip._roll(carry["packed"], o)
                                for o in offsets])
            if presence is None:
                mixed_ref = kops.moniqua_decode_reduce_stacked(
                    carry["packed"], p_nbrs, carry["ref"], carry["B"],
                    weights, spec, backend=backend,
                    worker_axes=self.worker_axes)
                delta = mixed_ref - carry["ref"]
            else:
                # elastic: gate each offset's decoded diff by the edge's
                # survival this round; absent rows apply no delta at all
                delta = jnp.zeros_like(carry["ref"])
                for k, (o, w) in enumerate(zip(offsets, weights)):
                    mixed_o = kops.moniqua_decode_reduce_stacked(
                        carry["packed"], p_nbrs[k:k + 1], carry["ref"],
                        carry["B"], (w,), spec, backend=backend,
                        worker_axes=self.worker_axes)
                    delta = delta + jnp.where(
                        _alive_cols(presence, o),
                        mixed_o - carry["ref"], 0.0)
                delta = jnp.where(_present_cols(presence), delta, 0.0)
            out = flat + jnp.where(carry["valid"], delta, 0.0)
        # encode round k from the post-mix model, for consumption at k+1
        B = modulo.b_theta(theta, spec.delta)
        with obs_trace.named_phase("comm.encode"):
            packed = kops.moniqua_encode_stacked(
                out, B, spec, seed, backend=backend,
                worker_axes=self.worker_axes)
        new_carry = {"packed": packed, "ref": out,
                     "B": jnp.asarray(B, jnp.float32),
                     "valid": jnp.ones((), jnp.bool_)}
        Xm = layout.unflatten(out.astype(layout.stage_dtype))
        health = (self._round_health(X, theta, key, None, presence)
                  if self.telemetry else None)
        return MixResult(Xm, new_carry, health)

    # -- round health (telemetry=True) -------------------------------------
    def _round_health(self, X: PyTree, theta, key: Optional[jax.Array],
                      new_state: Optional[dict],
                      presence: Optional[Tuple[int, ...]] = None) -> dict:
        """Health counters for the round just mixed (``repro.obs.metrics``).

        Always evaluated on the canonical flat bucket buffer with pure-jnp
        math, so the values are identical whichever backend, gossip path,
        or chunk count produced the mix: the per-leaf/chunked payloads
        concatenate to the bucketed one bitwise (PR-4 parity), and the jnp
        reference encode equals the Pallas kernel bitwise (PR-1 parity).
        On the bucketed moniqua path the sentinel's re-encode duplicates
        the round's own encode subgraph, which XLA CSEs away; elsewhere
        telemetry pays one extra encode per round — acceptable for an
        opt-in diagnostics flag.
        """
        with jax.named_scope("comm.telemetry"):
            layout = self.layout(X)
            flat = layout.flatten(X)
            offsets = self.topo.neighbor_offsets()
            h = obs_metrics.round_health_zero()
            h["consensus_inf"] = obs_metrics.consensus_inf(flat, offsets)
            h["bits_per_param"] = jnp.float32(
                8.0 * self.payload_bytes_per_broadcast(X)
                / max(layout.total_elems, 1))
            m = len(self.gossip_topo.neighbor_offsets())
            h["bytes_slow"] = jnp.float32(
                self.payload_bytes_per_broadcast(X) * m)
            h["bytes_fast"] = jnp.float32(self.fast_bytes_per_round(X))
            if presence is not None:
                # presence is a normalized static mask (partial by
                # construction: all-ones collapsed to None upstream)
                h["participation"] = jnp.float32(
                    sum(presence) / len(presence))
                h["dropped_neighbors"] = jnp.int32(
                    _dropped_edge_count(presence, self.gossip_topo))
            if self.codec.name == "moniqua" and theta is not None:
                spec = self.codec.spec
                theta = jnp.asarray(theta, jnp.float32)
                B = modulo.b_theta(theta, spec.delta)
                h["headroom"] = h["consensus_inf"] / B
                # tiered rounds encode per owned shard, so a whole-buffer
                # re-encode would not be bit-identical to the payloads the
                # round actually shipped: pin the sentinel to 0 instead of
                # reporting a number that doesn't describe the wire.
                if spec.delta < 0.25 and not self.tiered:
                    seed = kops._key_to_seed(key)
                    packed = kops.moniqua_encode_stacked(flat, B, spec,
                                                         seed, backend="jnp")
                    h["alias_count"] = obs_metrics.moniqua_alias_count(
                        packed, flat, B, theta, spec, offsets)
            if new_state is not None:
                h["ef_residual_l2"] = jnp.sqrt(jnp.sum(
                    jnp.square(new_state["residual"].astype(jnp.float32))))
                if self.codec.name == "onebit":
                    # the counter was already bumped: -1 recovers the flag
                    # the round just executed under
                    h["warm"] = (new_state["step"] - 1
                                 < self.codec.warmup).astype(jnp.float32)
            return h

    # -- stateful wires: error-feedback rounds on the flat bucket ----------
    def _mix_stateful(self, X: PyTree, state: dict,
                      key: Optional[jax.Array],
                      presence: Optional[Tuple[int, ...]] = None
                      ) -> Tuple[PyTree, dict]:
        """One EF gossip round; returns ``(X_{k+1/2}, new WireState)``.

        Both the bucketed (staged-plan) and the per-leaf paths run the same
        per-segment math on the canonical flat residual buffer: the
        bucketed round does it chunk by chunk over ``[n, D]`` (one
        segmented launch per chunk), the per-leaf round one leaf segment at
        a time (each leaf's payload rolled separately).  Same per-segment
        scales, same row-position rounding uniforms (``idx_base`` = the
        segment's bucket offset), same accumulation order — so outputs,
        payload bits, AND post-round state agree bitwise (the
        ``tests/test_engine.py`` stateful contracts).

        EF math runs in f32 on both backends (no Pallas kernel for the EF
        wires yet; ``resolve_backend`` still validates the name so the
        engine surface stays uniform).
        """
        layout = self.layout(X)
        if self._use_bucketed(X):
            out, res = self.round_plan(X, key=key, state=state,
                                       presence=presence).run()
        else:
            resolve_backend(self.backend)
            self._require_key(key)
            seed = kops._key_to_seed(key)
            flat = layout.flatten(X).astype(jnp.float32)
            residual, step = state["residual"], state["step"]
            out = jnp.zeros_like(flat)
            res = jnp.zeros_like(residual)
            for s in layout.slots:
                vi = jax.lax.slice_in_dim(flat, s.offset,
                                          s.offset + s.padded_size, axis=1)
                ri = jax.lax.slice_in_dim(residual, s.offset,
                                          s.offset + s.padded_size, axis=1)
                oi, rn = self._ef_flat_round(vi, ri, (s.padded_size,),
                                             s.offset, seed, step,
                                             presence)
                out = jax.lax.dynamic_update_slice(out, oi, (0, s.offset))
                res = jax.lax.dynamic_update_slice(res, rn, (0, s.offset))
        new_state = {"residual": res,
                     "step": state["step"] + jnp.int32(1)}
        return layout.unflatten(out.astype(layout.stage_dtype)), new_state

    def _ef_flat_round(self, v_base: jax.Array, residual: jax.Array,
                       segments: Tuple[int, ...], idx_base: int,
                       seed: jax.Array, step: jax.Array,
                       presence: Optional[Tuple[int, ...]] = None
                       ) -> Tuple[jax.Array, jax.Array]:
        """EF round on one flat f32 buffer slice (the per-leaf path): encode
        ``v = x + r``, gossip the codes, mix
        ``x + sum w_o (decode_j - decode_self)``, keep
        ``r' = v - decode_self``.  The bucketed path runs the identical
        math through ``RoundPlan`` phases.  ``presence`` gates dead edges
        to identity and carries absent rows' residuals untouched."""
        offsets = self.topo.neighbor_offsets()
        weights = self._neighbor_weights()
        spec = self.codec.spec

        def reduce(d_self, decode_neighbor):
            acc = None
            for o, w in zip(offsets, weights):
                t = decode_neighbor(o) - d_self
                if presence is not None:
                    t = jnp.where(_alive_cols(presence, o), t, 0.0)
                t = t * w
                acc = t if acc is None else acc + t
            return v_base + acc

        def mask_absent(out, res):
            if presence is None:
                return out, res
            here = _present_cols(presence)
            return (jnp.where(here, out, v_base),
                    jnp.where(here, res, residual))

        if self.codec.name == "ef_qsgd":
            v = v_base + residual
            with jax.named_scope("comm.encode"):
                packed, scales = ef_qsgd_encode_segmented(v, spec, seed,
                                                          segments, idx_base)
            with jax.named_scope("comm.decode_reduce"):
                d_self = qsgd_decode_segmented(packed, scales, spec,
                                               segments)
                out = reduce(d_self, lambda o: qsgd_decode_segmented(
                    gossip._roll(packed, o), gossip._roll(scales, o), spec,
                    segments))
            return mask_absent(out, v - d_self)

        # onebit: fp32 gossip during warmup, 1-bit sign codes + EF after.
        # The step counter is the need_reset-style switch.  Selected with
        # jnp.where, NOT lax.cond: cond branch bodies are optimized as
        # separate XLA computations whose fusion/FMA choices depend on the
        # buffer width, which breaks the bucketed-vs-per-leaf bitwise
        # contract at the ulp level.  Both value streams are cheap
        # elementwise math next to the communication, so computing both and
        # selecting is the right trade.
        warm_p = step < self.codec.warmup
        out_warm = (gossip.mix(v_base, self.topo) if presence is None
                    else _masked_circulant(v_base, self.topo, presence))
        v = v_base + residual
        packed, lo, hi = onebit_encode_segmented(v, seed, segments, idx_base,
                                                 spec.stochastic)
        d_self = onebit_decode_segmented(packed, lo, hi, segments)
        out_q = reduce(d_self, lambda o: onebit_decode_segmented(
            gossip._roll(packed, o), gossip._roll(lo, o),
            gossip._roll(hi, o), segments))
        return mask_absent(jnp.where(warm_p, out_warm, out_q),
                           jnp.where(warm_p, residual, v - d_self))

    def _mix_leaf(self, x: jax.Array, theta, seed: jax.Array,
                  backend: str, idx_base=0,
                  presence: Optional[Tuple[int, ...]] = None) -> jax.Array:
        if x.ndim == 1:      # scalar-per-worker leaf: give it a unit last axis
            return self._mix_leaf(x[:, None], theta, seed, backend,
                                  idx_base, presence)[:, 0]
        offsets = self.topo.neighbor_offsets()
        weights = self._neighbor_weights()
        if self.codec.name == "moniqua":
            spec = self.codec.spec
            B = modulo.b_theta(theta, spec.delta)
            # per-worker tiling: each worker's slice is encoded/decoded in
            # its own tile grid (kops stacked wrappers), so only the packed
            # payload roll crosses the worker axis and all workers share
            # one rounding-uniform stream per element (Supp. C)
            packed = kops.moniqua_encode_stacked(x, B, spec, seed,
                                                 backend=backend,
                                                 idx_base=idx_base,
                                                 worker_axes=self.worker_axes)
            p_nbrs = jnp.stack([gossip._roll(packed, o) for o in offsets])
            if presence is None:
                return kops.moniqua_decode_reduce_stacked(
                    packed, p_nbrs, x, B, weights, spec, backend=backend,
                    worker_axes=self.worker_axes)
            # elastic: fused decode-reduce per surviving offset, gated
            f = x.astype(jnp.float32)
            out = f
            for k, (o, w) in enumerate(zip(offsets, weights)):
                mixed_o = kops.moniqua_decode_reduce_stacked(
                    packed, p_nbrs[k:k + 1], x, B, (w,), spec,
                    backend=backend, worker_axes=self.worker_axes)
                out = out + jnp.where(_alive_cols(presence, o, x.ndim),
                                      mixed_o.astype(jnp.float32) - f, 0.0)
            return out.astype(x.dtype)
        # qsgd: reference-free decode; each worker ships (codes, own scale)
        spec = self.codec.spec
        packed, scale = qsgd_encode(x, spec, seed)
        xq_self = qsgd_decode(packed, scale, spec, x.shape[-1])
        acc = None
        for o, w in zip(offsets, weights):
            xq_j = qsgd_decode(gossip._roll(packed, o),
                               gossip._roll(scale, o), spec, x.shape[-1])
            t = xq_j - xq_self
            if presence is not None:
                t = jnp.where(_alive_cols(presence, o, x.ndim), t, 0.0)
            t = t * w
            acc = t if acc is None else acc + t
        return (x.astype(jnp.float32) + acc).astype(x.dtype)

    # -- layout plumbing ---------------------------------------------------
    def _align(self) -> int:
        """Row alignment of the flat buffer: values-per-byte for packed
        codecs (keeps per-leaf byte boundaries), 1 for the raw wire."""
        spec = getattr(self.codec, "spec", None)
        return spec.values_per_byte if spec is not None else 1

    def layout(self, X: PyTree) -> bucket.BucketLayout:
        """The (memoized) flat-buffer layout this engine uses for ``X``.

        Accepts abstract ``ShapeDtypeStruct`` trees, so callers (trainer,
        dryrun) can build the layout once outside jit; traced rounds then
        hit the cache with the identical static description.
        """
        return bucket.layout_of(X, self._align())

    def _neighbor_weights(self) -> Tuple[float, ...]:
        return _neighbor_weights_of(self.gossip_topo)

    def _require_key(self, key) -> None:
        """Stochastic rounding without a key would silently reuse seed 0
        every round, losing the across-step unbiasedness the convergence
        argument needs — fail loudly instead (matches the legacy path)."""
        spec = getattr(self.codec, "spec", None)
        if key is None and spec is not None and spec.stochastic:
            raise ValueError(
                f"{self.codec.name} wire with stochastic rounding needs a "
                "PRNG key (pass key=, or use a nearest-rounding QuantSpec)")

    # -- AD-PSGD's primitive: one edge exchange ----------------------------
    def init_edge_state(self, x: jax.Array) -> dict:
        """Per-endpoint ``WireState`` for :meth:`pair_average` (AD-PSGD
        edges): the EF residual lives in the padded flat domain of one
        model copy, plus the warmup step counter.  ``{}`` for stateless
        wires.  Accepts abstract shapes."""
        if not self.stateful:
            return {}
        vpb = self.codec.spec.values_per_byte
        size = int(np.prod(x.shape, dtype=np.int64))
        padded = -(-size // vpb) * vpb
        return {"residual": jnp.zeros((padded,), jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    def pair_average(self, xi: jax.Array, xj: jax.Array, theta=None,
                     key: Optional[jax.Array] = None,
                     state_i: Optional[dict] = None,
                     state_j: Optional[dict] = None,
                     presence=None) -> PairResult:
        """One gossip on edge (i, j) with the pair-averaging ``W_k``.

        Quantized codecs exchange payloads and decode against each endpoint's
        own model (Algorithm 3 lines 4-7); both endpoints encode under the
        same seed (shared randomness).  Simulator-scale API: always pure-jnp
        (AD-PSGD runs under ``lax.scan`` on host devices).

        Returns a :class:`PairResult`; stateful wires additionally require
        per-endpoint ``state_i`` / ``state_j`` carries from
        :meth:`init_edge_state` and fill ``.state_i`` / ``.state_j`` with
        the post-exchange carries (``{}`` for stateless wires).

        ``presence`` (elastic): a 2-mask ``(p_i, p_j)``.  If either
        endpoint is absent — or the message between them was dropped —
        the exchange is the *identity*: both models come back untouched
        and EF carries (step counters included) do not advance, exactly
        as if the edge had never fired.  ``sim.events.replay_adpsgd``
        routes fault-dropped exchanges through this, so the fault replay
        exercises the real engine API.
        """
        presence = _normalize_presence(presence, 2)
        if presence is not None:
            # at least one endpoint missing: identity exchange
            return PairResult(xi, xj,
                              state_i if self.stateful else {},
                              state_j if self.stateful else {})
        if self.stateful:
            return self._pair_average_stateful(xi, xj, key, state_i, state_j)
        if self.codec.name == "full":
            avg = 0.5 * (xi + xj)
            return PairResult(avg, avg)
        self._require_key(key)
        seed = kops._key_to_seed(key)
        if self.codec.name == "moniqua":
            spec = self.codec.spec
            B = modulo.b_theta(theta, spec.delta)
            pi = kops.moniqua_encode_jnp(xi, B, spec, seed)
            pj = kops.moniqua_encode_jnp(xj, B, spec, seed)
            n_last = xi.shape[-1]

            def val(p):
                return kops.moniqua_unpack_value(p, B, spec, n_last)

            xj_at_i = modulo.recover(val(pj), xi, B)
            xi_at_j = modulo.recover(val(pi), xj, B)
            xi_self = modulo.local_bias(val(pi), xi, B)
            xj_self = modulo.local_bias(val(pj), xj, B)
            return PairResult(xi + 0.5 * (xj_at_i - xi_self),
                              xj + 0.5 * (xi_at_j - xj_self))
        spec = self.codec.spec
        pi, si = qsgd_encode(xi, spec, seed, worker_axis=False)
        pj, sj = qsgd_encode(xj, spec, seed, worker_axis=False)
        qi = qsgd_decode(pi, si, spec, xi.shape[-1])
        qj = qsgd_decode(pj, sj, spec, xj.shape[-1])
        return PairResult(xi + 0.5 * (qj - qi), xj + 0.5 * (qi - qj))

    def _pair_average_stateful(self, xi: jax.Array, xj: jax.Array,
                               key: Optional[jax.Array],
                               state_i: Optional[dict],
                               state_j: Optional[dict]) -> PairResult:
        """EF edge exchange: each endpoint compensates with its own residual,
        ships codes of ``x + r``, and keeps ``r' = x + r - decode(sent)``."""
        for s in (state_i, state_j):
            if not isinstance(s, dict) or "residual" not in s:
                raise ValueError(
                    f"{self.codec.name} wire is stateful: pass state_i/"
                    "state_j=engine.init_edge_state(x) and thread the "
                    "returned PairResult.state_i/.state_j across edges")
        self._require_key(key)
        seed = kops._key_to_seed(key)
        spec = self.codec.spec
        size = int(np.prod(xi.shape, dtype=np.int64))
        padded = state_i["residual"].shape[0]
        seg = (padded,)

        def flat(x):
            f = jnp.ravel(x).astype(jnp.float32)
            return jnp.pad(f, (0, padded - size))[None, :]

        def unflat(f, like):
            return f[0, :size].reshape(like.shape).astype(like.dtype)

        fi, fj = flat(xi), flat(xj)
        vi = fi + state_i["residual"][None, :]
        vj = fj + state_j["residual"][None, :]

        if self.codec.name == "ef_qsgd":
            pi, si = ef_qsgd_encode_segmented(vi, spec, seed, seg)
            pj, sj = ef_qsgd_encode_segmented(vj, spec, seed, seg)
            di = qsgd_decode_segmented(pi, si, spec, seg)
            dj = qsgd_decode_segmented(pj, sj, spec, seg)
            oi, oj = fi + 0.5 * (dj - di), fj + 0.5 * (di - dj)
            ri, rj = vi - di, vj - dj
        else:
            # onebit: a mixed pair stays full-precision — the earlier of
            # the two counters decides warm-vs-quantized.  where-select
            # (not lax.cond) for the same bitwise-contract reason as the
            # gossip round.
            warm_p = jnp.minimum(state_i["step"],
                                 state_j["step"]) < self.codec.warmup
            avg = 0.5 * (fi + fj)
            pi, loi, hii = onebit_encode_segmented(vi, seed, seg, 0,
                                                   spec.stochastic)
            pj, loj, hij = onebit_encode_segmented(vj, seed, seg, 0,
                                                   spec.stochastic)
            di = onebit_decode_segmented(pi, loi, hii, seg)
            dj = onebit_decode_segmented(pj, loj, hij, seg)
            oi = jnp.where(warm_p, avg, fi + 0.5 * (dj - di))
            oj = jnp.where(warm_p, avg, fj + 0.5 * (di - dj))
            ri = jnp.where(warm_p, state_i["residual"][None, :], vi - di)
            rj = jnp.where(warm_p, state_j["residual"][None, :], vj - dj)
        return PairResult(
            unflat(oi, xi), unflat(oj, xj),
            {"residual": ri[0], "step": state_i["step"] + jnp.int32(1)},
            {"residual": rj[0], "step": state_j["step"] + jnp.int32(1)})

    def pair_health(self, xi: jax.Array, xj: jax.Array, theta=None,
                    key: Optional[jax.Array] = None) -> dict:
        """Round health of one :meth:`pair_average` edge exchange.

        Observational twin of ``mix``'s telemetry for the AD-PSGD
        primitive: consensus distance of the endpoints, plus (Moniqua) the
        theta headroom and both-direction alias sentinel on payloads
        re-encoded under the exchange seed — bit-identical to what
        ``pair_average`` ships.  Call on the *pre-exchange* endpoints.
        """
        with jax.named_scope("comm.telemetry"):
            spec = (self.codec.spec
                    if self.codec.name == "moniqua" else None)
            h = obs_metrics.pair_health(
                xi, xj, theta=theta, spec=spec,
                seed=kops._key_to_seed(key) if spec is not None else None)
            if spec is None:
                bits = getattr(getattr(self.codec, "spec", None), "bits",
                               32)
                h["bits_per_param"] = jnp.float32(
                    32.0 if self.codec.name == "full" else float(bits))
            return h

    # -- gossip building blocks shared by the algorithm zoo ----------------
    def neighbor_sum(self, X: PyTree, transform) -> PyTree:
        """``sum_{o != 0} w_o * transform(roll(X, -o), o)`` leaf-wise.

        Flat-topology primitive (replica-mixing baselines); tiered
        engines have no single circulant to roll on."""
        if self.tiered:
            raise ValueError(
                "neighbor_sum needs a flat circulant topology; the "
                "replica-mixing baselines do not support tiers")
        return gossip.neighbor_sum(X, self.topo, transform)

    def self_weight(self) -> float:
        if self.tiered:
            raise ValueError(
                "self_weight needs a flat circulant topology; the "
                "replica-mixing baselines do not support tiers")
        return gossip.self_weight(self.topo)

    # -- accounting --------------------------------------------------------
    def payload_bytes_per_broadcast(self, X: PyTree) -> int:
        """Bytes one worker ships to ONE neighbor per round.

        Bucketed rounds roll the packed flat buffer plus, for qsgd, the
        per-tensor scale vector; per-leaf rounds roll each leaf's payload.
        The vpb row alignment makes the bucketed Moniqua payload equal the
        per-leaf sum exactly — the tile-grid pad is sliced off before the
        roll and never rides the wire — and bucketed qsgd keeps one
        4-byte scale per tensor, so its bytes match the per-leaf sum too.
        A mixed-dtype tree on the ``full`` wire mixes per leaf (f32
        staging would change the arithmetic), so its bytes are the
        per-leaf sum as well.  Because the paths agree byte for byte,
        ``path="auto"`` resolution never changes this number.

        Tiered engines: each worker broadcasts only its *owned shard* on
        the slow axis.  The per-shard payloads sum to the whole-buffer
        staged payload exactly (``padded_elems // vpb`` and ``num_leaves``
        both distribute over slot-aligned shards), so one shard is a
        ceil'd ``n_intra``-th of the single-tier number — the ~n_intra-fold
        slow-axis reduction the hierarchy headline claims.
        """
        if not jax.tree.leaves(X):
            return 0
        if self.tiered:
            return -(-self._staged_payload_bytes(self.layout(X))
                     // self.topo.n_intra)
        if self.stateful:
            # EF wires gossip packed flat segments on BOTH paths (the
            # per-leaf round slices the same canonical bucket buffer), so
            # the accounting is layout-based either way.  onebit warmup
            # rounds ship f32 (``warmup_payload_bytes``); steady state is
            # what's reported.
            return self._staged_payload_bytes(self.layout(X))
        if self._use_bucketed(X):
            layout = self.layout(X)
            if self.codec.name == "full" and not layout.uniform_dtype:
                # per-leaf fallback path
                return sum(self.codec.payload_bytes(
                    leaf.shape[1:], leaf.dtype.itemsize)
                    for leaf in jax.tree.leaves(X))
            return self._staged_payload_bytes(layout)
        return sum(self.codec.payload_bytes(leaf.shape[1:],
                                            leaf.dtype.itemsize)
                   for leaf in jax.tree.leaves(X))

    def _staged_payload_bytes(self, layout: bucket.BucketLayout) -> int:
        """Whole-buffer payload on the staged (bucketed) path: packed codes
        plus per-segment scale words (one f32 for qsgd/ef_qsgd, a lo/hi
        level pair for onebit)."""
        if self.codec.name == "full":
            return layout.total_elems * jnp.dtype(
                layout.stage_dtype).itemsize
        spec = self.codec.spec
        nbytes = layout.padded_elems // spec.values_per_byte
        if self.codec.name in ("qsgd", "ef_qsgd"):
            nbytes += 4 * layout.num_leaves
        elif self.codec.name == "onebit":
            nbytes += 8 * layout.num_leaves
        return nbytes

    def fast_bytes_per_round(self, X: PyTree) -> int:
        """Fast-axis (intra) bytes one worker sends per tiered round:
        reduce-scatter plus all-gather of the staging buffer, i.e.
        ``2 * (n_intra - 1) / n_intra`` of it in the staging dtype (f32
        for EF wires, which stage in f32).  0 for single-tier engines
        and for a trivial intra tier.
        """
        if not self.tiered or not jax.tree.leaves(X):
            return 0
        k = self.topo.n_intra
        if k == 1:
            return 0
        layout = self.layout(X)
        itemsize = (4 if self.stateful
                    else jnp.dtype(layout.stage_dtype).itemsize)
        return 2 * itemsize * layout.padded_elems * (k - 1) // k

    def bytes_per_round(self, X: PyTree) -> int:
        """Payload bytes *sent* per worker per gossip round (all leaves).

        Tiered engines: the fast-axis reduce-scatter/all-gather bytes plus
        one owned-shard broadcast per *inter* neighbor on the slow axis.
        """
        m = len(self.gossip_topo.neighbor_offsets())
        return (self.fast_bytes_per_round(X)
                + self.payload_bytes_per_broadcast(X) * m)

    def _record(self, X: PyTree, ledger: BytesLedger) -> None:
        ledger.add(self.payload_bytes_per_broadcast(X),
                   len(self.gossip_topo.neighbor_offsets()), tier="slow")
        fast = self.fast_bytes_per_round(X)
        if fast:
            ledger.add(fast, 1, tier="fast")
