"""Quantizers satisfying the paper's bounded-error condition (Eq. 2).

A quantizer ``Q_delta`` must obey ``||Q(x) - x||_inf <= delta`` on
``x in [-1/2, 1/2]^d``.  Two families are provided:

* ``nearest``    -- biased linear quantizer: round to the lattice ``{2*delta*n}``.
* ``stochastic`` -- unbiased stochastic rounding on the same lattice, optionally
                    with *shared randomness* (same ``u`` on all workers; Supp. C).

Both are parameterised by a bit budget ``bits``: the lattice covers ``[-1/2, 1/2)``
with ``2**bits`` points, i.e. ``delta = 1 / (2 * (2**bits - 1))`` for nearest
rounding (``ceil(log2(1/(2 delta) + 1))`` bits suffice, Sec. 4 "Bound on the Bits").

Bit packing: quantized codes are integers in ``[0, 2**bits)`` packed into uint8
lanes (8/4/2/1 values per byte for 1/2/4/8 bits) so that the *communicated* array
is exactly ``bits/8`` bytes per parameter — the compression the roofline measures.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing


def delta_for_bits(bits: int, stochastic: bool = True) -> float:
    """Worst-case error of a ``bits``-wide linear quantizer on [-1/2, 1/2].

    We place the ``L = 2**bits`` representable points at the midpoints of the
    ``L`` cells tiling [-1/2, 1/2) (pitch ``1/L``).  Nearest rounding errs by
    at most half a pitch (``1/(2L)``); *stochastic* rounding moves to either
    adjacent point, erring by up to a full pitch (``1/L``).  The midpoint
    lattice is what makes 1-bit work: nearest 1-bit has ``delta = 1/4 < 1/2``
    as Theorem 3 requires (stochastic 1-bit has ``delta = 1/2`` and is
    rejected by ``modulo.b_theta``).
    """
    levels = 2 ** bits
    if levels < 2:
        raise ValueError(f"need at least 1 bit, got {bits}")
    return (1.0 / levels) if stochastic else (1.0 / (2.0 * levels))


def bits_for_delta(delta: float) -> int:
    """Paper Sec. 4: ``B <= ceil(log2(1/(2 delta) + 1))``."""
    return int(np.ceil(np.log2(1.0 / (2.0 * delta) + 1.0)))


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Static description of a quantizer.

    Attributes:
      bits: code width per parameter (1, 2, 4 or 8 for packable widths).
      stochastic: unbiased stochastic rounding if True, nearest (biased) if False.
      shared_randomness: reuse one uniform draw across all workers (Supp. C).
    """
    bits: int = 8
    stochastic: bool = True
    shared_randomness: bool = True

    @property
    def levels(self) -> int:
        return 2 ** self.bits

    @property
    def delta(self) -> float:
        return delta_for_bits(self.bits, self.stochastic)

    @property
    def values_per_byte(self) -> int:
        if self.bits not in (1, 2, 4, 8):
            raise ValueError(f"unpackable bit width {self.bits}")
        return 8 // self.bits

    @property
    def bytes_per_param(self) -> float:
        return self.bits / 8.0


# ---------------------------------------------------------------------------
# Code <-> value maps.  Codes 0..L-1 index the midpoints of the L cells tiling
# [-1/2, 1/2):   value(c) = (c + 1/2)/L - 1/2 ;  lattice(x) = (x + 1/2)*L - 1/2
# ---------------------------------------------------------------------------

def _to_lattice(x: jax.Array, levels: int) -> jax.Array:
    return (x.astype(jnp.float32) + 0.5) * levels - 0.5


def _from_lattice(c: jax.Array, levels: int) -> jax.Array:
    return (c.astype(jnp.float32) + 0.5) / levels - 0.5


def quantize_codes(
    x: jax.Array,
    spec: QuantSpec,
    key: Optional[jax.Array] = None,
) -> jax.Array:
    """Quantize ``x`` in [-1/2, 1/2] to integer codes in [0, levels).

    Stochastic mode implements ``Q(x) = delta_pitch * floor(x/pitch + u)`` with
    u ~ U[0,1) (the paper's stochastic rounding); nearest mode rounds half-up.
    Values outside [-1/2, 1/2] are clamped to the lattice ends (the theory never
    relies on behaviour outside the box).
    """
    lat = _to_lattice(x, spec.levels)
    if spec.stochastic:
        if key is None:
            raise ValueError("stochastic rounding needs a PRNG key")
        u = jax.random.uniform(key, x.shape, dtype=jnp.float32)
        codes = jnp.floor(lat + u)
    else:
        codes = jnp.floor(lat + 0.5)
    codes = jnp.clip(codes, 0, spec.levels - 1)
    return codes.astype(jnp.uint8 if spec.bits <= 8 else jnp.uint32)


def dequantize_codes(codes: jax.Array, spec: QuantSpec) -> jax.Array:
    return _from_lattice(codes, spec.levels)


def quantize(x: jax.Array, spec: QuantSpec, key: Optional[jax.Array] = None) -> jax.Array:
    """``Q_delta(x)``: quantize-then-dequantize (value-space round trip)."""
    return dequantize_codes(quantize_codes(x, spec, key), spec)


# ---------------------------------------------------------------------------
# Bit packing along the last axis.
# ---------------------------------------------------------------------------

def packed_last_dim(n: int, bits: int) -> int:
    vpb = 8 // bits
    return -(-n // vpb)  # ceil div


def pack_codes(codes: jax.Array, bits: int) -> jax.Array:
    """Pack integer codes (< 2**bits) into uint8 along the last axis.

    Pads the last axis with zeros up to a multiple of ``values_per_byte``;
    code ``b*vpb + j`` lands in byte ``b``, bit slot ``j`` (layout and its
    TPU-friendly computation: ``core/packing.py``).
    """
    return packing.pack(codes, bits)


def unpack_codes(packed: jax.Array, bits: int, n: int) -> jax.Array:
    """Inverse of :func:`pack_codes`; ``n`` is the original last-axis length."""
    if bits == 8:
        return packed[..., :n]
    return packing.unpack(packed, bits, n).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# QSGD-style scale + codes codec (Alistarh et al., 2017).
#
# Unlike Moniqua, QSGD transmits an explicit per-tensor scale alongside the
# codes: the sender normalizes by its own max-norm, quantizes the normalized
# value on the same midpoint lattice, and ships (packed codes, f32 scale).
# Payload = bits/8 bytes per parameter + 4 bytes per tensor per worker.  It
# needs no a-priori theta bound but pays the extra scale word and loses the
# modulo trick's reference-free exactness — the comparison CommEngine exposes.
# ---------------------------------------------------------------------------

def _counter_uniform(seed: jax.Array, idx: jax.Array) -> jax.Array:
    """murmur3-finalizer hash of (seed, idx) -> uniform f32 in [0, 1).

    Counter-based so that encode needs no PRNG-state threading and so the
    same (seed, element) pair draws the same uniform on every worker — the
    shared-randomness convention the Pallas encode kernel also uses.

    The final cast goes through int32: Mosaic has no ``uint32 -> float32``
    conversion, and ``h >> 8`` fits in 24 bits, so the detour is exact.
    """
    h = (idx.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)) ^ seed.astype(jnp.uint32)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return ((h >> 8).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(1.0 / (1 << 24)))


def qsgd_encode(x: jax.Array, spec: QuantSpec,
                seed: Optional[jax.Array] = None,
                worker_axis: bool = True) -> tuple[jax.Array, jax.Array]:
    """Encode ``x`` -> (packed codes, per-worker scale).

    With ``worker_axis`` the leading dim of ``x`` indexes workers and each
    worker row gets its own max-norm scale (shape ``[n, 1, ..., 1]``);
    otherwise one scalar scale covers the whole tensor.
    """
    xf = x.astype(jnp.float32)
    red = tuple(range(1, x.ndim)) if (worker_axis and x.ndim > 1) else None
    scale = jnp.max(jnp.abs(xf), axis=red, keepdims=True) + 1e-12
    r = xf / (2.0 * scale)                      # in [-1/2, 1/2]
    lat = _to_lattice(r, spec.levels)
    if spec.stochastic:
        if seed is None:
            raise ValueError("stochastic QSGD rounding needs a seed")
        idx = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
        codes = jnp.floor(lat + _counter_uniform(jnp.asarray(seed, jnp.uint32),
                                                 idx))
    else:
        codes = jnp.floor(lat + 0.5)
    codes = jnp.clip(codes, 0, spec.levels - 1).astype(jnp.uint8)
    return pack_codes(codes, spec.bits), scale


def qsgd_decode(packed: jax.Array, scale: jax.Array, spec: QuantSpec,
                last_dim: int) -> jax.Array:
    """Inverse of :func:`qsgd_encode`: codes -> values in [-scale, scale]."""
    codes = unpack_codes(packed, spec.bits, last_dim)
    return _from_lattice(codes, spec.levels) * (2.0 * scale)


def _segment_scale_map(scales: jax.Array, segments) -> jax.Array:
    """Broadcast per-segment scales ``[n, L]`` to element width ``[n, D]``.

    ``segments`` is the static tuple of per-segment lengths (contiguous
    ranges of the flat bucket).  Slices + broadcasts, NOT an element->id
    gather: a ``D``-sized index constant in the graph makes XLA's
    constant folder crawl for multi-million-element buckets.
    """
    n = scales.shape[0]
    return jnp.concatenate(
        [jnp.broadcast_to(scales[:, i:i + 1], (n, size))
         for i, size in enumerate(segments)], axis=1)


def qsgd_encode_segmented(x: jax.Array, spec: QuantSpec,
                          seed: Optional[jax.Array],
                          segments: tuple[int, ...],
                          idx_base: int = 0,
                          idx_stride: Optional[int] = None
                          ) -> tuple[jax.Array, jax.Array]:
    """QSGD on a flat ``[n, D]`` bucket with one scale per *segment*.

    ``segments`` gives the length of each tensor's contiguous range in
    the bucket (``BucketLayout.segment_sizes``), so the scale granularity
    matches the per-leaf path — one max-norm per tensor per worker.  A
    single whole-model scale would let a 100-scale weight matrix drown a
    0.01-scale bias in quantization noise; this keeps small tensors
    representable while the quantize/pack work stays one fused launch
    over the whole bucket.  Returns (packed codes ``[n, D*bits/8]``,
    scales ``[n, L]`` — both ride the wire).

    The rounding-uniform counter for element ``(w, e)`` is
    ``w * idx_stride + idx_base + e``.  With the defaults (``idx_base=0``,
    ``idx_stride = x.shape[-1]``) that is exactly the row-major flat index
    of the whole buffer — the historical bit stream.  A *chunked* encode
    (``CommEngine.round_plan``) passes the chunk's buffer offset and the
    FULL buffer width as the stride, so each element hashes the same
    ``(seed, global index)`` pair it would in the one-shot encode and the
    pipelined round stays bit-exact against the barrier round.
    """
    xf = x.astype(jnp.float32)
    off, parts = 0, []
    for size in segments:
        seg = jax.lax.slice_in_dim(xf, off, off + size, axis=1)
        parts.append(jnp.max(jnp.abs(seg), axis=1, keepdims=True))
        off += size
    scales = jnp.concatenate(parts, axis=1) + 1e-12     # [n, L]
    smap = _segment_scale_map(scales, segments)         # [n, D]
    lat = _to_lattice(xf / (2.0 * smap), spec.levels)
    if spec.stochastic:
        if seed is None:
            raise ValueError("stochastic QSGD rounding needs a seed")
        stride = x.shape[-1] if idx_stride is None else int(idx_stride)
        idx = (jnp.arange(x.shape[0], dtype=jnp.uint32)[:, None]
               * jnp.uint32(stride)
               + jnp.arange(x.shape[-1], dtype=jnp.uint32)[None, :]
               + jnp.uint32(idx_base))
        codes = jnp.floor(lat + _counter_uniform(jnp.asarray(seed, jnp.uint32),
                                                 idx))
    else:
        codes = jnp.floor(lat + 0.5)
    codes = jnp.clip(codes, 0, spec.levels - 1).astype(jnp.uint8)
    return pack_codes(codes, spec.bits), scales


def qsgd_decode_segmented(packed: jax.Array, scales: jax.Array,
                          spec: QuantSpec,
                          segments: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`qsgd_encode_segmented` on the flat bucket."""
    codes = unpack_codes(packed, spec.bits, sum(segments))
    smap = _segment_scale_map(scales, segments)
    return _from_lattice(codes, spec.levels) * (2.0 * smap)


def qsgd_payload_bytes(x_shape: tuple[int, ...], bits: int) -> int:
    """Wire bytes for one tensor: packed codes + one f32 scale."""
    if not x_shape:
        return 1 + 4
    inner = int(np.prod(x_shape[:-1], dtype=np.int64))
    return inner * packed_last_dim(x_shape[-1], bits) + 4


# ---------------------------------------------------------------------------
# Error-feedback codec family (Tang et al. 2019; Seide et al. 1-bit SGD;
# Tang et al. 2021 "1-bit Adam").  Unlike Moniqua, these wires carry
# *persistent per-worker state*: an f32 residual buffer accumulating what
# quantization dropped, re-injected into the next round's compressed value.
# The repo prices that Θ(nd) memory against Moniqua's zero-extra-memory
# claim in BENCH_memory_overhead.json.
#
# Randomness convention: stochastic rounding draws one uniform per flat
# *row position* (``idx_base + e``), hashed worker-free — every worker and
# both the bucketed and per-leaf gossip paths see the same uniform for a
# given element, which is what makes the paths bit-exact against each
# other (the ``tests/test_ef_codecs.py`` / ``tests/test_engine.py``
# contracts) and preserves Supp.-C shared randomness.
# ---------------------------------------------------------------------------

def _position_uniform(seed: jax.Array, idx_base, width: int) -> jax.Array:
    """``[1, width]`` uniforms hashed from the flat row position only."""
    idx = jnp.arange(width, dtype=jnp.uint32) + jnp.uint32(idx_base)
    return _counter_uniform(jnp.asarray(seed, jnp.uint32), idx)[None, :]


def ef_qsgd_encode_segmented(v: jax.Array, spec: QuantSpec,
                             seed: Optional[jax.Array],
                             segments: tuple[int, ...],
                             idx_base: int = 0
                             ) -> tuple[jax.Array, jax.Array]:
    """QSGD codes for an error-compensated flat ``[n, D]`` bucket.

    Same scale+codes wire format as :func:`qsgd_encode_segmented` (one
    max-norm f32 scale per segment, packed codes), but rounding uniforms
    come from the worker-free row-position hash so the per-leaf and
    bucketed paths (and all workers) draw identical uniforms.  ``v`` is
    the *compensated* value ``x + residual``; the caller keeps
    ``residual' = v - decode(sent)`` (see ``CommEngine._ef_flat_round``).
    """
    vf = v.astype(jnp.float32)
    off, parts = 0, []
    for size in segments:
        seg = jax.lax.slice_in_dim(vf, off, off + size, axis=1)
        parts.append(jnp.max(jnp.abs(seg), axis=1, keepdims=True))
        off += size
    scales = jnp.concatenate(parts, axis=1) + 1e-12     # [n, L]
    smap = _segment_scale_map(scales, segments)         # [n, D]
    lat = _to_lattice(vf / (2.0 * smap), spec.levels)
    if spec.stochastic:
        if seed is None:
            raise ValueError("stochastic EF-QSGD rounding needs a seed")
        codes = jnp.floor(lat + _position_uniform(seed, idx_base,
                                                  vf.shape[-1]))
    else:
        codes = jnp.floor(lat + 0.5)
    codes = jnp.clip(codes, 0, spec.levels - 1).astype(jnp.uint8)
    return pack_codes(codes, spec.bits), scales


def onebit_encode_segmented(v: jax.Array, seed: Optional[jax.Array],
                            segments: tuple[int, ...],
                            idx_base: int = 0, stochastic: bool = False
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """1-bit sign codec with per-segment cluster-mean levels (1-bit Adam
    wire; Seide et al. 2014 reconstruction).

    Each segment partitions elements by sign and ships two f32
    reconstruction levels — ``lo`` = mean of the negative cluster, ``hi``
    = mean of the non-negative cluster — plus one bit per element
    choosing a level: code 1 decodes to exactly ``hi``, code 0 to exactly
    ``lo`` (decode is a select, not arithmetic, so the shipped levels
    round-trip bitwise).  Cluster means, NOT the literal segment min/max:
    reconstructing at the cluster means makes the compression error the
    within-cluster variance, strictly below ``||v||^2`` — a contractive
    compressor, which the error-feedback loop needs.  Min/max endpoint
    levels are not contractive near consensus (every mid-range element
    pays ~span/2 error, so ``||err|| >> ||v||`` once workers agree) and
    measurably diverge under iterated gossip.

    Nearest mode codes the sign partition itself (deterministic, as in
    the 1-bit SGD/Adam literature — EF absorbs the bias); stochastic mode
    picks ``hi`` with probability ``(v - lo) / (hi - lo)`` (clipped),
    drawing from the row-position hash.  Returns
    ``(packed bits, lo [n, L], hi [n, L])``.
    """
    vf = v.astype(jnp.float32)
    pos = vf >= 0.0
    off, los, his = 0, [], []
    for size in segments:
        seg = jax.lax.slice_in_dim(vf, off, off + size, axis=1)
        m = jax.lax.slice_in_dim(pos, off, off + size, axis=1)
        n_pos = jnp.sum(m, axis=1, keepdims=True)
        pos_sum = jnp.sum(jnp.where(m, seg, 0.0), axis=1, keepdims=True)
        neg_sum = jnp.sum(jnp.where(m, 0.0, seg), axis=1, keepdims=True)
        his.append(pos_sum / jnp.maximum(n_pos, 1))
        los.append(neg_sum / jnp.maximum(size - n_pos, 1))
        off += size
    lo = jnp.concatenate(los, axis=1)                   # [n, L]
    hi = jnp.concatenate(his, axis=1)
    if stochastic:
        if seed is None:
            raise ValueError("stochastic 1-bit rounding needs a seed")
        lomap = _segment_scale_map(lo, segments)        # [n, D]
        span = _segment_scale_map(hi, segments) - lomap
        lat = jnp.clip((vf - lomap) / jnp.where(span > 0, span, 1.0),
                       0.0, 1.0)
        codes = jnp.floor(lat + _position_uniform(seed, idx_base,
                                                  vf.shape[-1]))
        codes = jnp.clip(codes, 0, 1).astype(jnp.uint8)
    else:
        codes = pos.astype(jnp.uint8)
    return pack_codes(codes, 1), lo, hi


def onebit_decode_segmented(packed: jax.Array, lo: jax.Array, hi: jax.Array,
                            segments: tuple[int, ...]) -> jax.Array:
    """Inverse of :func:`onebit_encode_segmented`: select lo/hi per bit."""
    codes = unpack_codes(packed, 1, sum(segments))
    lomap = _segment_scale_map(lo, segments)
    himap = _segment_scale_map(hi, segments)
    return jnp.where(codes.astype(bool), himap, lomap)


def onebit_payload_bytes(x_shape: tuple[int, ...]) -> int:
    """Steady-state wire bytes for one tensor: 1 bit/param + lo/hi words."""
    if not x_shape:
        return 1 + 8
    inner = int(np.prod(x_shape[:-1], dtype=np.int64))
    return inner * packed_last_dim(x_shape[-1], 1) + 8


# ---------------------------------------------------------------------------
# Worker-indexed keys for (non-)shared randomness.
# ---------------------------------------------------------------------------

def rounding_key(base: jax.Array, step: jax.Array | int, worker: int, spec: QuantSpec) -> jax.Array:
    """PRNG key for stochastic rounding at a given step/worker.

    With ``shared_randomness`` every worker derives the *same* key for a given
    step, so exchanged tensors are floored with the same ``u`` (Supp. C shows
    this bounds the pairwise error by the model distance instead of 2*delta*B).
    """
    k = jax.random.fold_in(base, jnp.asarray(step, dtype=jnp.uint32))
    if not spec.shared_randomness:
        k = jax.random.fold_in(k, worker)
    return k
