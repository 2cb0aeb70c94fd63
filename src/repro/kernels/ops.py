"""Jitted wrappers around the Moniqua codec kernels.

Handles arbitrary shapes/dtypes by flattening to a padded 2-D tile grid,
dispatching to the Pallas kernels (``interpret=True`` automatically off-TPU so
the same call validates on CPU), and restoring the caller's layout.

The packed layout matches ``core.quantizers.pack_codes`` (pack along the last
axis, zero-padded to the values-per-byte boundary; ``core/packing.py``) so
payload byte accounting is identical between the kernel and pure-jnp paths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantizers import QuantSpec, pack_codes, unpack_codes
from repro.kernels import moniqua_decode as _dec
from repro.kernels import moniqua_decode_reduce as _dr
from repro.kernels import moniqua_encode as _enc
from repro.kernels import ref as kref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _to_tiles(x: jax.Array, block_rows: int, block_cols: int):
    """Flatten to (rows, cols) padded to the tile grid; return unpad info."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    cols = block_cols
    rows = -(-n // cols)
    rows_p = -(-rows // block_rows) * block_rows
    pad = rows_p * cols - n
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows_p, cols), n


# Hash seed used when no PRNG key is supplied.  Only *deterministic*
# (nearest-rounding) specs may omit the key — the counter hash is never
# drawn on that path, so the constant is a documented placeholder, not a
# silent randomness source.  CommEngine._require_key rejects key=None for
# stochastic specs before this is ever reached; the legacy value 0 is kept
# so deterministic payload bits are unchanged across versions.
NO_KEY_SEED = 0


def _key_to_seed(key: Optional[jax.Array]) -> jax.Array:
    if key is None:
        return jnp.uint32(NO_KEY_SEED)
    return jax.random.key_data(key).reshape(-1)[-1].astype(jnp.uint32)


def _encode_layout(x: jax.Array, vpb: int):
    """Shared pad-to-tiles prologue for the kernel and pure-jnp encodes."""
    n_last = x.shape[-1] if x.ndim else 1
    pad = (-n_last) % vpb
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
    x2d, n = _to_tiles(xp, _enc.DEFAULT_BLOCK_ROWS, _enc.DEFAULT_BLOCK_COLS)
    return x2d, n, xp.shape[:-1], n_last, pad


def moniqua_encode(x: jax.Array, B: jax.Array, spec: QuantSpec,
                   key: Optional[jax.Array], *,
                   seed: Optional[jax.Array] = None,
                   interpret: Optional[bool] = None,
                   idx_base: jax.Array | int = 0) -> jax.Array:
    """Encode any-shape ``x`` -> packed uint8 with last dim ceil(n/vpb).

    Kernel-internal layout is a flat row-major tile grid; the public layout
    (matching ``pack_codes``) is recovered by unpack/repack only when the last
    dim is not already byte-aligned — the common aligned case is zero-copy.

    ``seed`` overrides the key-derived hash seed (CommEngine passes seeds
    directly so its jnp and Pallas backends draw identical uniforms).
    ``idx_base`` offsets the stochastic counter index — the flat-buffer
    offset of this tensor when it is one segment of a bucketed layout
    (``comm/bucket.py``), 0 for a standalone encode.
    """
    if interpret is None:
        interpret = not _on_tpu()
    if seed is None:
        seed = _key_to_seed(key)
    vpb = spec.values_per_byte
    x2d, n, lead_shape, n_last, pad = _encode_layout(x, vpb)
    p = _enc.encode(x2d, B, seed, bits=spec.bits, stochastic=spec.stochastic,
                    interpret=interpret, idx_base=idx_base)
    p = p.reshape(-1)[: n // vpb]
    return p.reshape(*lead_shape, (n_last + pad) // vpb)


def moniqua_encode_jnp(x: jax.Array, B: jax.Array, spec: QuantSpec,
                       seed: jax.Array,
                       idx_base: jax.Array | int = 0) -> jax.Array:
    """Pure-jnp encode, bit-identical to :func:`moniqua_encode`.

    Uses the same padded tile layout so the counter-based hash draws the same
    uniform per element as the kernel — the CommEngine jnp backend.  Packs
    with ``pack_codes``, the kernels' matrix-unit packing.
    """
    vpb = spec.values_per_byte
    x2d, n, lead_shape, n_last, pad = _encode_layout(x, vpb)
    idx = (jnp.asarray(idx_base, jnp.uint32)
           + jnp.arange(x2d.size, dtype=jnp.uint32).reshape(x2d.shape))
    codes = kref.codes_ref(x2d, B, spec.bits, spec.stochastic,
                           jnp.asarray(seed, jnp.uint32), idx)
    p = pack_codes(codes, spec.bits).reshape(-1)[: n // vpb]
    return p.reshape(*lead_shape, (n_last + pad) // vpb)


def _decode_common(packed: jax.Array, y: jax.Array, B, spec: QuantSpec,
                   mode: str, interpret: Optional[bool]) -> jax.Array:
    if interpret is None:
        interpret = not _on_tpu()
    vpb = spec.values_per_byte
    n_last = y.shape[-1]
    pad = (-n_last) % vpb
    yp = jnp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, pad)]) if pad else y
    br = _dec.DEFAULT_BLOCK_ROWS
    bc = _dec.DEFAULT_BLOCK_COLS
    y2d, n = _to_tiles(yp, br, bc)
    p2d = _p2d(packed, y2d.size // vpb, y2d.shape[0], y2d.shape[1] // vpb)
    out = _dec.decode(p2d, y2d, B, bits=spec.bits, mode=mode,
                      interpret=interpret)
    out = out.reshape(-1)[:n].reshape(yp.shape)
    if pad:
        out = out[..., :n_last]
    return out


def moniqua_decode_remote(packed, y, B, spec: QuantSpec, *,
                          interpret: Optional[bool] = None):
    return _decode_common(packed, y, B, spec, "remote", interpret)


def moniqua_decode_self(packed, x, B, spec: QuantSpec, *,
                        interpret: Optional[bool] = None):
    return _decode_common(packed, x, B, spec, "self", interpret)


# ---------------------------------------------------------------------------
# Fused decode-reduce: one gossip round's mixing in a single pass.
# ---------------------------------------------------------------------------

def _p2d(packed: jax.Array, p_need: int, rows: int, pcols: int) -> jax.Array:
    # jnp.pad, not zeros().at[].set(): the scatter form allocates and fills
    # a second full-size buffer on every mix; pad lowers to one concat
    pflat = packed.reshape(-1)
    return jnp.pad(pflat, (0, p_need - pflat.shape[0])).reshape(rows, pcols)


def moniqua_decode_reduce(p_self: jax.Array, p_nbrs: jax.Array, y: jax.Array,
                          B, weights, spec: QuantSpec, *,
                          interpret: Optional[bool] = None) -> jax.Array:
    """Fused gossip mix: ``y + sum_s w_s (xhat_s - xhat_self)`` (kernel path).

    ``p_nbrs`` stacks the neighbors' packed payloads on a new leading axis in
    topology offset order; ``weights`` are the matching static gossip weights.
    Handles arbitrary ``y`` shapes via the shared pad/tile layout.
    """
    if interpret is None:
        interpret = not _on_tpu()
    vpb = spec.values_per_byte
    n_last = y.shape[-1]
    pad = (-n_last) % vpb
    yp = jnp.pad(y, [(0, 0)] * (y.ndim - 1) + [(0, pad)]) if pad else y
    br, bc = _dr.DEFAULT_BLOCK_ROWS, _dr.DEFAULT_BLOCK_COLS
    y2d, n = _to_tiles(yp, br, bc)
    rows, pcols = y2d.shape[0], y2d.shape[1] // vpb
    p_need = rows * pcols
    ps2d = _p2d(p_self, p_need, rows, pcols)
    pn2d = [_p2d(p_nbrs[s], p_need, rows, pcols)
            for s in range(p_nbrs.shape[0])]
    out = _dr.decode_reduce(ps2d, pn2d, y2d, B, bits=spec.bits,
                            weights=tuple(float(w) for w in weights),
                            interpret=interpret)
    out = out.reshape(-1)[:n].reshape(yp.shape)
    return out[..., :n_last] if pad else out


def moniqua_decode_reduce_jnp(p_self: jax.Array, p_nbrs: jax.Array,
                              y: jax.Array, B, weights,
                              spec: QuantSpec) -> jax.Array:
    """Pure-jnp twin of :func:`moniqua_decode_reduce` (bit-exact off-TPU).

    Shares ``decode_reduce_values`` with the kernel body — same per-element
    f32 op sequence, same accumulation order, same optimization-barrier
    fences — so the CommEngine parity test asserts exact equality.
    """
    Bf = jnp.asarray(B, jnp.float32)
    n_last = y.shape[-1]

    def val(p):
        return _dr.unpack_values(p, spec.bits, Bf)[..., :n_last]

    qb_nbrs = [val(p_nbrs[s]) for s in range(p_nbrs.shape[0])]
    out = _dr.decode_reduce_values(val(p_self), qb_nbrs, y, Bf, weights)
    return out.astype(y.dtype)


# ---------------------------------------------------------------------------
# Stacked-worker wrappers: per-worker tiling over the leading [n, ...] axis.
#
# The tile layout above flattens a whole array (``_to_tiles``'s
# ``reshape(-1)``); applied directly to a stacked ``[n, ...]`` leaf that
# would cross the (sharded) worker axis — XLA could insert resharding
# around the encode/decode, and the counter-hash element index would differ
# per worker, breaking Supp. C's shared randomness.  These wrappers apply
# the layout to each worker's slice of axis 0 instead: each worker tiles its
# own slice with element indices 0..d-1 and the SAME seed, so (a) the only
# cross-worker traffic left in a CommEngine round is the packed
# collective-permute and (b) every worker draws identical rounding uniforms
# per element (Supp. C).
#
# ``worker_axes`` names the mesh axes the worker dim is sharded over (one
# worker per device on a worker mesh, entered with ``jax.set_mesh``).  The
# per-worker loop then runs under ``shard_map`` on those axes: each device
# launches the kernels on its own workers, and no custom call is left for
# XLA to partition (it cannot partition a Mosaic kernel: it either refuses
# or all-gathers the f32 operands onto every device).
# ---------------------------------------------------------------------------

def _per_worker(fn, worker_axes: tuple, in_axes: tuple, *args):
    """``fn`` on each worker's slice of ``args`` (worker dim ``in_axes[i]``,
    ``None`` = shared), stacked on a new axis 0; under ``shard_map`` over
    ``worker_axes`` when given.

    A Python loop over workers, not ``vmap``: batching the tile reshapes
    leaves XLA's TPU compiler relayouts of ``[n, D]`` arrays with ``n`` on
    the sublanes, whose compile time grows with ``D`` (over two minutes at
    the 1.2e8 elements of xlstm-125m, against seconds for the loop).
    """
    def local(*xs):
        n = next(x.shape[a] for x, a in zip(xs, in_axes) if a is not None)
        return jnp.stack([
            fn(*(x if a is None else jax.lax.index_in_dim(x, w, a, False)
                 for x, a in zip(xs, in_axes)))
            for w in range(n)])
    if not worker_axes:
        return local(*args)
    specs = tuple(P() if a is None else P(*([None] * a), tuple(worker_axes))
                  for a in in_axes)
    return jax.shard_map(local, in_specs=specs,
                         out_specs=P(tuple(worker_axes)),
                         check_vma=False)(*args)


def moniqua_encode_stacked(x: jax.Array, B, spec: QuantSpec,
                           seed: jax.Array, *, backend: str,
                           idx_base: jax.Array | int = 0,
                           worker_axes: tuple = ()) -> jax.Array:
    """Encode a stacked ``[n, ...]`` leaf with per-worker tile layout.

    ``idx_base`` is shared by every worker slice (the counter index never
    depends on the worker position — Supp. C shared randomness).
    """
    if backend == "pallas":
        def fn(xi, b, s):
            return moniqua_encode(xi, b, spec, None, seed=s,
                                  idx_base=idx_base)
    else:
        def fn(xi, b, s):
            return moniqua_encode_jnp(xi, b, spec, s, idx_base=idx_base)
    return _per_worker(fn, worker_axes, (0, None, None), x,
                       jnp.asarray(B, jnp.float32),
                       jnp.asarray(seed, jnp.uint32))


def moniqua_decode_reduce_stacked(p_self: jax.Array, p_nbrs: jax.Array,
                                  y: jax.Array, B, weights, spec: QuantSpec,
                                  *, backend: str,
                                  worker_axes: tuple = ()) -> jax.Array:
    """Fused decode-reduce over a stacked leaf, tiled per worker.

    ``p_self``/``y`` carry the worker axis at 0; ``p_nbrs`` stacks the
    neighbor payloads at axis 0 with the worker axis at 1 (the layout one
    ``jnp.roll`` per offset produces).
    """
    fn = (moniqua_decode_reduce if backend == "pallas"
          else moniqua_decode_reduce_jnp)
    return _per_worker(lambda ps, pn, yi, b: fn(ps, pn, yi, b, weights, spec),
                       worker_axes, (0, 1, 0, None), p_self, p_nbrs, y,
                       jnp.asarray(B, jnp.float32))


# ---------------------------------------------------------------------------
# Tile-staged launches: the whole round's buffer in the kernels' tile shape.
#
# ``BucketLayout.flatten_tiles`` stages the bucket as ``[n, R, 1024]``: each
# worker's flat buffer laid out in rows of the kernels' tile width, worker
# major.  Element ``e`` of worker ``w``'s buffer sits at ``[w, e // 1024,
# e % 1024]``, so the tile index ``row * 1024 + col`` IS the flat index the
# ``[n, D]`` round hashes (Supp. C: the worker never enters it).  Each
# kernel then runs once over all workers (grid ``(n, row blocks, 1)``) and
# reads and writes the staging buffer in place: no per-worker slice, no pad
# to the tile grid, no stack of the outputs.  The payload keeps the tile
# shape ``[n, R, 1024 / vpb]``; only its last row can carry padding.
# ---------------------------------------------------------------------------

def on_workers(fn, worker_axes: tuple, n_sharded: int, *args):
    """``fn(*args)``, under ``shard_map`` over ``worker_axes`` when given:
    the first ``n_sharded`` args (arrays or pytrees of them) carry the
    worker axis at 0, the rest are replicated."""
    if not worker_axes:
        return fn(*args)
    ax = P(tuple(worker_axes))
    specs = (ax,) * n_sharded + (P(),) * (len(args) - n_sharded)
    return jax.shard_map(fn, in_specs=specs, out_specs=ax,
                         check_vma=False)(*args)


def moniqua_encode_tiles(buf: jax.Array, B, spec: QuantSpec,
                         seed: jax.Array, *, backend: str,
                         worker_axes: tuple = ()) -> jax.Array:
    """Encode the tile-staged buffer ``[n, R, cols]`` -> ``[n, R,
    cols / vpb]`` packed bytes, every worker hashing the same flat indices
    ``row * cols + col``."""
    def pallas(x, b, s):
        return _enc.encode(x, b, s, bits=spec.bits,
                           stochastic=spec.stochastic,
                           interpret=not _on_tpu())

    def jnp_(x, b, s):
        rows, cols = x.shape[1:]
        idx = jnp.arange(rows * cols, dtype=jnp.uint32).reshape(rows, cols)
        return pack_codes(kref.codes_ref(x, b, spec.bits, spec.stochastic,
                                         s, idx), spec.bits)

    return on_workers(pallas if backend == "pallas" else jnp_, worker_axes,
                      1, buf, jnp.asarray(B, jnp.float32),
                      jnp.asarray(seed, jnp.uint32))


def moniqua_decode_reduce_tiles(p_self: jax.Array, p_nbrs, buf: jax.Array,
                                B, weights, spec: QuantSpec, *,
                                backend: str,
                                worker_axes: tuple = ()) -> jax.Array:
    """Fused decode-reduce of the tile-staged buffer ``[n, R, cols]``:
    ``p_self`` and each neighbor payload of ``p_nbrs`` (one operand per
    topology offset, in offset order) are ``[n, R, cols / vpb]``."""
    weights = tuple(float(w) for w in weights)
    m = len(weights)

    def pallas(ps, *rest):
        *pn, y, b = rest
        return _dr.decode_reduce(ps, pn, y, b, bits=spec.bits,
                                 weights=weights, interpret=not _on_tpu())

    def jnp_(ps, *rest):
        *pn, y, b = rest

        def val(p):
            return _dr.unpack_values(p, spec.bits, b)
        out = _dr.decode_reduce_values(val(ps), [val(p) for p in pn], y, b,
                                       weights)
        return out.astype(y.dtype)

    return on_workers(pallas if backend == "pallas" else jnp_, worker_axes,
                      m + 2, p_self, *p_nbrs, buf,
                      jnp.asarray(B, jnp.float32))


# ---------------------------------------------------------------------------
# Chunk-windowed launches: one pipeline stage of a staged gossip round.
#
# ``CommEngine.round_plan`` splits the flat [n, D] bucket into contiguous
# chunks (``comm/bucket.py::BucketLayout.chunks``) and encodes/decodes one
# window at a time so the chunk's collective-permute can overlap its
# neighbors' codec work.  Correctness hinges on the counter index: the
# window's elements must hash the SAME (seed, global index) pairs the
# one-shot whole-buffer encode hashes, so ``idx_base`` is the window's
# element offset in the buffer — that is the whole bit-exactness argument
# (identical per-element op sequence on a slice, identical uniforms).
# ---------------------------------------------------------------------------

def moniqua_encode_chunk(flat: jax.Array, offset: int, size: int, B,
                         spec: QuantSpec, seed: jax.Array, *,
                         backend: str, idx_base: Optional[int] = None,
                         worker_axes: tuple = ()) -> jax.Array:
    """Encode the window ``flat[:, offset:offset+size]`` of a stacked flat
    buffer, with globally-indexed rounding uniforms (``idx_base=offset``).

    ``idx_base`` overrides the counter base when ``flat`` is itself a
    window of a larger buffer (a shard plan slices at shard-local offsets
    but must hash *global* element indices to stay bit-exact against the
    whole-buffer encode).
    """
    win = jax.lax.slice_in_dim(flat, offset, offset + size, axis=1)
    return moniqua_encode_stacked(win, B, spec, seed, backend=backend,
                                  idx_base=offset if idx_base is None
                                  else idx_base, worker_axes=worker_axes)


def moniqua_decode_reduce_chunk(p_self: jax.Array, p_nbrs: jax.Array,
                                flat: jax.Array, offset: int, size: int, B,
                                weights, spec: QuantSpec, *,
                                backend: str,
                                worker_axes: tuple = ()) -> jax.Array:
    """Fused decode-reduce of one chunk's payloads against the matching
    window of the local flat buffer (decode draws no randomness, so only
    the window slice matters — no idx_base needed)."""
    win = jax.lax.slice_in_dim(flat, offset, offset + size, axis=1)
    return moniqua_decode_reduce_stacked(p_self, p_nbrs, win, B, weights,
                                         spec, backend=backend,
                                         worker_axes=worker_axes)


# Reference-path conveniences used by MoniquaCodec(use_pallas=True)

def moniqua_unpack_value(packed, B, spec: QuantSpec, last_dim: int):
    codes = unpack_codes(packed, spec.bits, last_dim)
    return ((codes.astype(jnp.float32) + 0.5) / spec.levels - 0.5) * B


def moniqua_recover(qb, y, B):
    return kref.cmod(qb - y.astype(jnp.float32), B) + y.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Flash attention: Pallas forward + reference backward (recompute).
# ---------------------------------------------------------------------------

def _sdpa_ref(q, k, v, scale, causal, window):
    """Masked-softmax oracle on [BH, S, D] layout (matches models/layers)."""
    sq, sk = q.shape[1], k.shape[1]
    scores = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    qi = jnp.arange(sq)[:, None]
    kj = jnp.arange(sk)[None, :]
    valid = jnp.ones((sq, sk), bool)
    if causal:
        valid &= kj <= qi
        if window:
            valid &= kj > qi - window
    scores = jnp.where(valid, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bqk,bkd->bqd", w, v)


@functools.lru_cache(maxsize=None)
def _flash_sdpa_fn(scale: float, causal: bool, window: int, interpret: bool):
    from repro.kernels.flash_attention import flash_attention

    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention(q, k, v, scale=scale, causal=causal,
                               window=window, interpret=interpret)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        # Recompute-based backward through the reference attention: the
        # forward never materialises scores (kernel); the backward pays the
        # jnp path once. A fused Pallas backward is the natural next step.
        q, k, v = res
        _, vjp = jax.vjp(lambda q_, k_, v_: _sdpa_ref(q_, k_, v_, scale,
                                                      causal, window),
                         q, k, v)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def flash_sdpa(q, k, v, *, scale: float, causal: bool = True,
               window: int = 0, interpret: Optional[bool] = None):
    """Differentiable flash attention on [..., S, H, D] tensors.

    Forward = Pallas kernel (scores stay in VMEM); backward = reference
    recompute.  interpret defaults to True off-TPU.
    """
    if interpret is None:
        interpret = not _on_tpu()
    *lead, S, H, D = q.shape
    Sk = k.shape[-3]
    fold = 1
    for n in lead:
        fold *= n
    qf = jnp.moveaxis(q, -2, -3).reshape(fold * H, S, D)
    kf = jnp.moveaxis(k, -2, -3).reshape(fold * H, Sk, D)
    vf = jnp.moveaxis(v, -2, -3).reshape(fold * H, Sk, D)
    o = _flash_sdpa_fn(scale, causal, window, interpret)(qf, kf, vf)
    o = o.reshape(*lead, H, S, D)
    return jnp.moveaxis(o, -3, -2)
