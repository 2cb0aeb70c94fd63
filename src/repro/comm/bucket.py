"""Bucketed flat-buffer gossip: one staging buffer for the whole model.

The per-leaf gossip round in ``CommEngine.mix`` pays a fixed cost per pytree
leaf: one encode launch, one decode-reduce launch, one payload roll per
offset, and — dominating everything for small leaves — one pad to the
256x1024 tile grid, which turns a 64-element bias into >=262k elements of
codec work.  A ResNet/transformer has dozens of sub-262k leaves (biases,
norms, scales), so dispatch + padding overhead swamps the tiny payloads a
1-bit wire actually ships.  This is the classic tensor-fusion observation
(Bagua's ``BaguaBucket``, Horovod's fusion buffer): flatten everything into
one contiguous buffer, pay the fixed costs once.

:class:`BucketLayout` is that buffer's static description.  Built once per
(treedef, leaf shapes/dtypes, alignment) — :func:`layout_of` memoizes — it
flattens a stacked ``[n, ...]`` pytree into one ``[n, D]`` staging buffer
and scatters the mixed result back.  Two invariants make the bucketed round
*bit-exact* against the per-leaf path (the contract
``tests/test_engine.py`` enforces):

1. **Per-leaf vpb row alignment.**  Each leaf's segment is the leaf
   flattened with its last axis zero-padded to the values-per-byte
   boundary — exactly the padding ``kernels/ops.py::_encode_layout``
   applies per leaf — so byte boundaries in the packed flat payload line
   up with the per-leaf payloads and the concatenation of per-leaf
   payload bytes IS the bucketed payload, bit for bit.
2. **Global element indexing.**  Element ``e`` of leaf ``i`` occupies flat
   position ``offset_i + e`` (row-padded positions), and the per-leaf path
   passes ``offset_i`` as the encode kernels' ``idx_base`` — both paths
   hash the same ``(seed, global_index)`` pair per element, so stochastic
   rounding draws identical uniforms (Supp.-C shared randomness is
   preserved: the worker axis never enters the index).

The Moniqua round stages the same buffer in the codec kernels' tile shape
instead: :meth:`BucketLayout.flatten_tiles` writes each segment straight
into ``[n, R, 1024]`` (worker major, ``R = ceil(padded_elems / 1024)``),
element ``e`` of leaf ``i`` at flat position ``offset_i + e`` as before,
so the kernels read and write it in place with no per-worker slice, pad
or stack.  Only the tail of the last row is padding, and it is the only
padding the packed payload carries (at most one row); the ``[n, D]``
rounds (``flatten``) slice their tile padding off inside
``kernels/ops.py`` before the payload rolls, so their payload bytes equal
the per-leaf sum exactly.

Staging dtype: leaves sharing one floating dtype stage natively (a uniform
bf16 tree ships bf16 on the full-precision wire); mixed-dtype trees stage
in f32.  Widening casts are exact, so the quantized codecs stay bit-exact
either way; the full-precision wire, whose *mixing arithmetic* would
change under f32 staging, falls back to the per-leaf circulant mix on
mixed-dtype trees (``CommEngine._mix_bucketed``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.moniqua_encode import DEFAULT_BLOCK_COLS as TILE_COLS
from repro.obs import trace as obs_trace

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Static placement of one stacked leaf inside the flat buffer."""
    shape: Tuple[int, ...]   # per-worker shape (leaf.shape[1:])
    dtype: Any               # original leaf dtype (restored on scatter)
    rows: int                # prod(shape[:-1]); 1 for scalar-per-worker
    last: int                # shape[-1]; 1 for scalar-per-worker
    last_padded: int         # last rounded up to the alignment
    size: int                # rows * last (real elements)
    padded_size: int         # rows * last_padded (elements in the buffer)
    offset: int              # element offset of this segment in the buffer


@dataclasses.dataclass(frozen=True)
class BucketChunk:
    """One contiguous window of the flat buffer, covering whole leaf slots.

    The staged-round pipeline (``CommEngine.round_plan``) encodes, permutes
    and decode-reduces one chunk at a time.  Chunk boundaries always fall on
    slot boundaries, so per-tensor codec statistics (qsgd's max-norm scale,
    onebit's lo/hi levels) never straddle a chunk, and — because every
    ``padded_size`` is a multiple of the layout alignment — chunk offsets
    stay on the values-per-byte packing boundary.
    """
    index: int               # position in the chunk sequence
    offset: int              # element offset of the window in the buffer
    size: int                # padded elements in the window
    slots: Tuple[LeafSlot, ...]   # the (contiguous) slots covered

    @property
    def segment_sizes(self) -> Tuple[int, ...]:
        """Per-tensor segment lengths inside this chunk (cf.
        ``BucketLayout.segment_sizes``, restricted to the window)."""
        return tuple(s.padded_size for s in self.slots)

    def chunks(self, k: int) -> Tuple["BucketChunk", ...]:
        """Sub-partition this window into (at most) ``k`` slot-aligned
        chunks — the same greedy sweep :meth:`BucketLayout.chunks` uses, so
        a shard window composes with the staged round's ``chunks=K``
        pipelining.  Offsets stay *global* buffer offsets (they are the
        encode kernels' ``idx_base``).  An empty window yields no chunks.
        """
        if not self.slots:
            return ()
        return _partition_slots(self.slots, max(int(k), 1))


@dataclasses.dataclass(frozen=True)
class BucketLayout:
    """Cached flat-buffer layout for one stacked pytree structure.

    ``flatten``/``unflatten`` are pure jnp and safe inside jit; everything
    else is static Python computed once per structure (``layout_of``).
    """
    treedef: Any
    slots: Tuple[LeafSlot, ...]
    n_workers: int
    align: int               # values-per-byte row alignment (1 = none)
    stage_dtype: Any         # staging dtype of the flat buffer

    @property
    def num_leaves(self) -> int:
        return len(self.slots)

    @property
    def total_elems(self) -> int:
        """Real elements per worker (no padding)."""
        return sum(s.size for s in self.slots)

    @property
    def padded_elems(self) -> int:
        """Flat-buffer elements per worker (row padding included)."""
        return sum(s.padded_size for s in self.slots)

    @property
    def offsets(self) -> Tuple[int, ...]:
        """Per-leaf element offsets — the encode kernels' ``idx_base``."""
        return tuple(s.offset for s in self.slots)

    @property
    def uniform_dtype(self) -> bool:
        """True when every leaf already has the staging dtype — i.e. the
        flat buffer is a pure relayout with no widening casts."""
        return all(s.dtype == jnp.dtype(self.stage_dtype)
                   for s in self.slots)

    @property
    def segment_sizes(self) -> Tuple[int, ...]:
        """Per-leaf contiguous segment lengths (row padding included) —
        the static description codecs with per-tensor statistics (qsgd's
        max-norm scale) use to stay per-tensor on the flat buffer."""
        return tuple(s.padded_size for s in self.slots)

    def chunks(self, k: int) -> Tuple[BucketChunk, ...]:
        """Partition the buffer into (at most) ``k`` contiguous chunks.

        Deterministic static partition, balanced by padded element count
        with a greedy sweep: each chunk accumulates whole slots until it
        reaches the remaining-average target.  ``k`` is clamped to
        ``num_leaves`` (a chunk never splits a slot, so per-tensor scale
        segments stay intact) and to >= 1.  ``chunks(1)`` is the whole
        buffer — the barrier round — and the concatenation of chunk
        windows always covers ``[0, padded_elems)`` exactly, in order.
        """
        return _chunks_of(self, max(int(k), 1))

    def shard(self, axis_size: int, axis_index: int) -> BucketChunk:
        """The slot-aligned shard window worker ``axis_index`` of an
        ``axis_size``-way intra axis *owns* in the flat buffer.

        Shards partition ``[0, padded_elems)`` exactly, in order, on slot
        boundaries (per-tensor codec statistics never straddle a shard) and
        balanced by padded element count — the same greedy sweep as
        :meth:`chunks`, but with a fixed shard count: when the tree has
        fewer slots than ``axis_size``, trailing shards are *empty*
        (zero-size windows at the buffer end) rather than the count being
        clamped, so every worker of the intra axis has a well-defined
        (possibly trivial) window.  ``shard(1, 0)`` is the whole buffer —
        the single-tier reference window.
        """
        if axis_size < 1:
            raise ValueError(f"axis_size must be >= 1, got {axis_size}")
        if not 0 <= axis_index < axis_size:
            raise ValueError(
                f"axis_index {axis_index} out of range for "
                f"axis_size {axis_size}")
        return _shards_of(self, int(axis_size))[axis_index]

    # -- the two jit-safe data movers --------------------------------------
    def flatten(self, X: PyTree) -> jax.Array:
        """Stacked pytree -> one ``[n, padded_elems]`` staging buffer.

        Writes each segment into a preallocated buffer with
        ``dynamic_update_slice`` rather than ``jnp.concatenate``: XLA's CPU
        concat emitter falls off the memcpy path when its operands are
        fused reshapes (measured ~14x slower on a 61-leaf ResNet tree),
        while consecutive in-place DUS fusions stay at copy speed.
        """
        leaves = self.treedef.flatten_up_to(X)
        with obs_trace.named_phase("comm.stage"):
            buf = jnp.zeros((self.n_workers, self.padded_elems),
                            self.stage_dtype)
            for leaf, s in zip(leaves, self.slots):
                seg = _segment(leaf, s, self.n_workers)
                buf = jax.lax.dynamic_update_slice(
                    buf, seg.astype(self.stage_dtype), (0, s.offset))
        return buf

    def unflatten(self, flat: jax.Array) -> PyTree:
        """Inverse of :func:`flatten`: slice segments, drop row padding,
        restore each leaf's shape and dtype."""
        out = []
        with obs_trace.named_phase("comm.scatter"):
            for s in self.slots:
                seg = jax.lax.slice_in_dim(flat, s.offset,
                                           s.offset + s.padded_size, axis=1)
                out.append(_unsegment(seg, s, self.n_workers))
        return self.treedef.unflatten(out)

    # -- the tile-staged buffer (the Moniqua round's kernels) ---------------
    @property
    def tile_rows(self) -> int:
        """Rows ``R`` of the tile-staged buffer ``[n, R, TILE_COLS]``."""
        return -(-self.padded_elems // TILE_COLS)

    def flatten_tiles(self, X: PyTree) -> jax.Array:
        """Stacked pytree -> ``[n, tile_rows, TILE_COLS]``: the buffer of
        :meth:`flatten` with each worker's row laid out in rows of the
        codec kernels' tile width (flat position ``p`` at ``[:, p //
        TILE_COLS, p % TILE_COLS]``); the tail of the last row is zero.

        No ``[n, padded_elems]`` array is formed.  Each segment, behind
        the unfinished row the segments before it left, is written into
        the buffer as whole rows; its remainder is the next unfinished
        row.  The ``optimization_barrier`` keeps the shift of a segment to
        its column out of the fusion that lays it out in rows: fused,
        XLA's TPU compiler emits code for every (shape, shift) pair, which
        made xlstm-125m's step program 82 MB larger, and the chip holds
        the program in HBM."""
        leaves = self.treedef.flatten_up_to(X)
        n = leaves[0].shape[0]        # the workers held here (shard_map)
        lead = _lead(n)
        with obs_trace.named_phase("comm.stage"):
            buf = jnp.zeros((n, self.tile_rows, TILE_COLS), self.stage_dtype)
            row, head = 0, None           # head: the unfinished row
            for leaf, s in zip(leaves, self.slots):
                seg = _segment(leaf, s, n).astype(self.stage_dtype)
                seg = seg.reshape(lead + seg.shape[1:])
                if head is not None:
                    seg = jnp.concatenate([head, seg], axis=-1)
                whole = seg.shape[-1] // TILE_COLS * TILE_COLS
                if whole:
                    seg = jax.lax.optimization_barrier(seg)
                    buf = jax.lax.dynamic_update_slice(
                        buf, seg[..., :whole].reshape(n, -1, TILE_COLS),
                        (0, row, 0))
                    row += whole // TILE_COLS
                head = seg[..., whole:] if whole < seg.shape[-1] else None
            if head is not None:
                buf = jax.lax.dynamic_update_slice(
                    buf, head.reshape(n, 1, -1), (0, row, 0))
        return buf

    def unflatten_tiles(self, buf: jax.Array) -> PyTree:
        """Inverse of :meth:`flatten_tiles`: read each segment back from
        the rows it spans, drop row padding, restore each leaf's shape
        and dtype."""
        n = buf.shape[0]
        lead = _lead(n)
        out = []
        with obs_trace.named_phase("comm.scatter"):
            for s in self.slots:
                row, col = divmod(s.offset, TILE_COLS)
                end = -(-(s.offset + s.padded_size) // TILE_COLS)
                win = jax.lax.slice_in_dim(buf, row, end, axis=1)
                win = win.reshape(lead + (-1,))[..., col:col + s.padded_size]
                out.append(_unsegment(win.reshape(n, -1), s, n))
        return self.treedef.unflatten(out)


def _lead(n: int) -> Tuple[int, ...]:
    """The worker axis of the tile staging's flat segments.  One worker (a
    device of a worker mesh) stages 1-D segments: XLA's TPU compiler lays
    out a 1-D array in rows of 1024 but pads a ``[1, S]`` one to a tile of
    sublanes, and its relayouts into ``[1, R, 1024]`` took three times the
    code (v5e compile)."""
    return (n,) if n > 1 else ()


def _segment(leaf: jax.Array, s: LeafSlot, n: int) -> jax.Array:
    """One stacked leaf as its ``[n, padded_size]`` segment (last axis
    zero-padded to the alignment)."""
    seg = jnp.reshape(leaf, (n, s.rows, s.last))
    if s.last_padded != s.last:
        seg = jnp.pad(seg, ((0, 0), (0, 0), (0, s.last_padded - s.last)))
    return seg.reshape(n, s.padded_size)


def _unsegment(seg: jax.Array, s: LeafSlot, n: int) -> jax.Array:
    """Inverse of :func:`_segment`."""
    if s.last_padded != s.last:
        seg = seg.reshape(n, s.rows, s.last_padded)[..., :s.last]
    return seg.reshape((n,) + s.shape).astype(s.dtype)


@functools.lru_cache(maxsize=1024)
def _partition_slots(slots: Tuple[LeafSlot, ...],
                     k: int) -> Tuple[BucketChunk, ...]:
    """Greedy slot-aligned partition of a contiguous slot window into
    ``min(k, len(slots))`` balanced chunks (memoized: slots are frozen/
    hashable, so a jitted round re-tracing with the same window reuses the
    same static chunk descriptors).  Shared by whole-layout chunking
    (``BucketLayout.chunks``), shard windows (``BucketLayout.shard``), and
    shard sub-chunking (``BucketChunk.chunks``)."""
    k = min(k, len(slots))
    chunks, start = [], 0
    remaining = sum(s.padded_size for s in slots)
    for i in range(k):
        target = remaining / (k - i)
        end, acc = start, 0
        # take slots until the chunk reaches the remaining-average target;
        # every chunk takes at least one slot so all k chunks are non-empty
        while end < len(slots) and (end == start or acc < target):
            nxt = acc + slots[end].padded_size
            # stop before overshooting past the target by more than the
            # undershoot — keeps chunk sizes balanced around the target
            if end > start and nxt - target > target - acc:
                break
            acc = nxt
            end += 1
        # leave enough slots for the chunks still to come
        end = min(end, len(slots) - (k - i - 1))
        end = max(end, start + 1)
        window = slots[start:end]
        chunks.append(BucketChunk(index=i, offset=window[0].offset,
                                  size=sum(s.padded_size for s in window),
                                  slots=tuple(window)))
        remaining -= chunks[-1].size
        start = end
    return tuple(chunks)


def _chunks_of(layout: "BucketLayout", k: int) -> Tuple[BucketChunk, ...]:
    return _partition_slots(layout.slots, k)


@functools.lru_cache(maxsize=1024)
def _shards_of(layout: "BucketLayout",
               axis_size: int) -> Tuple[BucketChunk, ...]:
    """Exactly ``axis_size`` shard windows covering the buffer in order.

    The first ``min(axis_size, num_leaves)`` are the greedy balanced
    partition; any remainder (more workers than slots) are empty windows
    pinned to the buffer end so indexing stays total.
    """
    real = _partition_slots(layout.slots, axis_size)
    if len(real) == axis_size:
        return real
    end = layout.padded_elems
    empties = tuple(BucketChunk(index=i, offset=end, size=0, slots=())
                    for i in range(len(real), axis_size))
    return real + empties


def _common_stage_dtype(dtypes) -> Any:
    """One shared inexact dtype stages natively; anything mixed -> f32."""
    uniq = {jnp.dtype(d) for d in dtypes}
    if len(uniq) == 1:
        d = uniq.pop()
        if jnp.issubdtype(d, jnp.inexact):
            return d
    return jnp.dtype(jnp.float32)


@functools.lru_cache(maxsize=256)
def _build(treedef, descs: Tuple[Tuple[Tuple[int, ...], Any], ...],
           align: int) -> BucketLayout:
    if align < 1:
        raise ValueError(f"alignment must be >= 1, got {align}")
    if not descs:
        raise ValueError("cannot bucket an empty pytree")
    n = descs[0][0][0] if descs[0][0] else 0
    slots = []
    offset = 0
    for shape, dtype in descs:
        if not shape or shape[0] != n:
            raise ValueError(
                f"stacked leaves need a shared worker axis: {shape} vs n={n}")
        inner = shape[1:]
        last = inner[-1] if inner else 1
        rows = int(np.prod(inner[:-1], dtype=np.int64)) if inner else 1
        last_p = -(-last // align) * align
        slots.append(LeafSlot(shape=inner, dtype=jnp.dtype(dtype), rows=rows,
                              last=last, last_padded=last_p,
                              size=rows * last,
                              padded_size=rows * last_p, offset=offset))
        offset += rows * last_p
    return BucketLayout(treedef=treedef, slots=tuple(slots), n_workers=n,
                        align=align,
                        stage_dtype=_common_stage_dtype(d for _, d in descs))


def layout_of(X: PyTree, align: int = 1) -> BucketLayout:
    """The (memoized) flat-buffer layout for a stacked pytree.

    ``X`` may hold concrete arrays or ``ShapeDtypeStruct``s — only shapes
    and dtypes are read, so a trainer can warm the cache from its abstract
    state before jit and every traced round reuses the same layout object.
    """
    leaves, treedef = jax.tree.flatten(X)
    descs = tuple((tuple(l.shape), jnp.dtype(l.dtype)) for l in leaves)
    return _build(treedef, descs, int(align))
