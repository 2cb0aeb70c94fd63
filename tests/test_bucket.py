"""BucketLayout: the flat-buffer contract behind bucketed gossip.

Covers the static layout invariants (offsets, vpb row alignment, staging
dtype), the flatten/unflatten round trip, and the memoization that lets a
trainer warm the cache from abstract shapes before jit.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import bucket


def _tree(n=8):
    k = jax.random.PRNGKey(0)
    ks = jax.random.split(k, 4)
    return {
        "w": jax.random.normal(ks[0], (n, 300)),
        "b": jax.random.normal(ks[1], (n, 17)),
        "c": jax.random.normal(ks[2], (n, 3, 7)).astype(jnp.bfloat16),
        "s": jax.random.normal(ks[3], (n,)),
    }


@pytest.mark.parametrize("align", [1, 2, 4, 8])
def test_flatten_unflatten_round_trip(align):
    X = _tree()
    layout = bucket.layout_of(X, align)
    flat = layout.flatten(X)
    assert flat.shape == (8, layout.padded_elems)
    out = layout.unflatten(flat)
    for k in X:
        assert out[k].dtype == X[k].dtype
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float32), np.asarray(X[k], np.float32))


def test_offsets_are_row_padded_and_aligned():
    X = _tree()
    layout = bucket.layout_of(X, 4)
    off = 0
    for s in layout.slots:
        assert s.offset == off
        assert s.last_padded % 4 == 0
        assert s.padded_size == s.rows * s.last_padded
        off += s.padded_size
    assert layout.padded_elems == off
    assert layout.total_elems == sum(s.size for s in layout.slots)
    # unaligned last dims pick up row padding, aligned ones don't
    by_shape = {s.shape: s for s in layout.slots}
    assert by_shape[(300,)].last_padded == 300
    assert by_shape[(17,)].last_padded == 20
    assert by_shape[(3, 7)].last_padded == 8
    assert by_shape[()].last_padded == 4     # scalar-per-worker leaf


def test_padding_is_zero_and_segments_match_leaves():
    X = _tree()
    layout = bucket.layout_of(X, 8)
    flat = np.asarray(layout.flatten(X))
    for leaf, s in zip(jax.tree.leaves(X), layout.slots):
        seg = flat[:, s.offset:s.offset + s.padded_size]
        seg = seg.reshape(8, s.rows, s.last_padded)
        np.testing.assert_array_equal(
            seg[..., :s.last],
            np.asarray(leaf, np.float32).reshape(8, s.rows, s.last))
        np.testing.assert_array_equal(seg[..., s.last:], 0.0)


def test_stage_dtype_rules():
    n = 4
    uniform = {"a": jnp.zeros((n, 8), jnp.bfloat16),
               "b": jnp.zeros((n, 3), jnp.bfloat16)}
    assert bucket.layout_of(uniform, 1).stage_dtype == jnp.bfloat16
    mixed = {"a": jnp.zeros((n, 8), jnp.bfloat16),
             "b": jnp.zeros((n, 3), jnp.float32)}
    assert bucket.layout_of(mixed, 1).stage_dtype == jnp.float32


def test_layout_memoized_and_abstract_safe():
    X = _tree()
    l1 = bucket.layout_of(X, 8)
    l2 = bucket.layout_of(jax.eval_shape(lambda: X), 8)
    assert l1 is l2
    assert bucket.layout_of(X, 4) is not l1      # alignment is part of key


def test_flatten_inside_jit():
    X = _tree()
    layout = bucket.layout_of(X, 8)
    eager = layout.flatten(X)
    jitted = jax.jit(layout.flatten)(X)
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(jitted))
    back = jax.jit(layout.unflatten)(jitted)
    for k in X:
        np.testing.assert_array_equal(np.asarray(back[k], np.float32),
                                      np.asarray(X[k], np.float32))


def test_rejects_mismatched_worker_axes_and_empty_trees():
    with pytest.raises(ValueError):
        bucket.layout_of({"a": jnp.zeros((8, 3)), "b": jnp.zeros((4, 3))}, 1)
    with pytest.raises(ValueError):
        bucket.layout_of({}, 1)


# -- shard windows (two-tier owned shards) ----------------------------------

def test_shards_partition_buffer_in_order_and_slot_aligned():
    X = _tree()
    layout = bucket.layout_of(X, 4)
    whole = layout.shard(1, 0)
    assert (whole.offset, whole.size) == (0, layout.padded_elems)
    assert whole.slots == layout.slots
    for k in (2, 3, 4):
        shards = [layout.shard(k, i) for i in range(k)]
        off = 0
        for s in shards:
            assert s.offset == off
            assert s.size == sum(sl.padded_size for sl in s.slots)
            off += s.size
        assert off == layout.padded_elems
        # slot-aligned: shard slots concatenate back to the layout's
        assert tuple(sl for s in shards for sl in s.slots) == layout.slots


def test_shards_pad_with_empty_windows_beyond_leaf_count():
    X = _tree()   # 4 leaves
    layout = bucket.layout_of(X, 1)
    shards = [layout.shard(6, i) for i in range(6)]
    assert sum(s.size for s in shards) == layout.padded_elems
    for s in shards[4:]:
        assert (s.size, s.slots) == (0, ())
        assert s.offset == layout.padded_elems


def test_shard_memoized_and_validated():
    X = _tree()
    layout = bucket.layout_of(X, 1)
    assert layout.shard(2, 1) is layout.shard(2, 1)
    with pytest.raises(ValueError):
        layout.shard(0, 0)
    with pytest.raises(ValueError):
        layout.shard(2, 2)
    with pytest.raises(ValueError):
        layout.shard(2, -1)


def _tile_tree(n=4):
    """Segments off the 1024-element rows: whole rows between two partial
    ones ("w"), one inside a row ("b"), a bf16 leaf and a scalar."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    return {
        "w": jax.random.normal(ks[0], (n, 3, 1100)),
        "b": jax.random.normal(ks[1], (n, 17)),
        "c": jax.random.normal(ks[2], (n, 3, 7)).astype(jnp.bfloat16),
        "s": jax.random.normal(ks[3], (n,)),
    }


@pytest.mark.parametrize("align", [1, 8])
def test_tile_staging_is_the_flat_buffer_in_rows(align):
    """``flatten_tiles`` is ``flatten``'s buffer laid out in rows of 1024
    with a zero tail, and ``unflatten_tiles`` gives the leaves back."""
    X = _tile_tree()
    layout = bucket.layout_of(X, align)
    tiles = layout.flatten_tiles(X)
    D = layout.padded_elems
    assert tiles.shape == (4, layout.tile_rows, bucket.TILE_COLS)
    assert layout.tile_rows == -(-D // 1024) and D % 1024
    rows = np.asarray(tiles).reshape(4, -1)
    np.testing.assert_array_equal(rows[:, :D], np.asarray(layout.flatten(X)))
    np.testing.assert_array_equal(rows[:, D:], 0.0)
    out = layout.unflatten_tiles(tiles)
    for k in X:
        assert out[k].dtype == X[k].dtype and out[k].shape == X[k].shape
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float32), np.asarray(X[k], np.float32))


@pytest.mark.parametrize("sizes", [
    (100,), (2048,), (3000,), (100, 50, 5000), (100, 924, 1024),
    (1000, 24, 48, 3000), (2048, 1024, 1), (7, 7, 7, 2100)])
def test_tile_staging_carries_unfinished_rows(sizes):
    """Segments of any length starting anywhere in a row: each lands at
    its flat position, behind the unfinished row the ones before it left,
    and comes back unchanged."""
    X = {f"l{i:02d}": jax.random.normal(jax.random.PRNGKey(i), (3, d))
         for i, d in enumerate(sizes)}
    layout = bucket.layout_of(X, 1)
    rows = np.asarray(layout.flatten_tiles(X)).reshape(3, -1)
    np.testing.assert_array_equal(rows[:, :layout.padded_elems],
                                  np.asarray(layout.flatten(X)))
    np.testing.assert_array_equal(rows[:, layout.padded_elems:], 0.0)
    out = layout.unflatten_tiles(layout.flatten_tiles(X))
    for k in X:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(X[k]))
