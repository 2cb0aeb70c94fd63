"""Ahead-of-time compiles for a described TPU v5e — no chip needed.

Interpret mode (every other kernel test) cannot reject what Mosaic
refuses: an unsupported cast, a lane-splitting reshape, a VMEM overrun.
These tests lower the main path's kernels for ``v5e:2x2`` with the TPU
compiler that ships with jax, at real tile shapes, and check the sharded
gossip round's collectives.  The topology is described inside a fixture
only: only one process may hold the TPU library, so describing it while
a module is imported would break the multi-worker test run.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.analysis.roofline import collective_ops
from repro.comm.engine import CommEngine, MoniquaWire
from repro.core.quantizers import QuantSpec
from repro.core.topology import exponential, ring
from repro.kernels import moniqua_decode_reduce as DR
from repro.kernels import moniqua_encode as ENC
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention
from repro.launch.mesh import make_worker_mesh

ROWS, COLS = 512, 2048          # a 2x2 grid of (256, 1024) tiles


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache; keep it out of the cache entirely
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stochastic", [False, True])
@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_encode_compiles_for_v5e(one_chip, bits, stochastic):
    f = jax.jit(lambda x, B, s: ENC.encode(x, B, s, bits=bits,
                                           stochastic=stochastic))
    c = f.lower(_sds((ROWS, COLS), jnp.float32, one_chip),
                _sds((), jnp.float32, one_chip),
                _sds((), jnp.uint32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("graph", [ring(8), exponential(8)],
                         ids=lambda t: t.name)
@pytest.mark.parametrize("bits", [1, 8])
def test_decode_reduce_compiles_for_v5e(one_chip, bits, graph):
    """The ring's 2 neighbors and exponential(8)'s 5: the larger neighbor
    count is the kernel's VMEM high-water mark (m payload blocks,
    double-buffered)."""
    m = len(graph.neighbor_offsets())
    vpb = 8 // bits
    w = tuple([1.0 / (m + 1)] * m)
    f = jax.jit(lambda ps, pn, y, B: DR.decode_reduce(
        ps, pn, y, B, bits=bits, weights=w))
    p = _sds((ROWS, COLS // vpb), jnp.uint8, one_chip)
    c = f.lower(p, (p,) * m, _sds((ROWS, COLS), jnp.float32, one_chip),
                _sds((), jnp.float32, one_chip)).compile()
    assert _has_kernel(c)


TILES = (4, 300, 1024)          # workers, rows (a ragged last block), cols


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
def test_tile_encode_compiles_for_v5e(one_chip, bits):
    """The tile-staged round's encode: the workers on the grid, a ragged
    last row block, a bf16 staging buffer."""
    f = jax.jit(lambda x, B, s: ENC.encode(x, B, s, bits=bits,
                                           stochastic=bits > 1))
    c = f.lower(_sds(TILES, jnp.bfloat16, one_chip),
                _sds((), jnp.float32, one_chip),
                _sds((), jnp.uint32, one_chip)).compile()
    assert _has_kernel(c)


@pytest.mark.parametrize("graph", [ring(8), exponential(8)],
                         ids=lambda t: t.name)
@pytest.mark.parametrize("bits", [1, 8])
def test_tile_decode_reduce_compiles_for_v5e(one_chip, bits, graph):
    """The tile-staged round's decode-reduce: one payload operand per
    neighbour, the workers on the grid, a ragged last row block."""
    m = len(graph.neighbor_offsets())
    w = tuple([1.0 / (m + 1)] * m)
    f = jax.jit(lambda ps, pn, y, B: DR.decode_reduce(
        ps, pn, y, B, bits=bits, weights=w))
    p = _sds(TILES[:2] + (TILES[2] * bits // 8,), jnp.uint8, one_chip)
    c = f.lower(p, (p,) * m, _sds(TILES, jnp.bfloat16, one_chip),
                _sds((), jnp.float32, one_chip)).compile()
    assert _has_kernel(c)


def test_flash_attention_compiles_for_v5e(one_chip):
    bh, s, d = 8, 1024, 128
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, scale=d ** -0.5))
    q = _sds((bh, s, d), jnp.bfloat16, one_chip)
    assert _has_kernel(f.lower(q, q, q).compile())


@pytest.mark.parametrize("bits", [1, 8])
def test_sharded_mix_permutes_packed_payload(topo, monkeypatch, bits):
    """One Pallas gossip round with one worker per chip of a 2x2 mesh:
    the packed uint8 payload crosses chips as collective-permutes, and no
    all-gather of the f32 flat buffer is left (what XLA puts in when it
    has to partition the kernels' custom calls itself)."""
    # the kernels pick interpret mode off-TPU; this compile targets a TPU
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = make_worker_mesh(topo.devices)
    n, d = 4, 1 << 20
    eng = CommEngine(ring(n), MoniquaWire(QuantSpec(bits=bits,
                                                    stochastic=bits > 1)),
                     backend="pallas", path="bucketed",
                     worker_axes=("data",))
    x = _sds((n, d), jnp.float32, NamedSharding(mesh, P("data")))
    key = _sds((2,), jnp.uint32, NamedSharding(mesh, P()))
    f = jax.jit(lambda x, k: eng.mix(x, theta=2.0, key=k).x)
    with jax.set_mesh(mesh):
        text = f.lower(x, key).compile().as_text()
    ops_ = collective_ops(text)
    permutes = [s for op, s in ops_ if op == "collective-permute"]
    assert permutes and all("u8[" in s for s in permutes), ops_
    assert not [s for op, s in ops_
                if op == "all-gather" and "f32[" in s], ops_
    assert "tpu_custom_call" in text
