"""Pallas kernels vs pure-jnp oracle (ref.py), interpret=True on CPU.

Sweeps shapes x dtypes x bit-widths; encode must be BIT-EXACT against the
oracle (same counter-based hash RNG), decode allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import packing
from repro.core.quantizers import QuantSpec
from repro.kernels import moniqua_decode as DEC
from repro.kernels import moniqua_encode as ENC
from repro.kernels import ops
from repro.kernels import ref as R

BITS = [1, 2, 4, 8]


def _tile(shape=(256, 1024), dtype=jnp.float32, seed=0, scale=3.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, dtype=jnp.float32)
    return (x * scale).astype(dtype)


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("stochastic", [False, True])
def test_encode_bit_exact(bits, stochastic):
    x = _tile()
    B = jnp.float32(4.0)
    p_k = ENC.encode(x, B, jnp.uint32(7), bits=bits, stochastic=stochastic,
                     interpret=True)
    p_r = R.encode_ref(x, 4.0, bits, stochastic, 7)
    assert p_k.dtype == jnp.uint8
    assert p_k.shape == (x.shape[0], x.shape[1] * bits // 8)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("bits", BITS)
def test_encode_over_workers_with_a_ragged_row_block(bits):
    """The workers on the grid and a last row block that overhangs the
    array: each worker's packed rows are the oracle's encode of its own
    rows, every worker hashing the same element indices (Supp. C)."""
    x = _tile(shape=(3, 300, 1024), seed=8)
    p_k = ENC.encode(x, jnp.float32(4.0), jnp.uint32(5), bits=bits,
                     stochastic=bits > 1, interpret=True)
    assert p_k.shape == (3, 300, 1024 * bits // 8)
    for w in range(3):
        np.testing.assert_array_equal(
            np.asarray(p_k[w]),
            np.asarray(R.encode_ref(x[w], 4.0, bits, bits > 1, 5)))


@pytest.mark.parametrize("bits", [1, 8])
def test_decode_reduce_over_workers_with_a_ragged_row_block(bits):
    """The fused decode-reduce over ``[n, R, 1024]`` with one operand per
    neighbour equals the shared per-element math, bit for bit."""
    from repro.kernels import moniqua_decode_reduce as DR
    y = _tile(shape=(3, 300, 1024), seed=9, scale=1.0)
    B = jnp.float32(4.0)
    ps = [ENC.encode(y + 0.1 * s, B, jnp.uint32(2), bits=bits,
                     stochastic=False, interpret=True) for s in range(3)]
    w = (0.25, 0.5)
    got = DR.decode_reduce(ps[0], ps[1:], y, B, bits=bits, weights=w,
                           interpret=True)
    want = DR.decode_reduce_values(
        DR.unpack_values(ps[0], bits, B),
        [DR.unpack_values(p, bits, B) for p in ps[1:]], y, B, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("mode", ["remote", "self"])
def test_decode_allclose(bits, mode):
    x = _tile(seed=1)
    B = 4.0
    p = R.encode_ref(x, B, bits, True, 3)
    y = x + 0.3 * _tile(seed=2, scale=1.0)
    d_k = DEC.decode(p, y, jnp.float32(B), bits=bits, mode=mode,
                     interpret=True)
    d_r = (R.decode_ref(p, y, B, bits) if mode == "remote"
           else R.decode_self_ref(p, y, B, bits))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r),
                               rtol=0, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtype_sweep(dtype):
    x = _tile(dtype=dtype, seed=4)
    p_k = ENC.encode(x, jnp.float32(4.0), jnp.uint32(0), bits=4,
                     stochastic=True, interpret=True)
    p_r = R.encode_ref(x, 4.0, 4, True, 0)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    d_k = DEC.decode(p_k, x, jnp.float32(4.0), bits=4, mode="remote",
                     interpret=True)
    assert d_k.dtype == dtype
    d_r = R.decode_ref(p_r, x, 4.0, 4)
    np.testing.assert_allclose(np.asarray(d_k, dtype=np.float32),
                               np.asarray(d_r), rtol=0, atol=0.05)


def test_multi_block_grid():
    """More than one grid block: global flat index must stay consistent."""
    x = _tile((512, 2048), seed=5)
    p_k = ENC.encode(x, jnp.float32(4.0), jnp.uint32(11), bits=8,
                     stochastic=True, interpret=True)
    p_r = R.encode_ref(x, 4.0, 8, True, 11)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("shape", [(7,), (3, 50), (2, 5, 33), (1000,)])
@pytest.mark.parametrize("bits", [2, 8])
def test_ops_wrapper_arbitrary_shapes(shape, bits):
    """ops.moniqua_encode/decode handle non-tile shapes via pad/unpad and the
    end-to-end roundtrip respects the Lemma 2 error bound."""
    theta = 2.0
    spec = QuantSpec(bits=bits, stochastic=True)
    B = 2.0 * theta / (1.0 - 2.0 * spec.delta)
    key = jax.random.PRNGKey(9)
    y = jax.random.normal(key, shape, dtype=jnp.float32) * 5.0
    x = y + jax.random.uniform(jax.random.PRNGKey(10), shape,
                               minval=-0.9, maxval=0.9) * theta
    p = ops.moniqua_encode(x, jnp.float32(B), spec, key, interpret=True)
    vpb = 8 // bits
    assert p.shape[-1] == -(-shape[-1] // vpb)
    out = ops.moniqua_decode_remote(p, y, jnp.float32(B), spec,
                                    interpret=True)
    assert out.shape == x.shape
    err = float(jnp.max(jnp.abs(out - x)))
    assert err <= spec.delta * B * (1 + 1e-3)


def test_ops_self_mode_matches_core():
    """decode_self wrapper agrees with the core jnp path numerically."""
    from repro.core import modulo
    spec = QuantSpec(bits=8, stochastic=False)
    theta = 2.0
    B = float(modulo.b_theta(theta, spec.delta))
    x = jax.random.normal(jax.random.PRNGKey(2), (128,), jnp.float32)
    p = ops.moniqua_encode(x, jnp.float32(B), spec, None, interpret=True)
    out = ops.moniqua_decode_self(p, x, jnp.float32(B), spec, interpret=True)
    # reconstruct with ref to compare
    ref = R.decode_self_ref(p, x, B, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def _np_pack(codes: np.ndarray, bits: int) -> np.ndarray:
    """The wire layout stated independently of ``core/packing.py``: code
    ``b*vpb + j`` of the last axis sits in byte ``b``, bits
    ``[j*bits, (j+1)*bits)``."""
    vpb = 8 // bits
    c = codes.astype(np.uint32).reshape(*codes.shape[:-1], -1, vpb)
    return sum(c[..., j] << (j * bits) for j in range(vpb)).astype(np.uint8)


@pytest.mark.parametrize("bits", BITS)
def test_packed_layout_round_trip(bits):
    """Pins the packed layout at every width: the kernel's packed tile is
    numpy's interleave of the reference codes, and both unpack paths (the
    jnp ``unpack_codes`` and the kernels' in-tile chunk unpack) give the
    codes back — every code value, in every bit slot."""
    from repro.core.quantizers import pack_codes, unpack_codes
    from repro.kernels import moniqua_decode_reduce as DR
    x = _tile(seed=6)
    idx = jnp.arange(x.size, dtype=jnp.uint32).reshape(x.shape)
    codes = np.asarray(R.codes_ref(x, 4.0, bits, True, jnp.uint32(13), idx))
    p_k = ENC.encode(x, jnp.float32(4.0), jnp.uint32(13), bits=bits,
                     stochastic=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(p_k), _np_pack(codes, bits))
    # all 2**bits code values in every slot, on an unaligned length
    full = np.arange(2 ** bits * 8 // bits * 3 + 5) % 2 ** bits
    full = np.resize(full, (2, full.size))
    np.testing.assert_array_equal(
        np.asarray(pack_codes(jnp.asarray(full, jnp.uint8), bits)),
        _np_pack(np.pad(full, [(0, 0), (0, -full.shape[1] % (8 // bits))]),
                 bits))
    back = unpack_codes(jnp.asarray(_np_pack(codes, bits)), bits,
                        codes.shape[-1])
    np.testing.assert_array_equal(np.asarray(back), codes)
    B = jnp.float32(4.0)
    umat = None if bits == 8 else packing.unpack_matrix(bits)
    chunk = DR.tile_values(p_k[:, :packing.LANES], bits, B, umat)
    np.testing.assert_array_equal(
        np.asarray(chunk),
        np.asarray(DR.dequant(jnp.asarray(
            codes[:, :chunk.shape[1]], jnp.float32), bits, B)))


def test_kernel_rejects_untied_shapes():
    with pytest.raises(ValueError):
        ENC.encode(jnp.zeros((100, 100)), jnp.float32(1.0), jnp.uint32(0),
                   bits=8, interpret=True)
