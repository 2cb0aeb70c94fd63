"""CommEngine: codec/backend parity, fused decode-reduce, bytes accounting.

The four contracts from the engine design (docs/architecture.md):

1. ``CommEngine(full_precision).mix == gossip.mix`` exactly (the engine's
   full-precision round IS the circulant ``X W``).
2. ``CommEngine(moniqua, pallas)`` (interpret off-TPU) is **bit-exact** with
   ``CommEngine(moniqua, jnp)`` — same counter-hash randomness, same fenced
   per-element math (kernels/moniqua_decode_reduce.py documents why the jnp
   path is compared as written, i.e. eagerly; under re-jit XLA may legally
   FMA-contract and drift by 1 ulp, checked separately with a tight bound).
3. BytesLedger: 1-bit Moniqua payloads are exactly 1/32 of f32 bytes.
4. ``CommEngine(path="bucketed")`` (the default flat-buffer round,
   comm/bucket.py) is **bit-exact** with ``path="per_leaf"`` for the
   Moniqua wire — same payload bits, same mixed output — on both
   backends, and its bytes accounting (bytes_per_round == ledger == the
   bytes the simulator prices) matches the per-leaf sum.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import gossip
from repro.comm.engine import (CommEngine, FullPrecisionWire, MoniquaWire,
                               QSGDWire, make_wire)
from repro.core import modulo
from repro.core.quantizers import QuantSpec
from repro.core.topology import exponential, ring

BITS = [1, 2, 4, 8]


def _stacked(scale=0.3, n=8, d=300, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * scale


# ---------------------------------------------------------------------------
# 1. full-precision parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo", [ring(8), exponential(8)],
                         ids=lambda t: t.name)
def test_full_precision_equals_gossip_mix(topo):
    X = {"w": _stacked(), "b": _stacked(d=17, seed=1)}
    eng = CommEngine(topo, FullPrecisionWire())
    out = eng.mix(X).x
    ref = gossip.mix(X, topo)
    for k in X:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))


# ---------------------------------------------------------------------------
# 2. moniqua backend parity (pallas interpret vs pure jnp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("topo", [ring(8), exponential(8)],
                         ids=lambda t: t.name)
def test_moniqua_pallas_vs_jnp_bit_exact(bits, topo):
    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    X = _stacked()
    key = jax.random.PRNGKey(3)
    a = CommEngine(topo, MoniquaWire(spec), backend="jnp").mix(
        X, theta=2.0, key=key).x
    b = CommEngine(topo, MoniquaWire(spec), backend="pallas").mix(
        X, theta=2.0, key=key).x
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("bits", [1, 4])
def test_moniqua_parity_on_pytrees(bits):
    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    X = {"w": _stacked(), "b": _stacked(d=17, seed=7).reshape(8, 17)}
    key = jax.random.PRNGKey(1)
    a = CommEngine(ring(8), MoniquaWire(spec), backend="jnp").mix(
        X, theta=2.0, key=key).x
    b = CommEngine(ring(8), MoniquaWire(spec), backend="pallas").mix(
        X, theta=2.0, key=key).x
    for k in X:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_moniqua_parity_under_jit_close():
    """Re-jitting the jnp path lets XLA contract FMAs: bounded by ~1 ulp."""
    spec = QuantSpec(bits=4)
    X = _stacked()
    key = jax.random.PRNGKey(3)
    ej = CommEngine(ring(8), MoniquaWire(spec), backend="jnp")
    b = CommEngine(ring(8), MoniquaWire(spec), backend="pallas").mix(
        X, theta=2.0, key=key).x
    aj = jax.jit(lambda x, k: ej.mix(x, theta=2.0, key=k).x)(X, key)
    np.testing.assert_allclose(np.asarray(aj), np.asarray(b),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_moniqua_engine_close_to_exact_mix(bits):
    """One engine round deviates from full-precision mixing by O(delta*B).

    Valid only under the a-priori bound |x_i - x_j| < theta, so workers are
    bounded perturbations of a common base model (as in test_gossip).
    """
    topo = ring(8)
    theta = 1.0
    spec = QuantSpec(bits=bits, stochastic=True)
    base = jax.random.normal(jax.random.PRNGKey(0), (1, 300)) * 10.0
    X = base + jax.random.uniform(jax.random.PRNGKey(1), (8, 300),
                                  minval=-0.45, maxval=0.45) * theta
    out = CommEngine(topo, MoniquaWire(spec), backend="jnp").mix(
        X, theta=theta, key=jax.random.PRNGKey(2)).x
    exact = gossip.mix(X, topo)
    B = float(modulo.b_theta(theta, spec.delta))
    assert float(jnp.max(jnp.abs(out - exact))) <= 2.0 * spec.delta * B + 1e-4


def test_single_worker_is_identity():
    eng = CommEngine(ring(1), MoniquaWire(QuantSpec(bits=8)))
    X = jnp.ones((1, 16))
    np.testing.assert_array_equal(
        np.asarray(eng.mix(X, theta=1.0, key=jax.random.PRNGKey(0)).x),
        np.asarray(X))


# ---------------------------------------------------------------------------
# per-worker tiling (stacked wrappers in kernels/ops.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_stacked_encode_is_per_worker(backend):
    """Each worker's payload equals its solo encode: the tile layout (and
    hence the counter-hash element index) must not depend on the worker's
    position in the stack or on n."""
    from repro.core import modulo
    from repro.kernels import ops as kops
    spec = QuantSpec(bits=4)
    B = modulo.b_theta(2.0, spec.delta)
    seed = jnp.uint32(77)
    X = _stacked(n=6, d=37)
    stacked = kops.moniqua_encode_stacked(X, B, spec, seed, backend=backend)
    for i in range(6):
        solo = (kops.moniqua_encode(X[i], B, spec, None, seed=seed)
                if backend == "pallas"
                else kops.moniqua_encode_jnp(X[i], B, spec, seed))
        np.testing.assert_array_equal(np.asarray(stacked[i]),
                                      np.asarray(solo))


def test_shared_randomness_identical_rows_identical_payloads():
    """Supp. C: workers holding the same model must emit the same payload
    (same uniforms per element), which per-worker tiling guarantees."""
    from repro.core import modulo
    from repro.kernels import ops as kops
    spec = QuantSpec(bits=8, stochastic=True)
    B = modulo.b_theta(2.0, spec.delta)
    row = jax.random.normal(jax.random.PRNGKey(9), (123,)) * 0.3
    X = jnp.broadcast_to(row, (5, 123))
    packed = kops.moniqua_encode_stacked(X, B, spec, jnp.uint32(3),
                                         backend="jnp")
    for i in range(1, 5):
        np.testing.assert_array_equal(np.asarray(packed[i]),
                                      np.asarray(packed[0]))


# ---------------------------------------------------------------------------
# bucketed flat-buffer gossip (comm/bucket.py)
# ---------------------------------------------------------------------------

def _mixed_tree():
    """Mixed shapes AND dtypes: unaligned last dims, a 3-D leaf, a
    scalar-per-worker leaf, and a bf16 leaf."""
    return {
        "w": _stacked(),                                       # (8, 300) f32
        "b": _stacked(d=17, seed=7),                           # (8, 17)  f32
        "c": _stacked(d=21, seed=5,
                      ).reshape(8, 3, 7).astype(jnp.bfloat16),  # (8,3,7) bf16
        "s": _stacked(d=1, seed=3).reshape(8),                 # (8,) scalar
    }


@pytest.mark.parametrize("bits", [1, 4])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bucketed_matches_per_leaf_bit_exact(bits, backend):
    """The tentpole contract: one flat-buffer round == the per-leaf round,
    bitwise, on a mixed-shape/mixed-dtype pytree — same stochastic uniforms
    per element (global counter indices), same decode math, same casts."""
    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    X = _mixed_tree()
    key = jax.random.PRNGKey(11)
    per_leaf = CommEngine(ring(8), MoniquaWire(spec), backend=backend,
                          path="per_leaf").mix(X, theta=2.0, key=key).x
    bucketed = CommEngine(ring(8), MoniquaWire(spec), backend=backend,
                          path="bucketed").mix(X, theta=2.0, key=key).x
    for k in X:
        np.testing.assert_array_equal(np.asarray(per_leaf[k]),
                                      np.asarray(bucketed[k]))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bucketed_stochastic_payload_bits_match_per_leaf(backend):
    """Concatenated per-leaf payload bytes ARE the bucketed payload: the
    vpb row alignment lines byte boundaries up and the global idx_base
    makes both paths hash identical (seed, element) pairs."""
    from repro.comm import bucket
    from repro.core import modulo
    from repro.kernels import ops as kops
    spec = QuantSpec(bits=4, stochastic=True)
    X = {"a": _stacked(d=37), "b": _stacked(d=300, seed=2)}
    layout = bucket.layout_of(X, spec.values_per_byte)
    B = modulo.b_theta(2.0, spec.delta)
    seed = jnp.uint32(5)
    flat = layout.flatten(X)
    p_bucket = kops.moniqua_encode_stacked(flat, B, spec, seed,
                                           backend=backend)
    leaves = jax.tree.leaves(X)
    p_leaves = [kops.moniqua_encode_stacked(l, B, spec, seed,
                                            backend=backend, idx_base=off)
                .reshape(8, -1)
                for l, off in zip(leaves, layout.offsets)]
    np.testing.assert_array_equal(
        np.asarray(p_bucket), np.asarray(jnp.concatenate(p_leaves, axis=1)))


def test_bucketed_full_precision_is_exact_mix():
    X = {"w": _stacked(), "b": _stacked(d=17, seed=1)}
    out = CommEngine(ring(8), FullPrecisionWire(), path="bucketed").mix(X).x
    ref = gossip.mix(X, ring(8))
    for k in X:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(ref[k]))


def test_bucketed_full_precision_mixed_dtype_is_exact_mix():
    """Contract 1 survives bucketing on mixed-dtype trees: the full wire
    falls back to the per-leaf circulant mix there, because f32 staging
    would accumulate bf16 rolls in f32 and drift from gossip.mix."""
    X = {"w": _stacked(), "c": _stacked(d=24, seed=5).astype(jnp.bfloat16)}
    eng = CommEngine(ring(8), FullPrecisionWire(), path="bucketed")
    out = eng.mix(X).x
    ref = gossip.mix(X, ring(8))
    for k in X:
        np.testing.assert_array_equal(np.asarray(out[k], np.float32),
                                      np.asarray(ref[k], np.float32))
    # and the bytes account the per-leaf payloads (bf16 ships 2 bytes)
    per_leaf = CommEngine(ring(8), FullPrecisionWire(), path="per_leaf")
    assert eng.bytes_per_round(X) == per_leaf.bytes_per_round(X)
    assert eng.bytes_per_round(X) == (300 * 4 + 24 * 2) * 2


def test_bucketed_qsgd_close_to_exact():
    X = {"w": _stacked(scale=0.25), "b": _stacked(d=17, seed=1, scale=0.25)}
    out = CommEngine(ring(8), QSGDWire(QuantSpec(bits=8)), backend="jnp",
                     path="bucketed").mix(X, key=jax.random.PRNGKey(2)).x
    ref = gossip.mix(X, ring(8))
    mx = max(float(jnp.max(jnp.abs(X[k]))) for k in X)
    tol = 2.0 * mx * (2.0 / 256.0) + 1e-4
    for k in X:
        assert float(jnp.max(jnp.abs(out[k] - ref[k]))) <= tol


def test_bucketed_mix_under_jit():
    spec = QuantSpec(bits=4)
    eng = CommEngine(ring(8), MoniquaWire(spec), backend="jnp",
                     path="bucketed")
    X = _mixed_tree()
    key = jax.random.PRNGKey(0)
    eager = eng.mix(X, theta=2.0, key=key).x
    jitted = jax.jit(lambda x, k: eng.mix(x, theta=2.0, key=k).x)(X, key)
    for k in X:
        np.testing.assert_allclose(
            np.asarray(eager[k], np.float32),
            np.asarray(jitted[k], np.float32), rtol=0, atol=1e-6)


def test_bucketed_bytes_ledger_and_sim_agree():
    """bytes_per_round == BytesLedger == the bytes the simulator prices:
    one consistent accounting for the bucketed layout (and for Moniqua it
    equals the per-leaf sum — tile padding never rides the wire)."""
    from repro.sim import events as SE
    from repro.sim import scenarios as SC
    topo = ring(8)
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    eng = CommEngine(topo, MoniquaWire(QuantSpec(bits=2)), backend="jnp",
                     path="bucketed")
    led = gossip.BytesLedger()
    eng.mix(X, theta=2.0, key=jax.random.PRNGKey(0), ledger=led)
    m = len(topo.neighbor_offsets())
    assert led.bytes_per_worker == eng.bytes_per_round(X)
    # identical to the per-leaf accounting: (25 + 6) bytes x 2 neighbors
    assert eng.bytes_per_round(X) == (25 + 6) * 2
    per_leaf = CommEngine(topo, MoniquaWire(QuantSpec(bits=2)),
                          backend="jnp", path="per_leaf")
    assert eng.bytes_per_round(X) == per_leaf.bytes_per_round(X)
    sc = SC.get_scenario("lan-10gbe-ring", n=8)
    trace = SE.simulate_sync_rounds(sc, eng.bytes_per_round(X) // m,
                                    num_rounds=1)
    assert trace.bytes_on_wire == 8 * eng.bytes_per_round(X)


def test_bucketed_qsgd_keeps_per_tensor_scales():
    """Bucketed qsgd quantizes each tensor under its own max-norm scale
    (segment_max over the flat buffer), so a tiny-magnitude leaf next to
    a huge one is not drowned in the big leaf's quantization noise —
    and the wire bytes (4 per tensor) match the per-leaf sum."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    X = {"w": jax.random.normal(k1, (8, 100)) * 100.0,
         "b": jax.random.normal(k2, (8, 32)) * 0.01}
    eng = CommEngine(ring(8), QSGDWire(QuantSpec(bits=8)), backend="jnp",
                     path="bucketed")
    out = eng.mix(X, key=jax.random.PRNGKey(3)).x
    ref = gossip.mix(X, ring(8))
    # error on the small leaf is bounded by ITS scale, not the big one's
    err_b = float(jnp.max(jnp.abs(out["b"] - ref["b"])))
    assert err_b <= 2.0 * 0.01 * 8.0 * (2.0 / 256.0) + 1e-5
    per_leaf = CommEngine(ring(8), QSGDWire(QuantSpec(bits=8)),
                          backend="jnp", path="per_leaf")
    assert eng.bytes_per_round(X) == per_leaf.bytes_per_round(X)
    assert eng.bytes_per_round(X) == (100 + 4 + 32 + 4) * 2


def test_bucketed_layout_cache_reused_across_abstract_and_concrete():
    from repro.comm import bucket
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    abstract = jax.eval_shape(lambda: X)
    assert bucket.layout_of(X, 4) is bucket.layout_of(abstract, 4)


# ---------------------------------------------------------------------------
# seed derivation: deterministic specs with key=None are explicit
# ---------------------------------------------------------------------------

def test_deterministic_spec_key_none_is_explicit_constant():
    """key=None is only legal for nearest-rounding specs, where the hash
    seed is never drawn: the mix must equal a keyed mix bit-for-bit, and
    the placeholder seed is the documented NO_KEY_SEED constant."""
    from repro.kernels import ops as kops
    assert int(kops._key_to_seed(None)) == kops.NO_KEY_SEED
    spec = QuantSpec(bits=4, stochastic=False)
    X = _stacked()
    for path in ("per_leaf", "bucketed"):
        eng = CommEngine(ring(8), MoniquaWire(spec), backend="jnp",
                         path=path)
        a = eng.mix(X, theta=2.0, key=None).x
        b = eng.mix(X, theta=2.0, key=jax.random.PRNGKey(123)).x
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", ["per_leaf", "bucketed"])
@pytest.mark.parametrize("wire", ["moniqua", "qsgd"])
def test_stochastic_spec_key_none_raises(path, wire):
    eng = CommEngine(ring(8), make_wire(wire, QuantSpec(bits=4,
                                                        stochastic=True)),
                     backend="jnp", path=path)
    with pytest.raises(ValueError, match="PRNG key"):
        eng.mix(_stacked(), theta=2.0, key=None)


# ---------------------------------------------------------------------------
# QSGD wire
# ---------------------------------------------------------------------------

def test_qsgd_mix_close_to_exact():
    topo = ring(8)
    X = _stacked(scale=0.25)
    out = CommEngine(topo, QSGDWire(QuantSpec(bits=8)), backend="jnp").mix(
        X, key=jax.random.PRNGKey(2)).x
    exact = gossip.mix(X, topo)
    # per-worker scale <= max|x|; 8-bit lattice pitch = 2*scale/256
    tol = 2.0 * float(jnp.max(jnp.abs(X))) * (2.0 / 256.0) + 1e-4
    assert float(jnp.max(jnp.abs(out - exact))) <= tol


def test_qsgd_preserves_mean_roughly():
    topo = ring(8)
    X = _stacked(scale=0.25)
    out = CommEngine(topo, QSGDWire(QuantSpec(bits=8)), backend="jnp").mix(
        X, key=jax.random.PRNGKey(4)).x
    drift = float(jnp.max(jnp.abs(out.mean(0) - X.mean(0))))
    assert drift <= 2.0 * float(jnp.max(jnp.abs(X))) * (2.0 / 256.0) + 1e-4


# ---------------------------------------------------------------------------
# 3. bytes accounting
# ---------------------------------------------------------------------------

def test_ledger_one_bit_is_one_thirtysecond_of_f32():
    topo = ring(8)
    X = jnp.zeros((8, 256))
    led_1bit, led_f32 = gossip.BytesLedger(), gossip.BytesLedger()
    CommEngine(topo, MoniquaWire(QuantSpec(bits=1, stochastic=False)),
               backend="jnp").mix(X, theta=2.0, ledger=led_1bit)
    CommEngine(topo, FullPrecisionWire()).mix(X, ledger=led_f32)
    assert led_1bit.bytes_per_worker > 0
    assert led_1bit.bytes_per_worker * 32 == led_f32.bytes_per_worker


def test_bytes_per_round_matches_ledger():
    topo = ring(8)
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    eng = CommEngine(topo, MoniquaWire(QuantSpec(bits=2)), backend="jnp")
    led = gossip.BytesLedger()
    eng.mix(X, theta=2.0, key=jax.random.PRNGKey(0), ledger=led)
    assert led.bytes_per_worker == eng.bytes_per_round(X)
    # 2 bits -> ceil(100/4)=25 and 3*ceil(7/4)=6 bytes per leaf, 2 neighbors
    assert eng.bytes_per_round(X) == (25 + 6) * 2


def test_qsgd_bytes_include_scale():
    eng = CommEngine(ring(8), QSGDWire(QuantSpec(bits=8)), backend="jnp")
    X = jnp.zeros((8, 100))
    # 100 code bytes + 4 scale bytes, 2 neighbors
    assert eng.bytes_per_round(X) == (100 + 4) * 2


# ---------------------------------------------------------------------------
# pair_average (AD-PSGD primitive)
# ---------------------------------------------------------------------------

def test_pair_average_full_is_exact_average():
    eng = CommEngine(ring(8), FullPrecisionWire())
    xi, xj = jnp.arange(4.0), jnp.arange(4.0) + 1.0
    res = eng.pair_average(xi, xj)
    ni, nj = res.xi, res.xj
    np.testing.assert_allclose(np.asarray(ni), np.asarray(0.5 * (xi + xj)))
    np.testing.assert_allclose(np.asarray(ni), np.asarray(nj))


@pytest.mark.parametrize("wire", ["moniqua", "qsgd"])
def test_pair_average_quantized_close(wire):
    theta = 1.0
    spec = QuantSpec(bits=8)
    eng = CommEngine(ring(8), make_wire(wire, spec), backend="jnp")
    xi = jax.random.normal(jax.random.PRNGKey(5), (64,)) * 0.2
    xj = xi + jax.random.uniform(jax.random.PRNGKey(6), (64,),
                                 minval=-0.4, maxval=0.4) * theta
    res = eng.pair_average(xi, xj, theta=theta, key=jax.random.PRNGKey(7))
    ni, nj = res.xi, res.xj
    avg = 0.5 * (xi + xj)
    B = float(modulo.b_theta(theta, spec.delta))
    tol = (2.0 * spec.delta * B if wire == "moniqua"
           else 2.0 * float(jnp.max(jnp.abs(xj))) * (2.0 / 256.0)) + 1e-4
    assert float(jnp.max(jnp.abs(ni - avg))) <= tol
    assert float(jnp.max(jnp.abs(nj - avg))) <= tol


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------

def test_unknown_wire_and_backend_raise():
    with pytest.raises(ValueError):
        make_wire("zstd")
    with pytest.raises(ValueError):
        CommEngine(ring(8), MoniquaWire(), backend="cuda").mix(
            jnp.zeros((8, 8)), theta=1.0)


def test_moniqua_requires_theta():
    eng = CommEngine(ring(8), MoniquaWire())
    with pytest.raises(ValueError):
        eng.mix(jnp.zeros((8, 8)))


# ---------------------------------------------------------------------------
# stateful EF wires (ef_qsgd / onebit): the WireState contracts
# ---------------------------------------------------------------------------

EF_CASES = [("ef_qsgd", False), ("ef_qsgd", True),
            ("onebit", False), ("onebit", True)]


def _ef_engine(wire, stochastic, backend="jnp", path="bucketed", warmup=2):
    spec = QuantSpec(bits=4 if wire == "ef_qsgd" else 1,
                     stochastic=stochastic)
    return CommEngine(ring(8), make_wire(wire, spec, warmup=warmup),
                      backend=backend, path=path)


@pytest.mark.parametrize("wire,stochastic", EF_CASES)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_ef_bucketed_matches_per_leaf_bit_exact(wire, stochastic, backend):
    """The stateful tentpole contract: 4 iterated rounds bucketed vs
    per-leaf agree bitwise — mixed outputs AND the post-round WireState —
    on a mixed-shape/mixed-dtype pytree, on both backend names (warmup=2
    exercises rounds on both sides of the onebit switch).  The residual
    living in the canonical flat bucket domain is what makes this hold."""
    Xa = Xb = _mixed_tree()
    a = _ef_engine(wire, stochastic, backend, path="bucketed")
    b = _ef_engine(wire, stochastic, backend, path="per_leaf")
    sa, sb = a.init_wire_state(Xa), b.init_wire_state(Xb)
    for k in range(4):
        key = jax.random.PRNGKey(90 + k)
        ra = a.mix(Xa, key=key, state=sa)
        rb = b.mix(Xb, key=key, state=sb)
        Xa, sa = ra.x, ra.state
        Xb, sb = rb.x, rb.state
        for lk in Xa:
            np.testing.assert_array_equal(
                np.asarray(Xa[lk], np.float32),
                np.asarray(Xb[lk], np.float32), err_msg=f"round {k} {lk}")
        np.testing.assert_array_equal(np.asarray(sa["residual"]),
                                      np.asarray(sb["residual"]),
                                      err_msg=f"round {k} residual")
        assert int(sa["step"]) == int(sb["step"]) == k + 1


@pytest.mark.parametrize("wire,stochastic", EF_CASES)
def test_ef_payload_bits_match_per_leaf(wire, stochastic):
    """Concatenated per-slot payloads (what the per-leaf round rolls) ARE
    the bucketed payload — codes and sideband words both — because both
    paths encode the same canonical flat segments under the same
    row-position uniforms (idx_base = the segment's bucket offset)."""
    from repro.core.quantizers import (ef_qsgd_encode_segmented,
                                       onebit_encode_segmented)
    eng = _ef_engine(wire, stochastic)
    X = {"a": _stacked(d=37), "b": _stacked(d=300, seed=2)}
    layout = eng.layout(X)
    flat = layout.flatten(X).astype(jnp.float32)
    seed = jnp.uint32(5)
    spec = eng.codec.spec

    def enc(buf, segments, idx_base):
        if wire == "ef_qsgd":
            return ef_qsgd_encode_segmented(buf, spec, seed, segments,
                                            idx_base)
        return onebit_encode_segmented(buf, seed, segments, idx_base,
                                       stochastic)

    whole = enc(flat, layout.segment_sizes, 0)
    parts = [enc(jax.lax.slice_in_dim(flat, s.offset,
                                      s.offset + s.padded_size, axis=1),
                 (s.padded_size,), s.offset)
             for s in layout.slots]
    for j, arrs in enumerate(zip(*parts)):
        np.testing.assert_array_equal(
            np.asarray(whole[j]),
            np.asarray(jnp.concatenate(arrs, axis=1)))


@pytest.mark.parametrize("wire,nbytes", [("ef_qsgd", 70), ("onebit", 32)])
def test_ef_bytes_ledger_and_sim_agree(wire, nbytes):
    """One consistent accounting for the EF wires: BytesLedger ==
    payload_bytes_per_broadcast (x neighbors) == the bytes the simulator
    prices, identical for the bucketed and per-leaf paths (both ship the
    same packed flat segments).  Exact numbers for {a: 100, b: 3x7} f32
    (each of b's 3 rows pads its last dim to the byte boundary):
    ef_qsgd-4bit packs 100+24=124 elems at 2/byte + 4B scale x 2 leaves =
    70; onebit packs 104+24=128 elems at 8/byte + 8B levels x 2 = 32."""
    from repro.sim import events as SE
    from repro.sim import scenarios as SC
    topo = ring(8)
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    bits = 4 if wire == "ef_qsgd" else 1
    eng = CommEngine(topo, make_wire(wire, QuantSpec(bits=bits)),
                     backend="jnp", path="bucketed")
    led = gossip.BytesLedger()
    st = eng.init_wire_state(X)
    eng.mix(X, key=jax.random.PRNGKey(0), ledger=led, state=st)
    m = len(topo.neighbor_offsets())
    assert eng.payload_bytes_per_broadcast(X) == nbytes
    assert led.bytes_per_worker == eng.bytes_per_round(X) == nbytes * m
    per_leaf = CommEngine(topo, make_wire(wire, QuantSpec(bits=bits)),
                          backend="jnp", path="per_leaf")
    assert per_leaf.bytes_per_round(X) == eng.bytes_per_round(X)
    sc = SC.get_scenario("lan-10gbe-ring", n=8)
    trace = SE.simulate_sync_rounds(sc, eng.bytes_per_round(X) // m,
                                    num_rounds=1)
    assert trace.bytes_on_wire == 8 * eng.bytes_per_round(X)


def test_onebit_warmup_payload_is_f32():
    wire = make_wire("onebit", QuantSpec(bits=1))
    assert wire.warmup_payload_bytes((100,)) == 400
    assert wire.payload_bytes((100,)) == 13 + 8   # ceil(100/8) + lo/hi


@pytest.mark.parametrize("wire", ["ef_qsgd", "onebit"])
@pytest.mark.parametrize("path", ["per_leaf", "bucketed"])
def test_stateful_mix_without_state_raises(wire, path):
    eng = CommEngine(ring(8), make_wire(wire, QuantSpec(bits=4)),
                     backend="jnp", path=path)
    with pytest.raises(ValueError, match="stateful"):
        eng.mix(_stacked(), key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="stateful"):
        eng.mix(_stacked(), key=jax.random.PRNGKey(0), state={})


def test_stateful_pair_average_without_state_raises():
    eng = CommEngine(ring(8), make_wire("ef_qsgd", QuantSpec(bits=4)),
                     backend="jnp")
    xi = jnp.zeros((16,))
    with pytest.raises(ValueError, match="stateful"):
        eng.pair_average(xi, xi, key=jax.random.PRNGKey(0))


@pytest.mark.parametrize("wire,stochastic", EF_CASES)
def test_ef_mix_under_jit_close(wire, stochastic):
    """Re-jitting may legally FMA-contract the EF math: ~1 ulp, like the
    Moniqua wire's jit bound."""
    eng = _ef_engine(wire, stochastic, warmup=0)
    X = _mixed_tree()
    st = eng.init_wire_state(X)
    key = jax.random.PRNGKey(4)
    er = eng.mix(X, key=key, state=st)
    jr = jax.jit(lambda x, s, k: eng.mix(x, key=k, state=s))(X, st, key)
    eo, es = er.x, er.state
    jo, js = jr.x, jr.state
    for k in X:
        np.testing.assert_allclose(np.asarray(eo[k], np.float32),
                                   np.asarray(jo[k], np.float32),
                                   rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(es["residual"]),
                               np.asarray(js["residual"]),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("wire", ["ef_qsgd", "onebit"])
def test_ef_pair_average_stateful(wire):
    """AD-PSGD edges: per-endpoint WireState carries; the warmup exchange
    is the exact average; iterated compressed exchanges keep shrinking
    the pair gap (EF makes the biased 1-bit exchange converge too) down
    to the codec's noise floor — 8-bit qsgd's pitch keeps it well under
    a tenth of the initial gap."""
    eng = CommEngine(ring(8), make_wire(wire, QuantSpec(bits=8), warmup=1),
                     backend="jnp")
    xi = jax.random.normal(jax.random.PRNGKey(5), (3, 5)) * 0.2
    xj = xi + 0.3
    si, sj = eng.init_edge_state(xi), eng.init_edge_state(xj)
    gap0 = float(jnp.max(jnp.abs(xi - xj)))
    res = eng.pair_average(xi, xj, key=jax.random.PRNGKey(0),
                           state_i=si, state_j=sj)
    ni, nj, si, sj = res.xi, res.xj, res.state_i, res.state_j
    avg = 0.5 * (xi + xj)
    if wire == "onebit":   # warm exchange: exactly the f32 average
        np.testing.assert_array_equal(np.asarray(ni), np.asarray(avg))
        np.testing.assert_array_equal(np.asarray(nj), np.asarray(avg))
    xi, xj = ni, nj
    for k in range(40):
        r = eng.pair_average(
            xi, xj, key=jax.random.PRNGKey(10 + k), state_i=si, state_j=sj)
        xi, xj, si, sj = r.xi, r.xj, r.state_i, r.state_j
    assert int(si["step"]) == int(sj["step"]) == 41
    assert float(jnp.max(jnp.abs(xi - xj))) < 0.1 * gap0


@pytest.mark.parametrize("wire,extra", [("moniqua", 0), ("qsgd", 0),
                                        ("full", 0), ("ef_qsgd", 4 * 124 + 4),
                                        ("onebit", 4 * 128 + 4)])
def test_wire_state_bytes_accounting(wire, extra):
    """Tables 1-2 memory column: stateless wires report exactly 0; EF
    wires one f32 per padded bucket element plus the counter word."""
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    bits = 1 if wire == "onebit" else 4
    eng = CommEngine(ring(8), make_wire(wire, QuantSpec(bits=bits)),
                     backend="jnp")
    assert eng.wire_state_bytes(X) == extra
    assert eng.stateful == (extra > 0)


def test_init_wire_state_from_abstract_shapes():
    """Trainers build the WireState under jax.eval_shape — shapes only."""
    X = {"a": jnp.zeros((8, 100)), "b": jnp.zeros((8, 3, 7))}
    eng = CommEngine(ring(8), make_wire("ef_qsgd", QuantSpec(bits=4)),
                     backend="jnp")
    concrete = eng.init_wire_state(X)
    abstract = jax.eval_shape(lambda: X)
    shaped = eng.init_wire_state(abstract)
    assert shaped["residual"].shape == concrete["residual"].shape
    assert shaped["residual"].dtype == concrete["residual"].dtype
    assert shaped["step"].dtype == jnp.int32
    stateless = CommEngine(ring(8), MoniquaWire())
    assert stateless.init_wire_state(X) == {}


# ---------------------------------------------------------------------------
# 5. the tile-staged round (CommEngine.staging() == "tiles")
# ---------------------------------------------------------------------------

# 1-bit stochastic rounding has delta 1/2, which Moniqua refuses
TILE_SPECS = [(b, s) for b in BITS for s in (False, True) if b > 1 or not s]


def _tile_tree():
    """Segments that start and end off the 1024-element rows: one smaller
    than a row, one spanning whole rows between two partial ones, a bf16
    leaf and a scalar per worker."""
    return {
        "a": _stacked(d=2100, seed=1).reshape(8, 3, 700),
        "b": _stacked(d=37, seed=2),
        "c": _stacked(d=2900, seed=3),
        "d": _stacked(d=275, seed=4).reshape(8, 5, 5, 11).astype(
            jnp.bfloat16),
        "s": _stacked(d=1, seed=5).reshape(8),
    }


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("topo", [ring(8), exponential(8)],
                         ids=lambda t: t.name)
@pytest.mark.parametrize("bits,stochastic", TILE_SPECS,
                         ids=[f"{b}bit-{'stoch' if s else 'nearest'}"
                              for b, s in TILE_SPECS])
def test_tile_round_matches_flat_round_bit_exact(bits, stochastic, topo,
                                                 backend):
    """The tile-staged round ships the ``[n, D]`` round's payload bytes
    and mixes to its leaves, bit for bit; the ``[n, D]`` round is the
    ``chunks=2`` one (bit-exact against ``chunks=1`` itself,
    ``tests/test_overlap.py``)."""
    from repro.kernels import ops as kops
    spec = QuantSpec(bits=bits, stochastic=stochastic)
    X = _tile_tree()
    key = jax.random.PRNGKey(7)
    tiles = CommEngine(topo, MoniquaWire(spec), backend=backend,
                       path="bucketed")
    flat = CommEngine(topo, MoniquaWire(spec), backend=backend,
                      path="bucketed", chunks=2)
    assert (tiles.staging(X), flat.staging(X)) == ("tiles", "flat")
    layout = tiles.layout(X)
    assert layout.padded_elems % 1024 and layout.tile_rows >= 3
    # payload bytes
    B = modulo.b_theta(0.5, spec.delta)
    seed = kops._key_to_seed(key)
    p_tiles = kops.moniqua_encode_tiles(layout.flatten_tiles(X), B, spec,
                                        seed, backend=backend)
    assert p_tiles.shape == (8, layout.tile_rows, 1024 // (8 // bits))
    plan = flat.round_plan(X, theta=0.5, key=key)
    p_flat = jnp.concatenate([plan.encode_chunk(i)[0]
                              for i in range(plan.num_chunks)], axis=1)
    np.testing.assert_array_equal(
        np.asarray(p_tiles.reshape(8, -1)[:, :p_flat.shape[1]]),
        np.asarray(p_flat))
    # mixed leaves
    got = tiles.mix(X, theta=0.5, key=key).x
    want = flat.mix(X, theta=0.5, key=key).x
    for k in X:
        assert got[k].dtype == X[k].dtype and got[k].shape == X[k].shape
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))
    assert float(jnp.max(jnp.abs(got["c"] - X["c"]))) > 0


def test_tile_round_forms_no_flat_buffer():
    """The lowered tile-staged round holds no ``[n, D]`` array, so nothing
    reshapes one into tiles; the ``[n, D]`` round holds one (the check
    sees it when it is there)."""
    import re
    spec = QuantSpec(bits=1, stochastic=False)
    X = _tile_tree()
    key = jax.random.PRNGKey(7)

    def lowered(chunks):
        eng = CommEngine(ring(8), MoniquaWire(spec), backend="pallas",
                         path="bucketed", chunks=chunks)
        return eng, jax.jit(lambda x, k: eng.mix(x, theta=0.5, key=k).x
                            ).lower(X, key).as_text()

    eng, text = lowered(1)
    layout = eng.layout(X)
    flat_shapes = re.compile(
        rf"tensor<8x({layout.padded_elems}|{layout.tile_rows * 1024})x")
    assert not flat_shapes.search(text)
    assert f"tensor<8x{layout.tile_rows}x1024xf32>" in text
    assert flat_shapes.search(lowered(2)[1])


def _cell_traffic():
    import json
    import os
    root = os.path.join(os.path.dirname(__file__), "..")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    out = {}
    for c in cells:
        with open(os.path.join(root, "chipbench", "traffic",
                               c["traffic"] + ".json")) as f:
            out[c["name"]] = json.load(f)
    return out


def _cell_engine(t, **over):
    from repro.train.trainer import TrainerConfig, build_hyper
    fields = dict(algo="moniqua", topology=t["topology"],
                  n_workers=t["n_workers"], bits=t["bits"],
                  theta=t["theta"], wire=t["wire"], backend=t["backend"],
                  comm_path=t["comm_path"], chunks=t["chunks"])
    fields.update(over)
    # as the Trainer does: the tiered round stages its own collectives
    axes = (("data",) if t["placement"] == "worker_per_chip"
            and fields.get("tiers", 1) <= 1 else ())
    return build_hyper(TrainerConfig(**fields), axes).engine()


@pytest.mark.parametrize("cell", sorted(_cell_traffic()))
def test_every_benchmark_cell_stages_in_tiles(cell):
    """Each benchmark cell's traffic settings take the tile-staged round;
    ``chunks=4``, a two-tier topology and the ef_qsgd wire keep the
    ``[n, D]`` buffer."""
    t = _cell_traffic()[cell]
    X = {"w": jnp.zeros((t["n_workers"], 3000)),
         "b": jnp.zeros((t["n_workers"], 37))}
    assert _cell_engine(t).staging(X) == "tiles"
    assert _cell_engine(t).staging(X, presence=[1, 0, 1, 1]) == "flat"
    assert _cell_engine(t, chunks=4).staging(X) == "flat"
    assert _cell_engine(t, tiers=2).staging(X) == "flat"
    assert _cell_engine(t, wire="ef_qsgd", bits=4).staging(X) == "flat"
