"""The trace reduction on a small profiler trace recorded on the CPU."""
import importlib
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import trace as T

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    from chipbench.cells import import_file
    return import_file(os.path.join(METRICS, name + ".py"), "test_" + name)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three steps of a small jitted program whose phases carry the round's
    scope names, traced with the benchmark's host spans."""
    @jax.jit
    def step(x):
        with jax.named_scope("comm.encode"):
            y = jnp.sin(x) * 3.0
        with jax.named_scope("comm.decode_reduce"):
            z = y @ y.T
        return jnp.tanh(z) @ x

    x = jnp.ones((192, 192))
    step(x).block_until_ready()
    hlo = step.lower(x).compile().as_text()
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.batch"):
                b = x + 1.0
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                r = step(b)
            with jax.profiler.TraceAnnotation("bench.fetch"):
                float(r[0, 0])
    return T.load(T.find_xplane(out), hlo)


def test_ops_and_spans_are_read(recorded):
    assert recorded.ops, "no device op read from the trace"
    names = [s.name for s in recorded.spans]
    assert names.count("bench.batch") == 3
    assert names.count("bench.dispatch") == 3
    assert names.count("bench.fetch") == 3
    assert all(o.dur_ns > 0 for o in recorded.ops)


def test_scopes_mapped_through_hlo_metadata(recorded):
    assert recorded.scope_method == "HLO op_name"
    enc = [o for o in recorded.ops if T.has_scope(o, "comm.encode")]
    dec = [o for o in recorded.ops if T.has_scope(o, "comm.decode_reduce")]
    assert enc and dec
    assert not any(T.has_scope(o, "comm.encode") for o in dec)


def test_union_and_gaps_by_hand():
    iv = [(0, 10), (5, 15), (20, 30), (40, 45)]
    assert T.union_ns(iv, 0, 50) == 15 + 10 + 5
    assert T.union_ns(iv, 8, 42) == 7 + 10 + 2
    assert T.gaps_ns(iv, 0, 50) == [(15, 20), (30, 40), (45, 50)]
    assert T.gaps_ns([], 3, 7) == [(3, 7)]


def _window(tr, **counts):
    lo = min(s.start_ns for s in tr.spans)
    hi = max(s.end_ns for s in tr.spans)
    base = dict(flops_per_step=1e6, wire_bytes=1234, elems=192 * 192,
                itemsize=4, bits=1, neighbors=2, workers_per_chip=1,
                wire="moniqua")
    return T.Window(tr, lo, hi, 3, 1, {"bf16_flops_per_s": 1e12,
                                       "hbm_bytes_per_s": 1e11},
                    dict(base, **counts))


def test_readers_on_the_recorded_window(recorded):
    win = _window(recorded)
    busy = win.op_seconds()
    assert 0 < busy[0] <= win.seconds
    idle = _reader("idle_share").read(win)
    assert 0 <= idle < 100
    assert abs(idle - (1 - busy[0] / win.seconds) * 100) < 1e-9
    assert _reader("comm_ms").read(win) > 0
    assert _reader("dispatch_ms").read(win) > 0
    assert _reader("wire_bytes").read(win) == 1234.0
    mfu = _reader("mfu").read(win)
    assert mfu == pytest.approx(1e6 * 3 / (win.seconds * 1e12) * 100)
    enc = win.op_seconds(lambda o: T.has_scope(o, "comm.encode"))[0]
    share = _reader("encode_roofline").read(win)
    want = (192 * 192 * 4 + 192 * 192 / 8) * 3 / 1e11 / enc * 100
    assert share == pytest.approx(want)
    assert _reader("decode_reduce_roofline").read(win) > 0


def test_readers_with_nothing_to_read(recorded):
    win = _window(recorded, wire="full")
    assert _reader("encode_roofline").read(win) is None
    assert _reader("decode_reduce_roofline").read(win) is None
    assert _reader("permute_ms").read(win) is None


def test_self_time_takes_nested_ops_out():
    ops = [T.Op(0, "while.1", "", 0, 100), T.Op(0, "fusion.1", "", 10, 30),
           T.Op(0, "fusion.2", "", 50, 40), T.Op(0, "copy.1", "", 60, 10),
           T.Op(0, "fusion.3", "", 120, 5)]
    assert T.self_ns(ops) == [30, 30, 30, 10, 5]


def test_permute_reads_async_ops_in_flight(recorded):
    win = _window(recorded)
    lo = win.lo_ns
    win.trace.async_ops[:] = [
        T.Op(0, "collective-permute-start.1", "", lo + 10, 2e6),
        T.Op(0, "copy-start.3", "", lo + 10, 5e6)]
    try:
        assert _reader("permute_ms").read(win) == pytest.approx(2e6 / 1e9
                                                                / 3 * 1e3)
    finally:
        win.trace.async_ops[:] = []
