"""repro.obs: round-health telemetry, phase traces, and run logs.

The observability contracts (docs/observability.md):

1. **Observational purity** — CommEngine.mix outputs (and WireState, for
   the stateful wires) are bit-exact with ``telemetry`` on or off, for
   every wire, on both gossip paths and both backend names; same at the
   algorithm level across jitted steps.
2. **Path/backend invariance** — the health values themselves are
   identical whether the engine runs bucketed or per-leaf, pallas or jnp
   (telemetry always evaluates on the canonical flat buffer with the jnp
   reference encode).
3. **Alias sentinel** — exactly zero on runs satisfying Lemma 1's
   ``|x_i - x_j|_inf < theta`` hypothesis; reliably nonzero over
   model-sized buffers once theta is undersized.
4. **Artifacts** — run logs validate against ``repro.obs.runlog/v1``,
   SpanRecorder / SimTrace exports validate as Chrome traces, and the
   ``tools/obs_report.py`` / ``tools/check_obs.py`` pipeline reads them.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.engine import CommEngine, make_wire
from repro.core import modulo
from repro.core.algorithms import AlgoHyper, get_algorithm
from repro.core.moniqua import MoniquaCodec
from repro.core.quantizers import QuantSpec
from repro.core.topology import ring
from repro.obs import metrics as M
from repro.obs import runlog as RL
from repro.obs import trace as TR

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def _stacked(scale=0.02, n=8, d=512, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * scale


def _tree(scale=0.02):
    return {"w": _stacked(scale=scale), "b": _stacked(scale=scale, d=33,
                                                      seed=1)}


def _engine(wire="moniqua", bits=8, backend="jnp", path="bucketed",
            telemetry=False, warmup=2, n=8):
    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    return CommEngine(ring(n), make_wire(wire, spec, warmup=warmup)
                      if wire in ("ef_qsgd", "onebit")
                      else make_wire(wire, spec),
                      backend=backend, path=path,
                      telemetry=telemetry)


# ---------------------------------------------------------------------------
# 1. observational purity: outputs bit-exact with telemetry on/off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", ["bucketed", "per_leaf"])
@pytest.mark.parametrize("wire,bits", [("full", 32), ("moniqua", 8),
                                       ("moniqua", 1), ("qsgd", 4)])
def test_stateless_mix_bit_exact_on_off(wire, bits, path):
    X = _tree()
    key = jax.random.PRNGKey(3)
    kw = dict(theta=2.0, key=key) if wire != "full" else {}
    off = _engine(wire, bits, path=path).mix(X, **kw).x
    r = _engine(wire, bits, path=path, telemetry=True).mix(X, **kw)
    on, health = r.x, r.health
    for k in X:
        np.testing.assert_array_equal(np.asarray(off[k]), np.asarray(on[k]))
    assert set(health) == set(M.HEALTH_ROUND_KEYS)
    assert health["alias_count"].dtype == jnp.int32


@pytest.mark.parametrize("path", ["bucketed", "per_leaf"])
@pytest.mark.parametrize("wire", ["ef_qsgd", "onebit"])
def test_stateful_mix_bit_exact_on_off(wire, path):
    """3 iterated rounds (crossing the onebit warmup switch): outputs AND
    the carried WireState are untouched by the telemetry flag."""
    Xa = Xb = _tree()
    a = _engine(wire, 4, path=path)
    b = _engine(wire, 4, path=path, telemetry=True)
    sa, sb = a.init_wire_state(Xa), b.init_wire_state(Xb)
    for k in range(3):
        key = jax.random.PRNGKey(40 + k)
        ra = a.mix(Xa, key=key, state=sa)
        rb = b.mix(Xb, key=key, state=sb)
        Xa, sa = ra.x, ra.state
        Xb, sb, health = rb.x, rb.state, rb.health
        for lk in Xa:
            np.testing.assert_array_equal(np.asarray(Xa[lk]),
                                          np.asarray(Xb[lk]),
                                          err_msg=f"round {k} {lk}")
        np.testing.assert_array_equal(np.asarray(sa["residual"]),
                                      np.asarray(sb["residual"]),
                                      err_msg=f"round {k} residual")
        # the warm flag reports the round just executed
        assert float(health["warm"]) == (1.0 if wire == "onebit" and k < 2
                                         else 0.0)
        assert float(health["ef_residual_l2"]) >= 0.0


@pytest.mark.parametrize("algo", ["dpsgd", "moniqua", "d2", "moniqua_d2"])
def test_algorithm_trajectory_unchanged_on_off(algo):
    """Jitted algorithm steps: the telemetry flag must not change the
    trajectory.  Eager engine mixes are bit-exact (tests above); under
    jit the extra telemetry consumers of the staging buffer may legally
    re-fuse the mix math (the repo's documented ~1-ulp FMA-contraction
    caveat), so this asserts a 1-ulp-tight bound instead of equality.
    The telemetry run also carries ``extra['health']`` with the
    cumulative alias counter threaded across steps."""
    n, d = 8, 256
    X = _stacked(n=n, d=d, scale=0.05)
    g = _stacked(n=n, d=d, seed=7, scale=0.1)
    a = get_algorithm(algo)

    def run(telemetry):
        hp = AlgoHyper(topo=ring(n),
                       codec=MoniquaCodec(QuantSpec(bits=8, stochastic=True)),
                       theta=2.0, telemetry=telemetry)
        extra = a.init(X, hp)
        step = jax.jit(lambda x, e, gg, k, kk: a.step(x, e, gg, 0.1, k, kk,
                                                      hp))
        x = X
        for k in range(3):
            x, extra = step(x, extra, g, jnp.asarray(k),
                            jax.random.PRNGKey(100 + k))
        return x, extra

    x_off, _ = run(False)
    x_on, extra_on = run(True)
    np.testing.assert_allclose(np.asarray(x_off), np.asarray(x_on),
                               rtol=0, atol=1e-6)
    h = extra_on["health"]
    assert set(h) == set(M.HEALTH_KEYS)
    assert int(h["alias_total"]) == 0          # safe theta: no alias events
    assert float(h["consensus_inf"]) > 0.0


# ---------------------------------------------------------------------------
# 2. path/backend invariance of the health values
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [1, 4, 8])
def test_health_invariant_across_paths_and_backends(bits):
    X = _tree()
    key = jax.random.PRNGKey(11)
    ref = None
    for backend in ("jnp", "pallas"):
        for path in ("bucketed", "per_leaf"):
            h = _engine("moniqua", bits, backend=backend,
                        path=path, telemetry=True).mix(
                            X, theta=2.0, key=key).health
            h = {k: np.asarray(v) for k, v in h.items()}
            if ref is None:
                ref = h
                continue
            for k in M.HEALTH_ROUND_KEYS:
                np.testing.assert_array_equal(
                    h[k], ref[k], err_msg=f"{k} @ {backend}/{path}")


# ---------------------------------------------------------------------------
# 3. the alias sentinel
# ---------------------------------------------------------------------------

def test_alias_zero_when_theta_bound_holds():
    """Lemma 1 hypothesis satisfied (with guard-band margin) -> exactly
    zero for every width whose sentinel is live (delta < 1/4)."""
    X = _tree(scale=0.01)   # consensus_inf << theta - delta*B
    for bits in (4, 8):
        h = _engine("moniqua", bits, telemetry=True).mix(
            X, theta=2.0, key=jax.random.PRNGKey(0)).health
        assert int(h["alias_count"]) == 0, bits
        assert float(h["headroom"]) < 0.5


def test_alias_pinned_to_zero_without_guard_band():
    """delta >= 1/4 (1-bit nearest, 2-bit stochastic): quantization error
    alone spans the whole band, so the sentinel is pinned to 0 even under
    gross violation — headroom is the live signal at these widths."""
    X = {"w": _stacked(scale=3.0, d=2048, seed=5)}
    for bits in (1, 2):
        h = _engine("moniqua", bits, telemetry=True).mix(
            X, theta=0.05, key=jax.random.PRNGKey(2)).health
        assert int(h["alias_count"]) == 0, bits
        assert float(h["headroom"]) > 0.5   # ...but headroom screams


@pytest.mark.parametrize("bits", [4, 8])
def test_alias_fires_when_theta_undersized(bits):
    """Gross theta violation over a model-sized buffer: neighbor distances
    are many multiples of B, so wrapped decodes land in the outer band at
    per-element rate ~2*delta per neighbor (1/8 @4-bit, 1/128 @8-bit
    stochastic) — thousands of hits at 4 bits, dozens at 8, never zero."""
    X = {"w": _stacked(scale=3.0, d=4096, seed=5)}   # >> theta=0.05
    h = _engine("moniqua", bits, telemetry=True).mix(
        X, theta=0.05, key=jax.random.PRNGKey(2)).health
    count = int(h["alias_count"])
    assert count > 0, f"undersized theta must trip the sentinel ({bits}b)"
    # calibration sanity: within a loose factor of the ~2*delta rate
    delta = QuantSpec(bits=bits, stochastic=True).delta
    expect = 2 * delta * 2 * 8 * 4096    # 2 neighbors x n x d
    assert count > expect / 8
    assert float(h["headroom"]) > 0.5


def test_alias_band_mask_semantics():
    """The band predicate on hand-built payload values (B=1, theta=0.4):
    fires iff ``|cmod(qb - y, B)| >= theta``, i.e. iff the payload's
    recovered difference lands in ``[theta, B - theta]`` mod B.  The
    d=0.61 case is the instructive one: a true violation whose wrap
    lands back inside (-theta, theta) — aliasing is per-element
    undetectable from the payload alone, which is exactly why the
    sentinel aggregates counts over model-sized buffers."""
    from repro.kernels import moniqua_decode_reduce as dr
    B, theta = 1.0, 0.4
    y = jnp.zeros((1, 6))
    qb = jnp.asarray([[0.00,    # in consensus: no fire
                       0.39,    # just under theta: no fire
                       0.45,    # budget exhausted: fire
                       0.55,    # cmod -> -0.45: fire
                       0.61,    # cmod -> -0.39: silent alias, no fire
                       1.00]])  # full period, cmod -> 0: no fire
    mask = np.asarray(dr.alias_band_mask(qb, y, B, theta))[0]
    np.testing.assert_array_equal(
        mask, [False, False, True, True, False, False])
    # shifting the reference shifts the band with it
    mask2 = np.asarray(dr.alias_band_mask(qb + 3.2, y + 3.2, B, theta))[0]
    np.testing.assert_array_equal(mask, mask2)


# ---------------------------------------------------------------------------
# 4. AD-PSGD edge telemetry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True])
def test_adpsgd_telemetry_pure_and_traced(quantized):
    from repro.core import adpsgd as A
    n, d = 8, 64
    x0 = _stacked(n=n, d=d, scale=0.01)
    grad = lambda x, i, k: x + 0.05 * jax.random.normal(k, x.shape)  # noqa
    kw = dict(topo=ring(n),
              codec=MoniquaCodec(QuantSpec(bits=8, stochastic=True)),
              theta=2.0, quantized=quantized)
    key = jax.random.PRNGKey(0)
    Xf0, tr0 = A.run(x0, grad, 0.05, 20, A.ADPSGDConfig(**kw), key)
    Xf1, tr1, health = A.run(x0, grad, 0.05, 20,
                             A.ADPSGDConfig(telemetry=True, **kw), key)
    np.testing.assert_array_equal(np.asarray(Xf0), np.asarray(Xf1))
    np.testing.assert_array_equal(np.asarray(tr0), np.asarray(tr1))
    assert set(health) == set(M.HEALTH_ROUND_KEYS)
    assert health["consensus_inf"].shape == (20,)
    assert int(jnp.sum(health["alias_count"])) == 0
    bpp = float(health["bits_per_param"][0])
    assert bpp == (8.0 if quantized else 32.0)


# ---------------------------------------------------------------------------
# 5. run logs + Chrome traces
# ---------------------------------------------------------------------------

def test_runlog_roundtrip_and_validation(tmp_path):
    path = str(tmp_path / "run.jsonl")
    rec = TR.SpanRecorder()
    with rec.span("phase.a", tid="t0", step=1):
        pass
    with RL.RunLogWriter(path, run={"algo": "moniqua", "bits": 8,
                                    "theta": jnp.float32(2.0)}) as w:
        w.step(0, {"loss": jnp.float32(1.5), "obs_alias_count": 0,
                   "obs_alias_total": 0})
        w.step(5, {"loss": 1.2, "obs_alias_count": 2, "obs_alias_total": 3})
        w.spans_from(rec)
        w.event("checkpoint", {"step": 5})
        w.result(steps=6, bytes_per_step=1234)
    assert RL.validate_runlog(path) == []
    records = RL.read_runlog(path)
    assert records[0]["kind"] == "header"
    assert records[0]["schema"] == RL.SCHEMA
    assert records[0]["run"]["theta"] == 2.0       # jax scalar -> JSON float
    assert len(RL.step_records(records)) == 2
    # alias_events prefers the cumulative counter over the per-step sum
    assert RL.alias_events(records) == 3


def test_runlog_validation_catches_malformed(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "step", "step": 0, "metrics": {}}) + "\n")
        f.write(json.dumps({"kind": "wat"}) + "\n")
        f.write(json.dumps({"kind": "span", "name": "x", "t0_s": -1.0,
                            "dur_s": 0.1}) + "\n")
    errors = RL.validate_runlog(path)
    assert any("header" in e for e in errors)
    assert any("unknown kind" in e for e in errors)
    assert any("t0_s" in e for e in errors)


def test_span_recorder_chrome_export_validates(tmp_path):
    rec = TR.SpanRecorder()
    with rec.span("outer", tid="train", step=0):
        with rec.span("inner", tid="train"):
            pass
    rec.instant("marker", tid="train")
    obj = rec.to_chrome(process_name="test")
    assert TR.validate_chrome(obj) == []
    names = {e["name"] for e in obj["traceEvents"]}
    assert {"outer", "inner", "marker"} <= names
    phases = {e["name"]: e["ph"] for e in obj["traceEvents"]
              if e["ph"] in ("X", "i")}
    assert phases["marker"] == "i" and phases["outer"] == "X"
    path = str(tmp_path / "t.json")
    rec.save(path)
    with open(path) as f:
        assert TR.validate_chrome(json.load(f)) == []


def _profiled_host_events(tmp_path, body):
    """Names of the host events a profiler trace of ``body()`` holds."""
    from jax.profiler import ProfileData
    out = str(tmp_path / "prof")
    with jax.profiler.trace(out):
        body()
    paths = [os.path.join(d, f) for d, _, fs in os.walk(out) for f in fs
             if f.endswith(".xplane.pb")]
    assert len(paths) == 1
    return [e.name.split("#")[0]
            for p in ProfileData.from_file(paths[0]).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events]


def test_span_recorder_spans_are_profiler_annotations(tmp_path):
    """A recorder's span is on the profiler's clock (a TraceAnnotation host
    event), and its Chrome export still validates."""
    rec = TR.SpanRecorder()

    def body():
        with rec.span("obs.test.outer", tid="train", step=3):
            with TR.span("obs.test.bare"):
                jnp.ones(4).block_until_ready()

    names = _profiled_host_events(tmp_path, body)
    assert names.count("obs.test.outer") == 1
    assert names.count("obs.test.bare") == 1
    # only the span given the recorder is recorded
    assert [e["name"] for e in rec.events] == ["obs.test.outer"]
    assert rec.events[0]["args"] == {"step": 3}
    assert TR.validate_chrome(rec.to_chrome()) == []


def test_every_named_scope_is_listed():
    """``SCOPES`` lists every scope the program opens inside jit."""
    import re
    src = os.path.join(os.path.dirname(__file__), "..", "src", "repro")
    opened = set()
    for d, _, files in os.walk(src):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    opened |= set(re.findall(
                        r'(?:named_scope|named_phase|chunk_phase)\(\s*"'
                        r'([^"]+)"', fh.read()))
    assert {"train.grad", "train.optimizer", "comm.stage",
            "comm.scatter"} <= opened
    assert opened <= set(TR.SCOPES)


def test_sim_trace_to_chrome_and_merge():
    from repro.sim import events as SE
    from repro.sim import scenarios as SC
    sc = SC.get_scenario("lan-10gbe-ring", n=4)
    trace = SE.simulate_sync_rounds(sc, 10_000, num_rounds=3)
    sim_obj = trace.to_chrome()
    assert TR.validate_chrome(sim_obj) == []
    assert any(e.get("pid") == 1 for e in sim_obj["traceEvents"])
    rec = TR.SpanRecorder()
    with rec.span("train.step", tid="train"):
        pass
    merged = TR.merge_chrome_traces([rec.to_chrome(), sim_obj])
    assert TR.validate_chrome(merged) == []
    pids = {e.get("pid") for e in merged["traceEvents"]}
    assert {0, 1} <= pids            # measured + sim side by side


def test_trainer_end_to_end_runlog_and_trace(tmp_path):
    """Trainer with telemetry + log_jsonl + trace_path: obs_* metrics in
    the history, a schema-valid run log the CI gate passes, and a valid
    Chrome trace with the loop's train.batch / train.dispatch /
    train.fetch spans — the whole satellite pipeline."""
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.models.model_factory import build_model
    from repro.train.trainer import Trainer, TrainerConfig
    import dataclasses
    cfg = get_config("llama3.2-3b").reduced()
    cfg = dataclasses.replace(cfg, num_layers=1, d_model=64, num_heads=2,
                              num_kv_heads=2, head_dim=32, d_ff=128,
                              vocab_size=64)
    model = build_model(cfg)
    shape = InputShape("tiny", seq_len=16, global_batch=8, kind="train")
    log = str(tmp_path / "run.jsonl")
    tr = str(tmp_path / "trace.json")
    tc = TrainerConfig(algo="moniqua", n_workers=4, bits=8, theta=2.0,
                       lr=0.3, steps=4, log_every=2, momentum=0.0,
                       weight_decay=0.0, telemetry=True, log_jsonl=log,
                       trace_path=tr)
    out = Trainer(model, shape, tc).run()
    h = out["history"][-1]
    assert "obs_headroom" in h and "obs_alias_total" in h
    assert h["obs_alias_total"] == 0        # theta=2 is safe on this run
    assert 0.0 < h["obs_headroom"] < 0.5
    assert h["obs_bits_per_param"] == pytest.approx(8.0, abs=0.5)
    assert RL.validate_runlog(log) == []
    records = RL.read_runlog(log)
    assert RL.alias_events(records) == 0
    steps = RL.step_records(records)
    assert steps and "obs_headroom" in steps[-1]["metrics"]
    spans = [r["name"] for r in records if r.get("kind") == "span"]
    assert spans.count("train.batch") == 4
    assert spans.count("train.dispatch") == 4
    assert spans.count("train.fetch") == 3          # log points 0, 2, 3
    assert "train.step" not in spans
    assert any(r.get("kind") == "result" for r in records)
    with open(tr) as f:
        obj = json.load(f)
    assert TR.validate_chrome(obj) == []
    assert any(e.get("name") == "train.dispatch" and e.get("ph") == "X"
               for e in obj["traceEvents"])


# ---------------------------------------------------------------------------
# 6. the tools (report + CI gate)
# ---------------------------------------------------------------------------

def _write_alias_log(path):
    with RL.RunLogWriter(str(path), run={"algo": "moniqua"}) as w:
        w.step(0, {"loss": 1.0, "obs_alias_count": 7, "obs_alias_total": 7,
                   "obs_headroom": 0.9, "theta": 0.05})
        w.result(steps=1)


def test_check_obs_gates_alias_and_telemetry(tmp_path, capsys):
    import check_obs
    bad = tmp_path / "alias.jsonl"
    _write_alias_log(bad)
    assert check_obs.main([str(bad)]) == 1
    assert "alias" in capsys.readouterr().out
    assert check_obs.main([str(bad), "--allow-alias"]) == 0
    # --require-telemetry fails a log whose steps carry no obs_* metrics
    plain = tmp_path / "plain.jsonl"
    with RL.RunLogWriter(str(plain)) as w:
        w.step(0, {"loss": 1.0})
    assert check_obs.main([str(plain)]) == 0
    assert check_obs.main([str(plain), "--require-telemetry"]) == 1


def test_obs_report_renders_and_warns(tmp_path, capsys):
    import obs_report
    log = tmp_path / "alias.jsonl"
    _write_alias_log(log)
    assert obs_report.main([str(log)]) == 0
    out = capsys.readouterr().out
    assert "ALIAS WARNING" in out and "Lemma 1" in out
    rec = TR.SpanRecorder()
    with rec.span("comm.encode", tid="t"):
        pass
    tr = tmp_path / "t.json"
    rec.save(str(tr))
    assert obs_report.main(["--trace", str(tr)]) == 0
    assert "comm.encode" in capsys.readouterr().out


def test_committed_sample_runlog_is_valid_and_alias_free():
    """RUNLOG_sample.jsonl (rendered in docs/observability.md) must stay
    schema-valid, telemetry-bearing, and alias-free — the obs-smoke CI
    job gates on exactly this."""
    path = os.path.join(os.path.dirname(__file__), "..",
                        "RUNLOG_sample.jsonl")
    assert RL.validate_runlog(path) == []
    records = RL.read_runlog(path)
    steps = RL.step_records(records)
    assert steps and any(k.startswith("obs_")
                         for k in steps[0].get("metrics", {}))
    assert RL.alias_events(records) == 0


# ---------------------------------------------------------------------------
# 7. property test: safe configurations never trip the sentinel
# ---------------------------------------------------------------------------

try:         # deterministic tests above must run even without hypothesis
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # not in the baked image
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(bits=st.sampled_from([4, 8]),
           seed=st.integers(0, 2**31 - 1),
           scale=st.floats(1e-3, 0.4))
    def test_property_safe_runs_are_alias_free(bits, seed, scale):
        """For any seed/scale with ``consensus_inf < theta - delta*B``
        (Lemma 1's hypothesis plus the guard band), the sentinel is
        exactly zero: scale < 0.4 keeps the worst pairwise distance of
        the +-1-bounded rows under 0.8, and theta=1 leaves a 0.857
        guard-band threshold even at 4 bits."""
        x = jnp.tanh(_stacked(scale=1.0, d=128, seed=seed % 1000)) * scale
        h = _engine("moniqua", bits, telemetry=True).mix(
            {"w": x}, theta=1.0, key=jax.random.PRNGKey(seed % 65536)).health
        assert float(h["consensus_inf"]) < 1.0
        assert int(h["alias_count"]) == 0
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_safe_runs_are_alias_free():
        pass
