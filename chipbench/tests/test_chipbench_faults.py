"""A whole run of a small cell decides ``correct`` from the comparison: it
holds for the program as it is, and comes out false with the timed path
broken underneath (the faults a training cell can have) and for the
control, the reference in float8.  The harness's look for a chip is
skipped; everything else is a run as on the chip."""
import jax
import pytest

from chipbench import algorithm_ref as AR
from chipbench import cells, check, run
from chipbench.tests import tiny


def _run(tmp_path, **traffic):
    root = tiny.make_root(str(tmp_path), **traffic)
    cell = cells.load(root, "tiny-cell")
    return run.run(cell, 2 ** 31 + 11, 0.5, False, jax.devices()[:1])


def test_sound_run_is_correct(tmp_path):
    out = _run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"step_ms", "step_ms_p90", "peak_hbm_gb",
                                   "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.train import train_step as TS
    real = TS.make_train_step

    def make(*a, **kw):
        step = real(*a, **kw)

        def broken(state, batch):
            return state, step(state, batch)[1]
        return broken
    monkeypatch.setattr(TS, "make_train_step", make)


def _half_batch(monkeypatch):
    from repro.models.model_factory import Model
    real = Model.loss
    monkeypatch.setattr(Model, "loss", lambda self, p, b: real(
        self, p, {k: v[: v.shape[0] // 2] for k, v in b.items()}))


def _no_exchange(monkeypatch):
    from repro.comm.engine import CommEngine, MixResult
    monkeypatch.setattr(CommEngine, "mix",
                        lambda self, X, *a, **kw: MixResult(X, {}, None))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _no_exchange])
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    fault(monkeypatch)
    out = _run(tmp_path)
    assert not out["correct"], out["checks"]


def test_control_in_float8_fails_a_limit(tmp_path):
    root = tiny.make_root(str(tmp_path))
    cell = cells.load(root, "tiny-cell")
    ref = AR.reference_numbers(cell, 5, 3)
    ctl = AR.reference_numbers(cell, 5, 3, "fp8")
    verdict = check.judge(check.numbers(ctl, ref), cell.limits)
    assert not all(v["ok"] for v in verdict.values()), verdict
