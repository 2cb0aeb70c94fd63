"""End-to-end trainer integration: loss decreases, checkpoints round-trip."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.models.model_factory import build_model
from repro.train.trainer import Trainer, TrainerConfig

SHAPE = InputShape("tiny", seq_len=16, global_batch=8, kind="train")


def _tiny_model():
    cfg = get_config("llama3.2-3b").reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, num_layers=1, d_model=64, num_heads=2,
                              num_kv_heads=2, head_dim=32, d_ff=128,
                              vocab_size=64)
    return build_model(cfg)


def test_trainer_loss_decreases():
    model = _tiny_model()
    tc = TrainerConfig(algo="moniqua", n_workers=4, bits=8, theta=2.0,
                       lr=0.3, steps=30, log_every=5, momentum=0.0,
                       weight_decay=0.0)
    out = Trainer(model, SHAPE, tc).run()
    hist = out["history"]
    assert hist[-1]["loss"] < hist[0]["loss"]
    assert np.isfinite(hist[-1]["loss"])
    assert out["bytes_per_step"] > 0


def test_trainer_quantized_tracks_full_precision():
    model = _tiny_model()
    common = dict(n_workers=4, lr=0.3, steps=25, log_every=25,
                  momentum=0.0, weight_decay=0.0, seed=1)
    fp = Trainer(model, SHAPE, TrainerConfig(algo="dpsgd", **common)).run()
    mq = Trainer(model, SHAPE, TrainerConfig(algo="moniqua", bits=8,
                                             theta=2.0, **common)).run()
    l_fp = fp["history"][-1]["loss"]
    l_mq = mq["history"][-1]["loss"]
    assert abs(l_mq - l_fp) < 0.25 * l_fp
    # and the quantized run ships 4x fewer bytes (8 vs 32)
    assert mq["bytes_per_step"] * 4 <= fp["bytes_per_step"] * 1.01


def test_checkpoint_roundtrip(tmp_path):
    model = _tiny_model()
    tc = TrainerConfig(algo="moniqua", n_workers=2, steps=3, log_every=1,
                       checkpoint_path=str(tmp_path / "ck"),
                       checkpoint_every=2)
    out = Trainer(model, SHAPE, tc).run()
    params = out["state"]["params"]
    restored = ckpt.restore(str(tmp_path / "ck"), params)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        assert a.shape == b.shape
        assert a.dtype == b.dtype
    meta = ckpt.load_meta(str(tmp_path / "ck"))
    assert meta["algo"] == "moniqua"


def test_checkpoint_exact_values(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": [jnp.ones((4,), jnp.bfloat16), jnp.zeros((), jnp.int32)]}
    ckpt.save(str(tmp_path / "t"), tree, {"k": 1})
    back = ckpt.restore(str(tmp_path / "t"), tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_trainer_theory_theta_mode():
    """ThetaSchedule(mode='theory') end-to-end: theta tracks alpha * g_inf
    via the Theorem-2 expression and training stays finite."""
    from repro.core.theta import ThetaSchedule, theta_dpsgd
    from repro.core.topology import ring
    from repro.train import train_step as TS
    from repro.core.algorithms import AlgoHyper, get_algorithm
    from repro.core.moniqua import MoniquaCodec
    from repro.core.quantizers import QuantSpec
    from repro.optim.sgd import SGDConfig
    from repro.data.pipeline import SyntheticLMPipeline

    model = _tiny_model()
    n = 4
    topo = ring(n)
    hp = AlgoHyper(topo=topo, codec=MoniquaCodec(QuantSpec(bits=8)))
    tcfg = TS.TrainStepConfig(
        algo="moniqua", sgd=SGDConfig(momentum=0.0, weight_decay=0.0),
        lr=0.2, theta=ThetaSchedule(mode="theory", n=n, rho=topo.rho))
    algo = get_algorithm("moniqua")
    step = jax.jit(TS.make_train_step(model, hp, tcfg))
    state = TS.init_state(model, algo, hp, n, jax.random.PRNGKey(0))
    pipe = SyntheticLMPipeline(model, SHAPE, n)
    for k in range(5):
        state, metrics = step(state, pipe.worker_batch(k))
    th = float(metrics["theta"])
    expect = theta_dpsgd(0.2, float(metrics["g_inf"]), n, topo.rho)
    assert th == pytest.approx(expect, rel=1e-4)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_trainer_on_worker_mesh_matches_stacked(backend):
    """The mesh path — state and batch placed on a worker mesh, the step
    jitted with in/out shardings, the codec kernels under ``shard_map`` on
    the worker axis — takes the same steps as the plain stacked path."""
    from repro.launch.mesh import make_worker_mesh
    from repro.models.sharding import ShardingRules
    model = _tiny_model()
    tc = TrainerConfig(algo="moniqua", n_workers=4, bits=1, theta=2.0,
                       lr=0.3, steps=3, log_every=1, backend=backend,
                       comm_path="bucketed")
    ref = Trainer(model, SHAPE, tc).run()["history"]
    tr = Trainer(model, SHAPE, tc, mesh=make_worker_mesh(jax.devices()[:1]),
                 rules=ShardingRules("decentralized"))
    assert tr.hp.worker_axes == ("data",)
    got = tr.run()["history"]
    np.testing.assert_allclose([h["loss"] for h in got],
                               [h["loss"] for h in ref], rtol=1e-5)
