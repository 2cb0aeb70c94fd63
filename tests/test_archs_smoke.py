"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates its REDUCED variant (2 layers,
d_model <= 256, <= 4 experts) and runs:
  * one decentralized Moniqua train step (vmap-grad + quantized gossip),
    asserting finite loss/params and correct shapes;
  * one cached decode step (serve path), asserting logits shape + finiteness.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import assigned_archs, get_config
from repro.configs.base import InputShape
from repro.core.algorithms import AlgoHyper, get_algorithm
from repro.core.moniqua import MoniquaCodec
from repro.core.quantizers import QuantSpec
from repro.core.theta import ThetaSchedule
from repro.core.topology import ring
from repro.models.model_factory import build_model
from repro.optim.sgd import SGDConfig
from repro.train import serve_step as SS
from repro.train import train_step as TS

SMOKE_TRAIN = InputShape("smoke_train", seq_len=32, global_batch=4,
                         kind="train")
SMOKE_DECODE = InputShape("smoke_decode", seq_len=64, global_batch=2,
                          kind="decode")
N_WORKERS = 2

ARCHS = assigned_archs()


def _batch(model, shape, key):
    spec = model.batch_spec(shape)
    out = {}
    for name, (shp, dt) in spec.items():
        key, k = jax.random.split(key)
        if jnp.issubdtype(dt, jnp.integer):
            out[name] = jax.random.randint(k, shp, 0, model.cfg.vocab_size,
                                           dtype=jnp.int32)
        else:
            out[name] = jax.random.normal(k, shp, jnp.float32).astype(dt)
    return out


def _stack(batch, n):
    return {k: v.reshape(n, v.shape[0] // n, *v.shape[1:])
            for k, v in batch.items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    algo = get_algorithm("moniqua")
    hp = AlgoHyper(topo=ring(N_WORKERS), codec=MoniquaCodec(QuantSpec(bits=8)),
                   theta=2.0)
    tcfg = TS.TrainStepConfig(algo="moniqua", sgd=SGDConfig(), lr=0.05,
                              theta=ThetaSchedule(mode="constant", value=2.0))
    step = TS.make_train_step(model, hp, tcfg)
    state = TS.init_state(model, algo, hp, N_WORKERS, jax.random.PRNGKey(0))
    batch = _stack(_batch(model, SMOKE_TRAIN, jax.random.PRNGKey(1)),
                   N_WORKERS)
    new_state, metrics = jax.jit(step)(state, batch)

    loss = float(metrics["loss"])
    assert np.isfinite(loss), arch
    assert loss > 0.0
    for leaf in jax.tree.leaves(new_state["params"]):
        assert leaf.shape[0] == N_WORKERS
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all()), arch
    assert int(new_state["step"]) == 1


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cache = model.init_cache(SMOKE_DECODE.global_batch, SMOKE_DECODE)
    tok = jnp.ones((SMOKE_DECODE.global_batch, 1), jnp.int32)
    sstep = jax.jit(SS.make_serve_step(model))
    logits, cache2 = sstep(params, cache, tok)
    logits, cache3 = sstep(params, cache2, tok)   # second token re-uses cache
    assert logits.shape[0] == SMOKE_DECODE.global_batch
    assert logits.shape[-1] >= cfg.vocab_size
    assert bool(jnp.isfinite(logits).all()), arch
    # cache positions advance
    pos = cache3["pos"] if "pos" in cache3 else None
    if pos is not None:
        assert int(pos) == 2


@pytest.mark.parametrize("arch", ["llama3.2-3b", "dbrx-132b", "zamba2-1.2b",
                                  "whisper-base", "phi-3-vision-4.2b"])
def test_prefill_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = InputShape("smoke_prefill", seq_len=32, global_batch=2,
                       kind="prefill")
    batch = _batch(model, shape, jax.random.PRNGKey(5))
    logits = jax.jit(SS.make_prefill_step(model))(params, batch)
    assert bool(jnp.isfinite(logits).all()), arch


def test_param_counts_match_published_scale():
    """Analytic parameter counts land near the published sizes (names)."""
    expected = {
        "dbrx-132b": 132e9, "grok-1-314b": 314e9, "chatglm3-6b": 6e9,
        "llama3.2-3b": 3e9, "xlstm-125m": 125e6, "internlm2-20b": 20e9,
        "qwen2-72b": 72e9, "zamba2-1.2b": 1.2e9,
    }
    for arch, target in expected.items():
        n = get_config(arch).param_count()
        assert 0.5 * target <= n <= 1.7 * target, (arch, n, target)


def test_reduced_configs_are_small():
    for arch in ARCHS:
        r = get_config(arch).reduced()
        assert r.num_layers <= 2
        assert r.d_model <= 512
        if r.moe:
            assert r.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ["llama3.2-3b", "zamba2-1.2b"])
def test_prefill_last_only_serving_semantics(arch):
    """serve_step prefill returns [B, 1, V] (last position only) and matches
    the full forward's final row."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    shape = InputShape("p", seq_len=32, global_batch=2, kind="prefill")
    batch = _batch(model, shape, jax.random.PRNGKey(3))
    last = jax.jit(SS.make_prefill_step(model))(params, batch)
    assert last.shape[1] == 1
    full = model.prefill_logits(params, batch, last_only=False)
    np.testing.assert_allclose(np.asarray(last[:, 0]),
                               np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


def test_mlstm_gradient_finite_at_published_chunk():
    """xlstm-125m's chunk of 128: above the diagonal the intra-chunk decay
    exponent exceeds f32's exp range, and masking after the exp turned the
    overflow into NaN gradients on the first step."""
    from repro.models.xlstm import _mlstm_scan_chunks
    B, S, H, D = 1, 128, 2, 8
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in (k1, k2, k3))
    log_f = jnp.full((B, S, H), -1.0)            # sigmoid gate of about -0.5
    log_i = jnp.full((B, S, H), -0.5)

    def loss(q, log_f):
        return jnp.sum(_mlstm_scan_chunks(q, k, v, log_f, log_i, 128))

    gq, gf = jax.grad(loss, argnums=(0, 1))(q, log_f)
    assert np.isfinite(np.asarray(gq)).all()
    assert np.isfinite(np.asarray(gf)).all()
