"""One worker per device: the cross-device gossip exchange on a real mesh.

Four forced host devices (a subprocess, so ``XLA_FLAGS`` is read before
jax starts).  Every other test runs the worker-mesh path on a one-device
mesh, where the neighbour roll never leaves the device; here each worker
sits on its own device, so a round that mixed up neighbours, sent a
worker its own payload or dropped the exchange would not match the
stacked round on one device.
"""
import json
import math
import os
import re
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.roofline import collective_ops
from repro.comm.engine import CommEngine, MoniquaWire
from repro.configs import get_config
from repro.configs.base import InputShape
from repro.core import modulo
from repro.core.quantizers import QuantSpec
from repro.core.topology import exponential, ring
from repro.kernels import ops
from repro.launch.mesh import make_worker_mesh
from repro.models.model_factory import build_model
from repro.models.sharding import ShardingRules
from repro.train.trainer import Trainer, TrainerConfig

mesh = make_worker_mesh()
on_mesh = NamedSharding(mesh, P("data"))
N, D, THETA = 4, 5000, 0.5
k1, k2, key = jax.random.split(jax.random.PRNGKey(0), 3)
# workers within theta of each other, as Lemma 1 assumes
X = (jax.random.normal(k1, (1, D), jnp.float32)
     + jax.random.uniform(k2, (N, D), jnp.float32, -0.45, 0.45) * THETA)
res = {"devices": mesh.devices.size, "rounds": {}, "trainer": {}}
for topo in (ring(N), exponential(N)):
    for bits in (1, 8):
        spec = QuantSpec(bits=bits, stochastic=bits > 1)
        B = float(modulo.b_theta(THETA, spec.delta))
        seed = ops._key_to_seed(key)
        for backend in ("jnp", "pallas"):
            def run(axes):
                eng = CommEngine(topo, MoniquaWire(spec), backend=backend,
                                 path="bucketed", worker_axes=axes)
                mix = jax.jit(lambda x, k: eng.mix(x, theta=THETA, key=k).x)
                enc = jax.jit(lambda x, s: ops.moniqua_encode_stacked(
                    x, B, spec, s, backend=backend, worker_axes=axes))
                layout = eng.layout(X)
                enc_tiles = jax.jit(lambda x, s: ops.moniqua_encode_tiles(
                    ops.on_workers(layout.flatten_tiles, axes, 1, x), B,
                    spec, s, backend=backend, worker_axes=axes))
                stage = eng.staging(X)
                if not axes:
                    return (mix(X, key), enc(X, seed), enc_tiles(X, seed),
                            None, stage)
                with jax.set_mesh(mesh):
                    xs = jax.device_put(X, on_mesh)
                    text = mix.lower(xs, key).compile().as_text()
                    return (mix(xs, key), enc(xs, seed), enc_tiles(xs, seed),
                            text, stage)
            ref, p_ref, t_ref, _, _ = run(())
            got, p_got, t_got, text, stage = run(("data",))
            ops_ = collective_ops(text)
            sum_w = sum(w for o, w in zip(topo.offsets, topo.weights)
                        if o % topo.n)
            res["rounds"][f"{topo.name}-{bits}bit-{backend}"] = {
                "max_diff": float(jnp.max(jnp.abs(got - ref))),
                "moved": float(jnp.max(jnp.abs(ref - X))),
                "level": B / 2 ** bits * sum_w,
                "payload_bytes_differing": int(jnp.sum(p_got != p_ref)),
                "tile_payload_bytes_differing": int(jnp.sum(t_got != t_ref)),
                "staging": stage,
                "payload_bytes": -(-D // spec.values_per_byte),
                "row_bytes": 1024 // spec.values_per_byte,
                "out_devices": len(got.sharding.device_set),
                "neighbours": len(topo.neighbor_offsets()),
                "permutes": [s for op, s in ops_
                             if op == "collective-permute"],
                "gathers": [s for op, s in ops_ if op == "all-gather"],
            }

cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(), num_layers=1,
                          d_model=64, num_heads=2, num_kv_heads=2,
                          head_dim=32, d_ff=128, vocab_size=64)
model = build_model(cfg)
shape = InputShape("tiny", seq_len=16, global_batch=8, kind="train")
for backend in ("jnp", "pallas"):
    tc = TrainerConfig(algo="moniqua", n_workers=N, bits=1, theta=2.0,
                       lr=0.3, steps=3, backend=backend,
                       comm_path="bucketed")
    out = {}
    for name, kw in (("stacked", {}),
                     ("mesh", dict(mesh=mesh,
                                   rules=ShardingRules("decentralized")))):
        tr = Trainer(model, shape, tc, **kw)
        state, losses = tr.init_state(), []
        for k in range(tc.steps):
            state, m = tr.step(state, tr.batch(k))
            losses.append(float(m["loss"]))
        out[name] = (losses, jax.device_get(state["params"]),
                     min(len(a.sharding.device_set)
                         for a in jax.tree.leaves(state["params"])))
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(
        jax.tree.leaves(out["mesh"][1]), jax.tree.leaves(out["stacked"][1])))
    res["trainer"][backend] = {
        "losses_mesh": out["mesh"][0], "losses_stacked": out["stacked"][0],
        "param_max_diff": diff, "param_devices": out["mesh"][2]}
print("RESULTS_JSON=" + json.dumps(res))
"""

ROUNDS = [f"{t}-{b}bit-{be}" for t in ("ring", "exponential")
          for b in (1, 8) for be in ("jnp", "pallas")]


@pytest.fixture(scope="module")
def mesh_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULTS_JSON=")][0]
    res = json.loads(line[len("RESULTS_JSON="):])
    assert res["devices"] == 4
    return res


@pytest.mark.parametrize("name", ROUNDS)
def test_sharded_round_matches_stacked(mesh_results, name):
    """A ``mix`` round with one worker per device gives the stacked
    round's payload bytes and mixed values."""
    r = mesh_results["rounds"][name]
    assert r["staging"] == "tiles"
    assert r["out_devices"] == 4
    assert r["payload_bytes_differing"] == 0
    assert r["tile_payload_bytes_differing"] == 0
    # the round moves values by up to a quantization level; a swapped or
    # missing neighbour would move them by as much again
    assert r["moved"] > 0.1 * r["level"]
    assert r["max_diff"] <= 1e-6, r


@pytest.mark.parametrize("name", ROUNDS)
def test_sharded_round_permutes_packed_bytes(mesh_results, name):
    """The exchange crosses devices as uint8 collective-permutes, one per
    neighbour offset, each no more than one 1024-element row of tile
    padding above the packed bucket, and nothing is gathered."""
    r = mesh_results["rounds"][name]
    assert len(r["permutes"]) >= r["neighbours"], r["permutes"]
    assert all("u8[" in s for s in r["permutes"]), r["permutes"]
    for s in r["permutes"]:
        for dims in re.findall(r"u8\[([\d,]*)\]", s):
            size = math.prod(int(d) for d in dims.split(",") if d)
            assert size <= r["payload_bytes"] + r["row_bytes"], s
    assert not r["gathers"], r["gathers"]


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_trainer_on_four_devices_matches_stacked(mesh_results, backend):
    """Three train steps with one worker per device take the stacked
    trainer's steps: same losses, same parameters."""
    r = mesh_results["trainer"][backend]
    assert r["param_devices"] == 4
    assert r["losses_mesh"] == pytest.approx(r["losses_stacked"], rel=1e-5)
    assert r["param_max_diff"] <= 1e-5, r["param_max_diff"]
