#!/usr/bin/env python3
"""Smoke test of decentralized Moniqua training on the TPU.

Drives the main path once, through the entry points a user calls:
``Trainer`` training xlstm-125m at its published widths (12 layers,
d_model 768, vocab 50304; weights random from ``--seed``) with n=4 workers
on a ring, sequence 1024, global batch 16, Moniqua gossip on the bucketed
path (theta 0.1, lr 0.01).  The Pallas backend (the TPU kernels) is
checked against the pure-jnp backend with the same seed.

    python chip_smoke.py               # one chip: 1 and 8 bits, both
                                       # backends, plus one kernel-level
                                       # gossip round per width
    python chip_smoke.py --four-chips  # one worker per chip on four chips
                                       # against the workers stacked on
                                       # one chip: 1-bit training, and one
                                       # gossip round per width

Needs a TPU: without one it exits 2 and prints no result.  Any failed check
exits 1.  On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Times printed here are host-clock times of this device, after warm-up.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "xlstm-125m"
N_WORKERS = 4
SEQ, GLOBAL_BATCH = 1024, 16
LR = 0.01
# Lemma 1's bound on how far two workers' parameters may differ.  1-bit
# Moniqua codes the sign of (x mod B), with B = 4 theta: a parameter near
# 0 whose code differs from its neighbour's moves by B/2 times the weight,
# 0.07 here.  The workers start equal, and a step moves a parameter by at
# most lr |g|_inf; the printed g_inf, a running max that starts at 1 and
# decays by 0.9 a step, bounds |g|_inf by 0.9 at step 0, so 0.1 holds with
# room over the run.  The trainer's default theta of 2 makes that jump 1.3;
# 1-bit training at theta 2 diverged to NaN within 6 steps on the CPU at
# reduced size (it was not run on the chip).
THETA = 0.1
# Largest |loss(a) - loss(b)| allowed at any step, in nats.  A sanity bound
# on the model step only: the loss starts at ln(50304) = 10.8 and moves by
# about 0.02 nats over the run, and 1- and 8-bit gossip differ by 0.016
# nats at step 3, so no gossip fault would show here.  The gossip is
# checked by the kernel-level rounds: equal payload bytes and mixed values
# within one quantization level, on workers that differ by up to theta.
LOSS_TOL = 0.05
# Largest share of payload bytes Pallas and jnp may encode differently on
# one chip: a boundary flip needs an element within an ulp or two of a
# rounding boundary (rate ~1e-5 at 8 bits); a layout bug changes most
# bytes.  The same kernels on four chips and on one must agree exactly.
BYTES_TOL = 1e-3


class Checks:
    """Every check of a run, so that a failed one does not hide the rest;
    the script exits 1 if any failed."""

    def __init__(self) -> None:
        self.failed: list = []

    def __call__(self, cond: bool, msg: str) -> None:
        print(f"  {'ok' if cond else 'FAILED'}: {msg}")
        if not cond:
            self.failed.append(msg)

    def phase(self, name: str, fn, *args):
        """Run one phase; an exception fails it and the run goes on."""
        try:
            return fn(*args)
        except Exception:  # noqa: BLE001 — reported, and fails the run
            traceback.print_exc()
            self(False, f"phase {name} ran to its end")
            return None


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def build_model():
    from repro.configs import get_config
    from repro.configs.base import InputShape
    from repro.models.model_factory import build_model as build
    shape = InputShape("smoke", seq_len=SEQ, global_batch=GLOBAL_BATCH,
                       kind="train")
    return build(get_config(ARCH)), shape


def make_trainer(model, shape, bits: int, backend: str, steps: int,
                 seed: int, mesh=None):
    from repro.models.sharding import ShardingRules
    from repro.train.trainer import Trainer, TrainerConfig
    tc = TrainerConfig(algo="moniqua", topology="ring", n_workers=N_WORKERS,
                       bits=bits, theta=THETA, lr=LR, wire="moniqua",
                       backend=backend, comm_path="bucketed", steps=steps,
                       seed=seed)
    return Trainer(model, shape, tc, mesh=mesh,
                   rules=ShardingRules("decentralized") if mesh else None)


def train_run(label: str, model, shape, bits: int, backend: str,
              steps: int, seed: int, mesh=None, keep: tuple = ()):
    """Compile one train step ahead of time, take ``steps`` steps and time
    those after the first.  Returns losses, step times, the HLO text and a
    host copy of the parameters after each step in ``keep``."""
    import jax
    tr = make_trainer(model, shape, bits, backend, steps, seed, mesh)
    state = tr.init_state()
    batches = [tr.batch(k) for k in range(steps)]
    t0 = time.perf_counter()
    if mesh is None:
        compiled = tr.jstep.lower(state, batches[0]).compile()
    else:
        with jax.set_mesh(mesh):
            compiled = tr.jstep.lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    losses, g_inf, times, kept = [], [], [], {}
    for k in range(steps):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batches[k])
        jax.block_until_ready((state, metrics))
        times.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
        g_inf.append(float(metrics["g_inf"]))
        if k in keep:
            kept[k] = jax.device_get(state["params"])
    del state, batches, compiled
    warm = times[1:] or times
    print(f"[{label}] compile {compile_s:.1f} s; losses "
          + " ".join(f"{v:.6f}" for v in losses)
          + "; g_inf " + " ".join(f"{v:.4g}" for v in g_inf))
    print(f"[{label}] step time on this device after warm-up: "
          f"mean {sum(warm) / len(warm) * 1e3:.1f} ms over {len(warm)} "
          f"steps ({' '.join(f'{t * 1e3:.1f}' for t in warm)} ms)")
    print(f"[{label}] memory_analysis of this compile, bytes per device: "
          f"arguments {ma.argument_size_in_bytes}, outputs "
          f"{ma.output_size_in_bytes}, temporaries {ma.temp_size_in_bytes}, "
          f"aliased {ma.alias_size_in_bytes}")
    return {"losses": losses, "times": warm, "text": text, "kept": kept}


def check_losses(check: Checks, label: str, run) -> None:
    check(all(math.isfinite(v) for v in run["losses"]),
          f"{label}: every loss is finite")


def compare_losses(check: Checks, label: str, a, b) -> None:
    gap = max(abs(x - y) for x, y in zip(a["losses"], b["losses"]))
    print(f"[{label}] largest loss gap {gap:.3e} nats (tolerance "
          f"{LOSS_TOL})")
    check(gap <= LOSS_TOL, f"{label}: loss gap within {LOSS_TOL} nats")


def _mix_round(spec, X, key, B: float, backend: str, mesh=None):
    """One ``CommEngine.mix`` round on a ring and the stacked encode of
    ``X``; with ``mesh``, one worker per chip, and both results brought
    back to the first chip.  Returns (mixed values, payload)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.comm.engine import CommEngine, MoniquaWire
    from repro.core.topology import ring
    from repro.kernels import ops as kops
    axes = ("data",) if mesh is not None else ()
    eng = CommEngine(ring(N_WORKERS), MoniquaWire(spec), backend=backend,
                     path="bucketed", worker_axes=axes)
    mix = jax.jit(lambda x, k: eng.mix(x, theta=THETA, key=k).x)
    enc = jax.jit(lambda x, s: kops.moniqua_encode_stacked(
        x, B, spec, s, backend=backend, worker_axes=axes))
    seed = kops._key_to_seed(key)
    if mesh is None:
        return mix(X, key), enc(X, seed)
    with jax.set_mesh(mesh):
        xm = jax.device_put(X, NamedSharding(mesh, P("data")))
        out = mix(xm, key), enc(xm, seed)
    del xm
    first = X.sharding
    return tuple(jax.device_put(a, first) for a in out)


def kernel_round(check: Checks, bits: int, width: int, seed: int,
                 mesh=None) -> None:
    """One gossip round on a [4, width] buffer: Pallas against jnp on one
    chip or, with ``mesh``, Pallas with one worker per chip against Pallas
    with the workers stacked on one chip."""
    import jax
    import jax.numpy as jnp
    from repro.core import modulo
    from repro.core.quantizers import QuantSpec
    from repro.core.topology import ring

    spec = QuantSpec(bits=bits, stochastic=bits > 1)
    topo = ring(N_WORKERS)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    base = jax.random.normal(k1, (1, width), jnp.float32) * 0.02
    # workers within theta of each other, as Lemma 1 assumes
    X = base + jax.random.uniform(k2, (N_WORKERS, width), jnp.float32,
                                  -0.45, 0.45) * THETA
    B = float(modulo.b_theta(THETA, spec.delta))
    if mesh is None:
        names, bytes_tol = ("pallas", "jnp"), BYTES_TOL
        a = _mix_round(spec, X, k3, B, "pallas")
        b = _mix_round(spec, X, k3, B, "jnp")
    else:
        names, bytes_tol = ("4 chips", "1 chip stacked"), 0.0
        b = _mix_round(spec, X, k3, B, "pallas")
        a = _mix_round(spec, X, k3, B, "pallas", mesh)
    moved = float(jnp.max(jnp.abs(b[0] - X)))
    del X
    diff = float(jnp.max(jnp.abs(a[0] - b[0])))
    share_el = float(jnp.mean(a[0] != b[0]))
    share_b = float(jnp.mean(a[1] != b[1]))
    del a, b
    sum_w = sum(w for o, w in zip(topo.offsets, topo.weights) if o % topo.n)
    level = B / 2 ** bits
    eps = 8 * 2.0 ** -23 * (B + 1.0)
    print(f"[kernel {bits}-bit, [{N_WORKERS}, {width}]] max |{names[0]} - "
          f"{names[1]}| {diff:.3e} (one level x sum of weights = "
          f"{level * sum_w:.3e}; the round moved values by up to "
          f"{moved:.3e}); elements differing {share_el:.3e}; payload bytes "
          f"differing {share_b:.3e}")
    check(diff <= level * sum_w + eps,
          f"kernel {bits}-bit {names[0]} vs {names[1]}: mixed values within "
          f"one quantization level")
    check(share_b <= bytes_tol,
          f"kernel {bits}-bit {names[0]} vs {names[1]}: payload bytes "
          f"differing <= {bytes_tol}")


def one_chip(check: Checks, args, devices) -> None:
    from repro.comm.engine import resolve_backend
    resolved = resolve_backend("auto")
    print(f"resolve_backend('auto') = {resolved!r}")
    check(resolved == "pallas", "the auto backend resolves to pallas")
    model, shape = build_model()
    print(f"model {ARCH} at published widths: layers "
          f"{model.cfg.num_layers}, d_model {model.cfg.d_model}, vocab "
          f"{model.cfg.vocab_size}; n={N_WORKERS} ring, seq "
          f"{shape.seq_len}, global batch {shape.global_batch}, wire "
          f"moniqua, path bucketed, theta {THETA}, lr {LR}")
    for bits in (1, 8):
        runs = {}
        for backend in ("pallas", "jnp"):
            label = f"{bits}-bit {backend}"
            run = check.phase(label, train_run, label, model, shape, bits,
                              backend, args.steps, args.seed)
            if run is None:
                continue
            runs[backend] = run
            check_losses(check, label, run)
            n_kernels = run["text"].count("tpu_custom_call")
            print(f"[{label}] tpu_custom_call in compiled step: {n_kernels}")
            if backend == "pallas":
                check(n_kernels > 0,
                      f"{label}: the compiled step runs the Pallas kernels")
            print(f"[{label}] peak_bytes_in_use so far "
                  f"{peak_bytes(devices)}")
        if len(runs) == 2:
            compare_losses(check, f"{bits}-bit pallas vs jnp",
                           runs["pallas"], runs["jnp"])
    width = flat_width(model, shape, args.seed)
    for bits in (1, 8):
        check.phase(f"kernel {bits}-bit", kernel_round, check, bits, width,
                    args.seed)
    print(f"peak_bytes_in_use {peak_bytes(devices)}")


def flat_width(model, shape, seed: int) -> int:
    """Elements per worker of the gossip round's flat buffer."""
    import jax
    tr = make_trainer(model, shape, 8, "jnp", 1, seed)
    return tr.hp.engine().layout(
        jax.eval_shape(tr.init_state)["params"]).padded_elems


def param_gaps(label: str, a, b, jump: float) -> None:
    """How far two host copies of the stacked parameters differ: at all,
    and by more than half of the move a flipped 1-bit code makes."""
    import jax
    import numpy as np
    n = differ = big = 0
    largest, big_vals = 0.0, []
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        d = np.abs(np.asarray(x, np.float32) - np.asarray(y, np.float32))
        n += d.size
        differ += int(np.count_nonzero(d))
        largest = max(largest, float(d.max()))
        sel = d[d > jump / 2]
        big += sel.size
        if sel.size:
            big_vals.append(sel)
    typical = float(np.median(np.concatenate(big_vals))) if big_vals else 0.0
    print(f"[{label}] max |4 chips - 1 chip stacked| {largest:.3e}; elements "
          f"differing {differ / n:.3e}; elements off by more than half a "
          f"1-bit jump ({jump / 2:.3e}): {big} of {n}, median {typical:.3e}")


def four_chips(check: Checks, args, devices) -> None:
    from repro.analysis.roofline import collective_ops
    from repro.core import modulo
    from repro.core.quantizers import QuantSpec
    from repro.launch.mesh import make_worker_mesh
    mesh = make_worker_mesh(devices[:N_WORKERS])
    model, shape = build_model()
    bits = 1
    print(f"model {ARCH} at published widths, {bits}-bit moniqua, pallas; "
          f"one worker per chip on {mesh.devices.size} chips vs all "
          f"{N_WORKERS} workers stacked on one chip")
    keep = (0, 1)
    sharded = check.phase("4 chips", train_run, "4 chips", model, shape,
                          bits, "pallas", args.steps, args.seed, mesh, keep)
    if sharded is not None:
        check_losses(check, "4 chips", sharded)
        ops_ = collective_ops(sharded["text"])
        permutes = [s for op, s in ops_ if op == "collective-permute"]
        gathers = [s for op, s in ops_ if op == "all-gather"]
        print(f"[4 chips] collective-permutes: {len(permutes)} "
              f"{sorted(set(s.split('{')[0].lstrip('(') for s in permutes))}")
        print(f"[4 chips] all-gathers: {gathers}")
        check(bool(permutes) and all("u8[" in s and "f32[" not in s
                                     and "bf16[" not in s for s in permutes),
              "4 chips: every collective-permute carries a u8 payload")
        check(not any("f32[" in s for s in gathers),
              "4 chips: no all-gather of f32 data")
        check("tpu_custom_call" in sharded["text"],
              "4 chips: the compiled step runs the Pallas kernels")
        print(f"[4 chips] peak_bytes_in_use (max over chips) "
              f"{peak_bytes(devices)}")
    stacked = check.phase("1 chip stacked", train_run, "1 chip stacked",
                          model, shape, bits, "pallas", args.steps,
                          args.seed, None, keep)
    if stacked is not None:
        check_losses(check, "1 chip stacked", stacked)
    if sharded is not None and stacked is not None:
        compare_losses(check, "4 chips vs 1 chip stacked", sharded, stacked)
        # step 0's loss is the forward pass on the same initial parameters:
        # no gossip has run yet
        print(f"[4 chips vs 1 chip stacked] step-0 loss gap, before any "
              f"gossip: {abs(sharded['losses'][0] - stacked['losses'][0]):.3e}"
              f" nats")
        # the round of step 0 mixes equal initial parameters, so the
        # parameters after it differ only by the gradients; step 1's round
        # is the first that codes parameters which differ
        jump = (float(modulo.b_theta(THETA, QuantSpec(bits=bits, stochastic=False).delta))
                / 2 ** bits / 3)
        for k in keep:
            check.phase(f"params after step {k}", param_gaps,
                        f"params after step {k}", sharded["kept"][k],
                        stacked["kept"][k], jump)
    del sharded, stacked
    width = flat_width(model, shape, args.seed)
    for b in (1, 8):
        check.phase(f"kernel {b}-bit 4 chips", kernel_round, check, b, width,
                    args.seed, mesh)
    print(f"peak_bytes_in_use (max over chips) {peak_bytes(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="one worker per chip on four chips, against the "
                         "one-chip stacked run; nothing else")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    print(f"device: platform {info['platform']}, kind {info['kind']}, "
          f"count {info['count']}")
    if info["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < N_WORKERS:
        print(f"chip_smoke: --four-chips needs {N_WORKERS} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    from repro.launch import compile_cache
    print(f"compile cache: {compile_cache.enable()}")
    check = Checks()
    (four_chips if args.four_chips else one_chip)(check, args, devices)
    if check.failed:
        print("chip_smoke: FAILED: " + "; ".join(check.failed),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
