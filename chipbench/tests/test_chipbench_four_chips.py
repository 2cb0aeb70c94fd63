"""One worker per chip: a whole run of a small cell on four devices decides
``correct`` from the comparison, which holds for the program as it is and
fails with the exchange between the devices left out.  Four forced host
devices, in a subprocess, so that ``XLA_FLAGS`` is read before JAX starts;
the harness's look for a chip is skipped."""
import json
import os
import subprocess
import sys

from chipbench.tests import tiny

SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from chipbench import cells, run
from chipbench.tests import tiny

root = tiny.make_root(sys.argv[1], placement="worker_per_chip",
                      global_batch=8)
devices = jax.devices()[:4]
out = {"devices": len(devices)}
out["sound"] = run.run(cells.load(root, "tiny-cell"), 2 ** 31 + 13, 0.5,
                       False, devices)["correct"]
from repro.comm.engine import CommEngine, MixResult
CommEngine.mix = lambda self, X, *a, **kw: MixResult(X, {}, None)
out["no_exchange"] = run.run(cells.load(root, "tiny-cell"), 2 ** 31 + 13,
                             0.5, False, devices)["correct"]
print("RESULT " + json.dumps(out))
"""


def test_four_devices_sound_and_without_exchange(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(tiny.ROOT, "src"), tiny.ROOT,
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESULT ")]
    assert proc.returncode == 0 and lines, proc.stderr[-3000:]
    res = json.loads(lines[-1][len("RESULT "):])
    assert res == {"devices": 4, "sound": True, "no_exchange": False}
