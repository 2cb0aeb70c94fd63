"""Round layer (``comm/engine.py``, ``comm/bucket.py``): device
milliseconds per step in the operations under the gossip round's
``comm.encode``, ``comm.permute`` and ``comm.decode_reduce`` scopes, on
the chip that spends the most."""
from chipbench.trace import has_scope

PHASES = ("comm.encode", "comm.permute", "comm.decode_reduce")


def read(win):
    per_chip = win.op_seconds(
        lambda op: any(has_scope(op, p) for p in PHASES))
    if not any(per_chip.values()):
        return None
    return max(per_chip.values()) / win.steps * 1e3
