"""The layer of a device operation, from the program's named scopes.

The program opens scopes inside the step (``repro.obs.trace.SCOPES``):
``train.grad`` around the forward and backward passes (JAX puts the
backward's operations under a ``transpose(`` component of the path),
``train.optimizer`` around the momentum and the parameter update,
``comm.stage`` and ``comm.scatter`` around the copies into and out of the
gossip round's flat buffer, and the round's phases.  Each operation is
read by its own scope path, as ``chipbench/trace.py`` loads it (an
operation the compiler made with no ``op_name`` has none).  Where the step
program opens no ``train.*`` scope (an older program), the readers have
nothing to read and return None.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from chipbench import trace as T

# the round's phases, as ``comm_ms`` reads them
ROUND = ("comm.encode", "comm.permute", "comm.decode_reduce")


def _grad_path(op: T.Op) -> Optional[List[str]]:
    parts = op.scope.split("/")
    if "train.grad" not in parts:
        return None
    return parts[parts.index("train.grad") + 1:]


def is_forward(op: T.Op) -> bool:
    rest = _grad_path(op)
    return rest is not None and not any("transpose(" in p for p in rest)


def is_backward(op: T.Op) -> bool:
    rest = _grad_path(op)
    return rest is not None and any("transpose(" in p for p in rest)


def is_optimizer(op: T.Op) -> bool:
    return T.has_scope(op, "train.optimizer")


def is_stage(op: T.Op) -> bool:
    return T.has_scope(op, "comm.stage") or T.has_scope(op, "comm.scatter")


def is_round(op: T.Op) -> bool:
    return any(T.has_scope(op, p) for p in ROUND)


def is_unscoped(op: T.Op) -> bool:
    return not any(p.startswith(("train.", "comm."))
                   for p in op.scope.split("/"))


def device_ms(win, pred: Callable[[T.Op], bool]) -> Optional[float]:
    """Device ms per step in the ops whose scope satisfies ``pred``, on the
    chip that spends the most; None where the step program opens no
    ``train.*`` scope."""
    if not any(p.startswith("train.") for o in win.trace.ops
               for p in o.scope.split("/")):
        return None
    return max(win.op_seconds(pred).values()) / win.steps * 1e3
