"""Work counted from shapes: the bytes a gossip kernel must move, and the
peak table the shares are taken against.

The codec kernels are elementwise, so their least time is their bytes over
the chip's HBM bandwidth.  Bytes are counted from the work, per worker, for
a flat buffer of ``D`` elements of ``itemsize`` bytes (the parameters'
dtype, in which the round stages them), ``bits`` per element on the wire
and ``k`` neighbours:

* encode: read the buffer, write the packed payload;
* decode-reduce: read the buffer, the worker's own payload and the ``k``
  neighbours' payloads; write the mixed buffer.

Model FLOPs live with each configuration's reference (``train_flops``).
"""
from __future__ import annotations

import json
import math
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def flat_elems(shapes) -> int:
    """Elements of one worker's parameters (``shapes``: one per leaf)."""
    return sum(math.prod(s) for s in shapes)


def encode_bytes(elems: int, itemsize: int, bits: int) -> float:
    return elems * itemsize + elems * bits / 8


def decode_reduce_bytes(elems: int, itemsize: int, bits: int,
                        neighbors: int) -> float:
    return 2 * elems * itemsize + (neighbors + 1) * elems * bits / 8


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
