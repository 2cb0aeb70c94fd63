"""Kernels layer (``kernels/moniqua_encode.py``): the encode phase's share
of its HBM roofline.  Bytes counted from the work (``counts.encode_bytes``:
read the staged buffer, write the packed payload, for each worker the chip
holds) over the chip's HBM bandwidth, over the device time of the ops under
the ``comm.encode`` scope on the chip that spends the most.  Only on the
moniqua wire."""
from chipbench import counts
from chipbench.trace import has_scope


def read(win):
    c = win.counts
    if c["wire"] != "moniqua":
        return None
    per_chip = win.op_seconds(lambda op: has_scope(op, "comm.encode"))
    if not any(per_chip.values()):
        return None
    need = c["workers_per_chip"] * counts.encode_bytes(
        c["elems"], c["itemsize"], c["bits"]) * win.steps
    return need / win.peak["hbm_bytes_per_s"] / max(per_chip.values()) * 100
