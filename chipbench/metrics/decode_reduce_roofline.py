"""Kernels layer (``kernels/moniqua_decode_reduce.py``): the decode-reduce
phase's share of its HBM roofline.  Bytes counted from the work
(``counts.decode_reduce_bytes``: read the staged buffer, the worker's own
payload and its neighbours', write the mixed buffer, for each worker the
chip holds) over the chip's HBM bandwidth, over the device time of the ops
under the ``comm.decode_reduce`` scope on the chip that spends the most.
Only on the moniqua wire."""
from chipbench import counts
from chipbench.trace import has_scope


def read(win):
    c = win.counts
    if c["wire"] != "moniqua":
        return None
    per_chip = win.op_seconds(
        lambda op: has_scope(op, "comm.decode_reduce"))
    if not any(per_chip.values()):
        return None
    need = c["workers_per_chip"] * counts.decode_reduce_bytes(
        c["elems"], c["itemsize"], c["bits"], c["neighbors"]) * win.steps
    return need / win.peak["hbm_bytes_per_s"] / max(per_chip.values()) * 100
