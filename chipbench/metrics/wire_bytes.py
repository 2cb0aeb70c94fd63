"""Round layer: payload bytes one worker sends per step
(``Trainer.bytes_per_step``, the program's count from shapes)."""


def read(win):
    return float(win.counts["wire_bytes"])
