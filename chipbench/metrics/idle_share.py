"""Device layer: the share of the traced window in which no operation ran,
mean over the chips (1 - union of op intervals / window)."""


def read(win):
    busy = win.op_seconds()
    if not busy:
        return None
    return (1.0 - sum(busy.values()) / len(busy) / win.seconds) * 100.0
