"""Device layer: device milliseconds per step in which a collective-permute
(the payload crossing between chips) was in flight, from its start to its
done (the asynchronous ops of the trace; the synchronous ones where the
trace has none), on the chip that spends the most.  Nothing to read where
no collective-permute ran."""


def read(win):
    def permute(op):
        return "collective-permute" in op.name

    per_chip = win.op_seconds(permute, asynchronous=True)
    if not any(per_chip.values()):
        per_chip = win.op_seconds(permute)
    if not any(per_chip.values()):
        return None
    return max(per_chip.values()) / win.steps * 1e3
