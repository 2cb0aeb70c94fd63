"""Loop layer (``train/trainer.py``): host milliseconds per step spent
making the batch (``Trainer.batch``) and dispatching the step
(``Trainer.step``), from the benchmark's spans in the traced window."""


def read(win):
    return ((win.span_seconds("bench.batch")
             + win.span_seconds("bench.dispatch")) / win.steps * 1e3)
