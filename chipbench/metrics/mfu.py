"""Step layer (``train/train_step.py``, ``models/``): the whole step's share
of the chips' bf16 peak.  The configuration's model FLOPs per step (its
reference's ``train_flops``: forward and backward, recomputation not
counted) times the steps traced, over the traced window times the chips
times the peak of their ``device_kind``."""


def read(win):
    flops = win.counts["flops_per_step"] * win.steps
    return flops / (win.seconds * win.chips
                    * win.peak["bf16_flops_per_s"]) * 100.0
