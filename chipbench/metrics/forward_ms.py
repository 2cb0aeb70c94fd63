"""Step layer (``train/train_step.py``, ``models/``): device milliseconds
per step in the forward pass, the operations under the step's
``train.grad`` scope with no ``transpose(`` component, on the chip that
spends the most."""
from chipbench import layers as L


def read(win):
    return L.device_ms(win, L.is_forward)
