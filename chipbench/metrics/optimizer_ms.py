"""Step layer (``optim/sgd.py``, ``core/algorithms.py``): device
milliseconds per step under the ``train.optimizer`` scope (momentum,
weight decay, the running gradient bound and the parameter update), on the
chip that spends the most."""
from chipbench import layers as L


def read(win):
    return L.device_ms(win, L.is_optimizer)
