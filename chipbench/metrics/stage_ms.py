"""Round layer (``comm/bucket.py``): device milliseconds per step in the
copies into and out of the gossip round's flat buffer, the operations
under ``comm.stage`` (``BucketLayout.flatten``) or ``comm.scatter``
(``BucketLayout.unflatten``), on the chip that spends the most."""
from chipbench import layers as L


def read(win):
    return L.device_ms(win, L.is_stage)
