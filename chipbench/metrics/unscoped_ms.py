"""Device layer: device milliseconds per step in operations under none of
the program's ``train.*`` or ``comm.*`` scopes, on the chip that spends
the most: the step's own bookkeeping, the programs that make the batch,
and every operation the compiler made with no ``op_name`` (copies,
prefetches, zeroed buffers and the loops that fill them)."""
from chipbench import layers as L


def read(win):
    return L.device_ms(win, L.is_unscoped)
