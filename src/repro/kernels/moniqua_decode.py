"""Pallas TPU kernel: fused Moniqua decode (unpack → dequant → mod-recover).

Receiver side of Algorithm 1: given the packed payload from a neighbor and the
receiver's own model tile ``y`` (the Lemma 1 reference), produce

    x_hat = ((q * B) - y) mod B + y           (mode="remote", line 5)
    x_hat = (q * B) - (y mod B) + y           (mode="self",   line 4)

in a single VMEM pass: one packed read (bits/8 bytes/elem) + one y read +
one f32/bf16 write.  The two modes share the decode-reduce kernel's
chunked MXU unpack (``moniqua_decode_reduce.tile_values``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import packing
from repro.kernels.moniqua_decode_reduce import chunk_loop, tile_values

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_COLS = 1024


def _decode_kernel(p_ref, y_ref, b_ref, *refs, bits: int, mode: str):
    o_ref = refs[-1]
    umat = refs[0][...] if len(refs) == 2 else None
    B = b_ref[0]
    for cs, ps in chunk_loop(bits, y_ref.shape[1]):
        qb = tile_values(p_ref[:, ps], bits, B, umat)
        y = y_ref[:, cs].astype(jnp.float32)
        if mode == "remote":
            d = qb - y
            out = (d - B * jnp.floor(d / B + 0.5)) + y   # cmod(q*B - y, B) + y
        elif mode == "self":
            ymod = y - B * jnp.floor(y / B + 0.5)        # cmod(y, B)
            out = qb - ymod + y
        else:
            raise ValueError(mode)
        o_ref[:, cs] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "mode", "block_rows",
                                             "block_cols", "interpret"))
def decode(packed: jax.Array, y2d: jax.Array, B: jax.Array, *, bits: int,
           mode: str = "remote",
           block_rows: int = DEFAULT_BLOCK_ROWS,
           block_cols: int = DEFAULT_BLOCK_COLS,
           interpret: bool = False) -> jax.Array:
    """Decode packed (rows, cols*bits/8) against local y (rows, cols)."""
    rows, cols = y2d.shape
    vpb = 8 // bits
    if cols % block_cols or rows % block_rows:
        raise ValueError(f"shape {y2d.shape} not tiled by "
                         f"({block_rows},{block_cols}); pad in ops.py")
    grid = (rows // block_rows, cols // block_cols)
    kernel = functools.partial(_decode_kernel, bits=bits, mode=mode)
    in_specs = [
        pl.BlockSpec((block_rows, block_cols // vpb), lambda i, j: (i, j)),
        pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        pl.BlockSpec((1,), lambda i, j: (0,)),
    ]
    args = [packed, y2d, jnp.asarray(B, jnp.float32).reshape(1)]
    if vpb > 1:
        umat = packing.unpack_matrix(bits)
        in_specs.append(pl.BlockSpec(umat.shape, lambda i, j: (0, 0)))
        args.append(umat)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_rows, block_cols), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), y2d.dtype),
        interpret=interpret,
    )(*args)
