"""Pallas TPU kernel: flash-attention forward (online-softmax, windowed).

Why this kernel exists (EXPERIMENTS.md §Perf): at prefill/train shapes the
jnp attention materialises the [Sq, Sk] score matrix in f32 plus a ~5-op
softmax chain over it — the single largest HBM-bytes term of every dense
roofline (e.g. internlm2-20b train_4k: ~55% of bytes; llama3.2-3b
prefill_32k: ~70%).  The fused kernel streams K/V blocks through VMEM with
online-softmax accumulators, so HBM traffic is exactly Q+K+V+O — scores
never leave VMEM/VREGs.

TPU adaptation (vs the CUDA flash-attention):
  * grid = (batch*heads, q_blocks, k_blocks) with the k dimension marked
    "arbitrary" (sequential): accumulators (m, l, acc) live in VMEM scratch
    that persists across the k sweep — the Pallas/TPU idiom replacing CUDA
    warp-level reductions;
  * block shapes default (128, head_dim) / (128, head_dim): the QK^T and
    PV matmuls are 128x128-aligned for the MXU, and head_dim (64/128 for
    every assigned arch) is lane-aligned;
  * causal/sliding-window masks are computed from global indices via iota —
    no mask tensor is ever read from HBM (the jnp path broadcasts a
    [Sq, Sk] bool/f32 mask: measured ~100 GB/layer at 4k);
  * fully-masked k-blocks (beyond the causal frontier or the window) are
    skipped with @pl.when, so sliding-window attention does S*(w+c) work,
    matching the banded jnp fallback.

Validated bit-for-bit reasonable (allclose) against ``ref.flash_ref`` /
the model's masked-softmax oracle in ``tests/test_flash_attention.py``
(interpret mode; shapes x dtypes x window sweeps).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, sq: int, sk: int,
               blk_q: int, blk_k: int):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # k block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # global positions of this tile
    iq = i * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
    jk = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)

    # block-level skip: entirely above the causal diagonal / out of window
    q_lo = i * blk_q                       # smallest query index in tile
    q_hi = i * blk_q + blk_q - 1
    k_lo = j * blk_k
    k_hi = j * blk_k + blk_k - 1
    live = jnp.bool_(True)
    if causal:
        live = live & (k_lo <= q_hi)
        if window:
            live = live & (k_hi > q_lo - window)

    @pl.when(live)
    def _attend():
        q = q_ref[0].astype(jnp.float32)          # [blk_q, D]
        k = k_ref[0].astype(jnp.float32)          # [blk_k, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        valid = (iq < sq) & (jk < sk)
        if causal:
            valid &= jk <= iq
            if window:
                valid &= jk > iq - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                       # [blk_q, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                    # [blk_q, blk_k]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "causal", "window",
                                             "blk_q", "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, causal: bool = True, window: int = 0,
                    blk_q: int = DEFAULT_BLOCK_Q,
                    blk_k: int = DEFAULT_BLOCK_K,
                    interpret: bool = False) -> jax.Array:
    """q: [BH, Sq, D]; k, v: [BH, Sk, D] -> o [BH, Sq, D].

    Sq / Sk are padded to block multiples internally; padded keys are masked,
    padded queries produce garbage rows that are sliced off.
    """
    bh, sq, d = q.shape
    sk = k.shape[1]
    pq = (-sq) % blk_q
    pk = (-sk) % blk_k
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0))) if pk else v
    grid = (bh, (sq + pq) // blk_q, (sk + pk) // blk_k)
    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               window=window, sq=sq, sk=sk,
                               blk_q=blk_q, blk_k=blk_k)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq + pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),   # m: running max
            pltpu.VMEM((blk_q, 1), jnp.float32),   # l: running denominator
            pltpu.VMEM((blk_q, d), jnp.float32),   # acc: running numerator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :sq, :]
