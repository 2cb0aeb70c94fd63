"""xLSTM blocks (sLSTM + mLSTM, arXiv:2405.04517), TPU-adapted.

Structure per the paper: a stack interleaving

* **mLSTM blocks** — matrix-memory LSTM: per head, state
  ``C_t = f_t C_{t-1} + i_t v_t k_t^T``, normalizer ``n_t = f_t n_{t-1} + i_t k_t``,
  output ``h_t = C_t q_t / max(|n_t . q_t|, 1)``.  Fully parallelizable; we
  compute it **chunkwise** (intra-chunk quadratic + inter-chunk scanned state),
  which is the TPU-native formulation (MXU-friendly [c x c] blocks instead of a
  length-S sequential loop).
* **sLSTM blocks** — scalar-memory LSTM with per-head recurrent mixing
  ``R h_{t-1}``; inherently sequential, computed with ``lax.scan`` over time.

Hardware adaptation (recorded per DESIGN.md): the paper's *exponential* input
gate is replaced by a sigmoid (log-gate clipped <= 0).  This removes the
running-max stabilizer state while preserving the matrix-memory/normalizer
recurrence; on TPU it avoids f32 overflow in the chunkwise exp() terms.

Pattern: layer ``l`` is sLSTM iff ``l % slstm_every == 0`` (cfg.ssm.slstm_every
> 0), expressed as a scanned super-block of ``slstm_every`` layers.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as L


# ---------------------------------------------------------------------------
# mLSTM cell (chunkwise parallel)
# ---------------------------------------------------------------------------

def init_mlstm(key, cfg):
    d, nh = cfg.d_model, cfg.num_heads
    hd = d // nh
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 7)
    return {
        "ln": jnp.ones((d,), dt),
        "w_up": L.dense_init(ks[0], d, 2 * d, dt),
        "wq": L.dense_init(ks[1], d, d, dt),
        "wk": L.dense_init(ks[2], d, d, dt),
        "wv": L.dense_init(ks[3], d, d, dt),
        "w_if": L.dense_init(ks[4], d, 2 * nh, dt),   # input & forget pre-gates
        "w_down": L.dense_init(ks[5], d, d, dt, scale=1.0 / math.sqrt(d)),
    }


def mlstm_pspecs():
    return {"ln": (None,), "w_up": ("embed", "mlp"), "wq": ("embed", "heads"),
            "wk": ("embed", "heads"), "wv": ("embed", "heads"),
            "w_if": ("embed", None), "w_down": ("heads", "embed")}


def _mlstm_scan_chunks(q, k, v, log_f, log_i, chunk):
    """q,k,v: [B,S,H,D]; log_f/log_i: [B,S,H] (<= 0).  Returns h [B,S,H,D]."""
    B, S, H, D = q.shape
    c = min(chunk, S)
    assert S % c == 0
    nc = S // c
    qc = q.reshape(B, nc, c, H, D)
    kc = k.reshape(B, nc, c, H, D)
    vc = v.reshape(B, nc, c, H, D)
    lf = log_f.reshape(B, nc, c, H)
    li = log_i.reshape(B, nc, c, H)
    F = jnp.cumsum(lf, axis=2)                      # within-chunk decay prefix
    Ftot = F[:, :, -1, :]                           # [B,nc,H]

    # intra-chunk: att[t,s] = exp(F_t - F_s + li_s) * (q_t . k_s), s <= t
    expo = F[:, :, :, None, :] - F[:, :, None, :, :] + li[:, :, None, :, :]
    tri = jnp.tril(jnp.ones((c, c), bool))
    # mask before the exp: above the diagonal F_t - F_s > 0 grows with the
    # chunk and overflows f32 at chunk 128, and a masked exp(inf) still
    # sends inf * 0 = NaN into the backward pass
    w = jnp.exp(jnp.where(tri[None, None, :, :, None], expo,
                          -jnp.inf))                     # [B,nc,t,s,H]
    qk = jnp.einsum("bnthd,bnshd->bntsh", qc, kc).astype(jnp.float32)
    aw = w * qk / math.sqrt(D)
    y_intra = jnp.einsum("bntsh,bnshd->bnthd", aw.astype(q.dtype), vc)
    # normalizer intra part: n_t . q_t = sum_s w[t,s] * (k_s . q_t)
    denom_intra = jnp.sum(aw, axis=3)               # [B,nc,t,H]

    # per-chunk boundary contributions: S_c = sum_s exp(Ftot - F_s + li_s) k_s v_s^T
    wS = jnp.exp(Ftot[:, :, None, :] - F + li)       # [B,nc,c,H]
    Sc = jnp.einsum("bnsh,bnshd,bnshe->bnhde", wS.astype(q.dtype), kc, vc)
    nSc = jnp.einsum("bnsh,bnshd->bnhd", wS.astype(q.dtype), kc)

    # inter-chunk recurrence over nc chunks
    def body(carry, xs):
        Cprev, nprev = carry
        Sc_i, nSc_i, Ftot_i = xs
        dec = jnp.exp(Ftot_i)[:, :, None, None].astype(Cprev.dtype)
        Cn = Cprev * dec + Sc_i
        nn = nprev * dec[:, :, :, 0] + nSc_i
        return (Cn, nn), (Cprev, nprev)

    C0 = jnp.zeros((B, H, D, D), q.dtype)
    n0 = jnp.zeros((B, H, D), q.dtype)
    xs = (jnp.moveaxis(Sc, 1, 0), jnp.moveaxis(nSc, 1, 0),
          jnp.moveaxis(Ftot, 1, 0))
    (_, _), (Cprevs, nprevs) = jax.lax.scan(body, (C0, n0), xs)
    Cprevs = jnp.moveaxis(Cprevs, 0, 1)             # [B,nc,H,D,D] state before chunk
    nprevs = jnp.moveaxis(nprevs, 0, 1)

    wq_in = jnp.exp(F)                               # decay from chunk start
    y_inter = jnp.einsum("bnth,bnthd,bnhde->bnthe",
                         wq_in.astype(q.dtype), qc, Cprevs) / math.sqrt(D)
    denom_inter = jnp.einsum("bnth,bnthd,bnhd->bnth",
                             wq_in.astype(q.dtype), qc, nprevs) / math.sqrt(D)

    y = y_intra + y_inter
    denom = jnp.maximum(jnp.abs(denom_intra + denom_inter.astype(jnp.float32)), 1.0)
    h = y / denom[..., None].astype(y.dtype)
    return h.reshape(B, S, H, D)


def mlstm_block(p, cfg, x):
    """x: [B,S,d] -> [B,S,d]."""
    B, S, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    xin = L.rms_norm(x, p["ln"])
    up = xin @ p["w_up"]
    u, z = jnp.split(up, 2, axis=-1)
    q = (u @ p["wq"]).reshape(B, S, nh, hd)
    k = (u @ p["wk"]).reshape(B, S, nh, hd)
    v = (u @ p["wv"]).reshape(B, S, nh, hd)
    gates = (u @ p["w_if"]).astype(jnp.float32)
    li = jax.nn.log_sigmoid(gates[..., :nh])
    lf = jax.nn.log_sigmoid(gates[..., nh:])
    h = _mlstm_scan_chunks(q, k, v, lf, li, cfg.ssm.chunk)
    out = (h.reshape(B, S, d) * jax.nn.silu(z)) @ p["w_down"]
    return x + out


def mlstm_decode(p, cfg, x, state):
    """Single step. x: [B,1,d]; state: {"C":[B,H,D,D], "n":[B,H,D]}."""
    B, _, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    xin = L.rms_norm(x, p["ln"])
    up = xin @ p["w_up"]
    u, z = jnp.split(up, 2, axis=-1)
    u1 = u[:, 0]
    q = (u1 @ p["wq"]).reshape(B, nh, hd)
    k = (u1 @ p["wk"]).reshape(B, nh, hd)
    v = (u1 @ p["wv"]).reshape(B, nh, hd)
    gates = (u1 @ p["w_if"]).astype(jnp.float32)
    i = jax.nn.sigmoid(gates[..., :nh])[..., None]
    f = jax.nn.sigmoid(gates[..., nh:])[..., None]
    C = state["C"] * f[..., None].astype(state["C"].dtype) + \
        (i.astype(v.dtype))[..., None] * v[..., :, None] * k[..., None, :]
    n = state["n"] * f.astype(state["n"].dtype) + i.astype(k.dtype) * k
    num = jnp.einsum("bhd,bhed->bhe", q, C) / math.sqrt(hd)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n)) / math.sqrt(hd), 1.0)
    h = (num / den[..., None]).reshape(B, 1, d)
    out = (h * jax.nn.silu(z)) @ p["w_down"]
    return x + out, {"C": C, "n": n}


def init_mlstm_state(batch, cfg):
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    dt = jnp.dtype(cfg.dtype)
    return {"C": jnp.zeros((batch, nh, hd, hd), dt),
            "n": jnp.zeros((batch, nh, hd), dt)}


# ---------------------------------------------------------------------------
# sLSTM cell (sequential scan)
# ---------------------------------------------------------------------------

def init_slstm(key, cfg):
    d, nh = cfg.d_model, cfg.num_heads
    hd = d // nh
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    return {
        "ln": jnp.ones((d,), dt),
        "w": L.dense_init(ks[0], d, 4 * d, dt),          # z,i,f,o pre-acts
        "r": (jax.random.normal(ks[1], (nh, hd, 4 * hd), jnp.float32)
              / math.sqrt(hd)).astype(dt),               # recurrent per head
        "w_down": L.dense_init(ks[2], d, d, dt, scale=1.0 / math.sqrt(d)),
    }


def slstm_pspecs():
    return {"ln": (None,), "w": ("embed", None), "r": ("heads", None, None),
            "w_down": ("embed", "embed")}


def _slstm_step(p, cfg, wx_t, state):
    """wx_t: [B, 4d] precomputed input part; state h/c/n: [B,H,D]."""
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    B = wx_t.shape[0]
    h_prev = state["h"]
    rec = jnp.einsum("bhd,hde->bhe", h_prev, p["r"])     # [B,H,4hd]
    pre = wx_t.reshape(B, nh, 4 * hd) + rec
    z, i, f, o = jnp.split(pre.astype(jnp.float32), 4, axis=-1)
    z = jnp.tanh(z)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    o = jax.nn.sigmoid(o)
    c = f * state["c"].astype(jnp.float32) + i * z
    n = f * state["n"].astype(jnp.float32) + i
    h = o * c / jnp.maximum(n, 1.0)
    dt = state["h"].dtype
    return {"h": h.astype(dt), "c": c.astype(dt), "n": n.astype(dt)}


def slstm_block(p, cfg, x):
    B, S, d = x.shape
    nh = cfg.num_heads
    hd = d // nh
    xin = L.rms_norm(x, p["ln"])
    wx = xin @ p["w"]                                    # [B,S,4d]
    state = init_slstm_state(B, cfg)

    def body(st, wx_t):
        st = _slstm_step(p, cfg, wx_t, st)
        return st, st["h"]

    _, hs = jax.lax.scan(body, state, jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    return x + h @ p["w_down"]


def slstm_decode(p, cfg, x, state):
    xin = L.rms_norm(x, p["ln"])
    wx = (xin @ p["w"])[:, 0]
    st = _slstm_step(p, cfg, wx, state)
    h = st["h"].reshape(x.shape[0], 1, cfg.d_model)
    return x + h @ p["w_down"], st


def init_slstm_state(batch, cfg):
    nh = cfg.num_heads
    hd = cfg.d_model // nh
    dt = jnp.dtype(cfg.dtype)
    z = jnp.zeros((batch, nh, hd), dt)
    return {"h": z, "c": z, "n": z}
