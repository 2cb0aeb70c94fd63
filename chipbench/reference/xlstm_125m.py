"""Plain float32 reference of the xLSTM language model (arXiv:2405.04517).

Written from the paper's equations, in the form the configuration runs.
It imports nothing of the program: the weights come from
:func:`init_params` (made by the benchmark from the seed, in the layout the
program's parameter tree has), and every matrix product takes its operands
through ``mm``, so the control can put the same model in a lower precision.

The blocks, as the configuration states them:

* mLSTM (matrix memory), per head with ``D = d_model / heads``::

      C_t = f_t C_{t-1} + i_t v_t k_t^T      n_t = f_t n_{t-1} + i_t k_t
      h_t = C_t q_t / max(|n_t . q_t|, 1)    (q.k scaled by 1 / sqrt(D))

  computed here in the paper's parallel form over the whole sequence,
  ``h = (exp(F_t - F_s + log i_s) [s <= t] * q_t.k_s) v / max(|row sum|, 1)``
  with ``F`` the cumulative ``log f``.  Block: RMS norm, up-projection to
  ``2d`` split into ``u`` and the output gate ``z``; ``q, k, v`` and the
  two gate pre-activations from ``u``; ``(h * silu(z)) W_down`` added to
  the residual.
* sLSTM (scalar memory), per head, a recurrence over time with
  head-block-diagonal recurrent weights ``r``: pre-activations
  ``W x_t + r h_{t-1}`` split per head into ``z, i, f, o``;
  ``c_t = f c + i tanh(z)``, ``n_t = f n + i``, ``h_t = o c_t / max(n_t, 1)``.

Departures from the paper, which the configuration makes and this reference
follows: the input gate is a sigmoid instead of an exponential (so no
stabiliser state), the mLSTM block has no causal convolution, learnable skip
or group norm, and the sLSTM block has no gated MLP after it.  Layer ``l``
is an sLSTM block when ``l % slstm_every == 0``.  The vocabulary is padded
to a multiple of 256 and the loss masks the padding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def padded_vocab(cfg) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def is_slstm(cfg, layer: int) -> bool:
    k = (cfg.get("ssm") or {}).get("slstm_every", 0)
    return bool(k) and layer % k == 0


def batch_spec(cfg, traffic):
    """The global batch of one step: (name, shape, dtype) in feed order."""
    shape = (traffic["global_batch"], traffic["seq_len"])
    return [("tokens", shape, "int32"), ("labels", shape, "int32")]


def _tn(key, shape, scale, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def init_params(key, cfg):
    """Seeded weights in the program's parameter layout and served dtype."""
    d, nh = cfg["d_model"], cfg["num_heads"]
    hd = d // nh
    V = padded_vocab(cfg)
    dt = jnp.dtype(cfg["dtype"])
    ke, kb, kh = jax.random.split(key, 3)
    layers = []
    for i, k in enumerate(jax.random.split(kb, cfg["num_layers"])):
        ks = jax.random.split(k, 6)
        if is_slstm(cfg, i):
            layers.append({"slstm": {
                "ln": jnp.ones((d,), dt),
                "w": _tn(ks[0], (d, 4 * d), 1 / math.sqrt(d), dt),
                "r": _tn(ks[1], (nh, hd, 4 * hd), 1 / math.sqrt(hd), dt),
                "w_down": _tn(ks[2], (d, d), 1 / math.sqrt(d), dt)}})
        else:
            layers.append({"mlstm": {
                "ln": jnp.ones((d,), dt),
                "w_up": _tn(ks[0], (d, 2 * d), 1 / math.sqrt(d), dt),
                "wq": _tn(ks[1], (d, d), 1 / math.sqrt(d), dt),
                "wk": _tn(ks[2], (d, d), 1 / math.sqrt(d), dt),
                "wv": _tn(ks[3], (d, d), 1 / math.sqrt(d), dt),
                "w_if": _tn(ks[4], (d, 2 * nh), 1 / math.sqrt(d), dt),
                "w_down": _tn(ks[5], (d, d), 1 / math.sqrt(d), dt)}})
    return {"embed": _tn(ke, (V, d), 0.02, dt), "layers": layers,
            "ln_f": jnp.ones((d,), dt),
            "head": _tn(kh, (d, V), 1 / math.sqrt(d), dt)}


def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mlstm(p, cfg, x, mm):
    B, S, d = x.shape
    nh = cfg["num_heads"]
    D = d // nh
    xin = _rms(x, p["ln"])
    up = jnp.einsum("bsd,de->bse", mm(xin), mm(p["w_up"]), precision=HI)
    u, z = up[..., :d], up[..., d:]

    def proj(w):
        return jnp.einsum("bsd,de->bse", mm(u), mm(w), precision=HI)

    q = proj(p["wq"]).reshape(B, S, nh, D)
    k = proj(p["wk"]).reshape(B, S, nh, D)
    v = proj(p["wv"]).reshape(B, S, nh, D)
    gates = proj(p["w_if"])
    log_i = jax.nn.log_sigmoid(gates[..., :nh])           # [B,S,H]
    log_f = jax.nn.log_sigmoid(gates[..., nh:])
    F = jnp.cumsum(log_f, axis=1)
    expo = (F[:, :, None, :] - F[:, None, :, :]
            + log_i[:, None, :, :])                        # [B,t,s,H]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, expo, -jnp.inf))
    qk = jnp.einsum("bthd,bshd->btsh", mm(q), mm(k), precision=HI)
    a = decay * qk / math.sqrt(D)
    y = jnp.einsum("btsh,bshd->bthd", mm(a), mm(v), precision=HI)
    h = y / jnp.maximum(jnp.abs(jnp.sum(a, axis=2)), 1.0)[..., None]
    out = jnp.einsum("bsd,de->bse", mm(h.reshape(B, S, d) * jax.nn.silu(z)),
                     mm(p["w_down"]), precision=HI)
    return x + out


def _slstm(p, cfg, x, mm):
    B, S, d = x.shape
    nh = cfg["num_heads"]
    D = d // nh
    xin = _rms(x, p["ln"])
    wx = jnp.einsum("bsd,de->bse", mm(xin), mm(p["w"]),
                    precision=HI).reshape(B, S, nh, 4 * D)
    r = mm(p["r"])

    def step(carry, wx_t):
        h, c, n = carry
        pre = wx_t + jnp.einsum("bhd,hde->bhe", mm(h), r, precision=HI)
        zt, it, ft, ot = jnp.split(pre, 4, axis=-1)
        c = jax.nn.sigmoid(ft) * c + jax.nn.sigmoid(it) * jnp.tanh(zt)
        n = jax.nn.sigmoid(ft) * n + jax.nn.sigmoid(it)
        h = jax.nn.sigmoid(ot) * c / jnp.maximum(n, 1.0)
        return (h, c, n), h

    zero = jnp.zeros((B, nh, D), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero), jnp.moveaxis(wx, 1, 0))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, d)
    return x + jnp.einsum("bsd,de->bse", mm(h), mm(p["w_down"]), precision=HI)


def xent(logits, labels, vocab_size):
    """Mean token cross-entropy over the real vocabulary (padding masked)."""
    logits = jnp.where(jnp.arange(logits.shape[-1]) < vocab_size, logits,
                       -jnp.inf)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def loss(params, batch, cfg, mm):
    """Training loss of one worker's batch, all in float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    x = p["embed"][batch["tokens"]]
    for i, bp in enumerate(p["layers"]):
        # one block's intermediates at a time: the backward pass recomputes
        # them, which keeps the reference inside one chip's memory
        if is_slstm(cfg, i):
            x = jax.checkpoint(lambda q, y: _slstm(q, cfg, y, mm))(
                bp["slstm"], x)
        else:
            x = jax.checkpoint(lambda q, y: _mlstm(q, cfg, y, mm))(
                bp["mlstm"], x)
    h = _rms(x, p["ln_f"])
    logits = jnp.einsum("bsd,dv->bsv", mm(h), mm(p["head"]), precision=HI)
    return xent(logits, batch["labels"], cfg["vocab_size"])


def forward_flops_per_token(cfg, seq_len: int) -> float:
    """Matrix-product FLOPs (2 per multiply-add) of one token's forward pass,
    as the configuration computes it: the mLSTM in chunks of ``chunk``
    (an intra-chunk block of ``chunk`` scores per token and head, and the
    chunk state's ``D x D`` update and read), the sLSTM recurrence, the
    output head over the real vocabulary.  The embedding is a gather."""
    d, nh = cfg["d_model"], cfg["num_heads"]
    D = d // nh
    c = min(cfg["ssm"]["chunk"], seq_len)
    mlstm = (2 * d * 2 * d            # up-projection to u, z
             + 3 * 2 * d * d          # q, k, v
             + 2 * d * 2 * nh         # input and forget gates
             + 2 * d * d              # down-projection
             + 2 * 2 * c * d          # intra-chunk q.k and weights . v
             + 2 * 2 * D * d          # chunk state k v^T, and q . C
             + 2 * 2 * d)             # normaliser: k sums, and q . n
    slstm = (2 * d * 4 * d            # input pre-activations
             + 2 * nh * D * 4 * D     # recurrent pre-activations
             + 2 * d * d)             # down-projection
    n_s = sum(is_slstm(cfg, i) for i in range(cfg["num_layers"]))
    n_m = cfg["num_layers"] - n_s
    return n_m * mlstm + n_s * slstm + 2 * d * cfg["vocab_size"]


def train_flops(cfg, traffic) -> float:
    """Model FLOPs of one training step over all workers: forward and
    backward (twice the forward), recomputation not counted."""
    tokens = traffic["global_batch"] * traffic["seq_len"]
    return 3.0 * tokens * forward_flops_per_token(cfg, traffic["seq_len"])
