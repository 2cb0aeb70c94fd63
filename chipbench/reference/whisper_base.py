"""Plain float32 reference of the Whisper encoder-decoder (arXiv:2212.04356).

Written from the paper (Sec. 2.3, Fig. 1) in the form the configuration
runs.  It imports nothing of the program: the weights come from
:func:`init_params` (made by the benchmark from the seed, in the layout the
program's parameter tree has), and every matrix product takes its operands
through ``mm``, so the control can put the same model in a lower precision.

* Encoder: the frame embeddings that the two-convolution front end would
  produce are inputs (``enc_embeds``, drawn from the seed), plus sinusoidal
  positions; pre-norm blocks of bidirectional self-attention and a GELU MLP;
  a final layer norm.
* Decoder: token embeddings plus learned positions; pre-norm blocks of
  causal self-attention, cross-attention over the encoder output and a GELU
  MLP; a final layer norm; logits against the tied token embedding.

Departures from the paper, which the configuration makes and this reference
follows: the convolutional front end is not run (its output is an input),
the attention projections carry no biases, and the GELU is the tanh
approximation.  The vocabulary is padded to a multiple of 256 and the loss
masks the padding.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def padded_vocab(cfg) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def lengths(cfg, seq_len: int):
    """(encoder frames, decoder tokens) of a ``seq_len``-frame input."""
    enc = seq_len // cfg["encoder_downsample"]
    dec = min(cfg["decoder_len_cap"], max(seq_len // 8, 16))
    return enc, dec


def batch_spec(cfg, traffic):
    """The global batch of one step: (name, shape, dtype) in feed order."""
    gb = traffic["global_batch"]
    enc, dec = lengths(cfg, traffic["seq_len"])
    return [("enc_embeds", (gb, enc, cfg["d_model"]), cfg["dtype"]),
            ("tokens", (gb, dec), "int32"), ("labels", (gb, dec), "int32")]


def _tn(key, shape, scale, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def _attn_init(key, d, nh, hd, dt):
    ks = jax.random.split(key, 4)
    return {"wq": _tn(ks[0], (d, nh, hd), 1 / math.sqrt(d), dt),
            "wk": _tn(ks[1], (d, nh, hd), 1 / math.sqrt(d), dt),
            "wv": _tn(ks[2], (d, nh, hd), 1 / math.sqrt(d), dt),
            "wo": _tn(ks[3], (nh, hd, d), 1 / math.sqrt(nh * hd), dt)}


def _mlp_init(key, d, f, dt):
    k1, k2 = jax.random.split(key)
    return {"w_up": _tn(k1, (d, f), 1 / math.sqrt(d), dt),
            "w_down": _tn(k2, (f, d), 1 / math.sqrt(f), dt)}


def init_params(key, cfg):
    """Seeded weights in the program's parameter layout and served dtype:
    the blocks of each stack have a leading layer axis."""
    d, f, nh, hd = (cfg["d_model"], cfg["d_ff"], cfg["num_heads"],
                    cfg["head_dim"])
    dt = jnp.dtype(cfg["dtype"])
    ke, kd, kt, kp = jax.random.split(key, 4)
    ones, zeros = jnp.ones((d,), dt), jnp.zeros((d,), dt)

    def enc_block(k):
        ka, km = jax.random.split(k)
        return {"ln1": ones, "ln1b": zeros, "ln2": ones, "ln2b": zeros,
                "attn": _attn_init(ka, d, nh, hd, dt),
                "mlp": _mlp_init(km, d, f, dt)}

    def dec_block(k):
        ka, kc, km = jax.random.split(k, 3)
        return {"ln1": ones, "ln1b": zeros, "lnx": ones, "lnxb": zeros,
                "ln2": ones, "ln2b": zeros,
                "self_attn": _attn_init(ka, d, nh, hd, dt),
                "cross_attn": _attn_init(kc, d, nh, hd, dt),
                "mlp": _mlp_init(km, d, f, dt)}

    return {
        "enc_blocks": jax.vmap(enc_block)(
            jax.random.split(ke, cfg["encoder_layers"])),
        "dec_blocks": jax.vmap(dec_block)(
            jax.random.split(kd, cfg["num_layers"])),
        "tok_embed": _tn(kt, (padded_vocab(cfg), d), 0.02, dt),
        "dec_pos": _tn(kp, (cfg["decoder_len_cap"], d), 0.01, dt),
        "ln_enc": ones, "ln_encb": zeros, "ln_f": ones, "ln_fb": zeros,
    }


def _ln(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def sinusoids(length: int, channels: int):
    half = channels // 2
    inv = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                  * math.log(10000.0) / (half - 1))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _attention(p, xq, xkv, causal: bool, mm):
    q = jnp.einsum("bsd,dnh->bsnh", mm(xq), mm(p["wq"]), precision=HI)
    k = jnp.einsum("bsd,dnh->bsnh", mm(xkv), mm(p["wk"]), precision=HI)
    v = jnp.einsum("bsd,dnh->bsnh", mm(xkv), mm(p["wv"]), precision=HI)
    s = jnp.einsum("bqnh,bknh->bnqk", mm(q), mm(k),
                   precision=HI) / math.sqrt(q.shape[-1])
    if causal:
        sq, sk = s.shape[-2:]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnqk,bknh->bqnh", mm(w), mm(v), precision=HI)
    return jnp.einsum("bqnh,nhd->bqd", mm(o), mm(p["wo"]), precision=HI)


def _mlp(p, x, mm):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", mm(x), mm(p["w_up"]),
                               precision=HI), approximate=True)
    return jnp.einsum("bsf,fd->bsd", mm(h), mm(p["w_down"]), precision=HI)


def _enc_block(bp, x, mm):
    h = _ln(x, bp["ln1"], bp["ln1b"])
    x = x + _attention(bp["attn"], h, h, False, mm)
    return x + _mlp(bp["mlp"], _ln(x, bp["ln2"], bp["ln2b"]), mm)


def _dec_block(bp, x, enc, mm):
    h = _ln(x, bp["ln1"], bp["ln1b"])
    x = x + _attention(bp["self_attn"], h, h, True, mm)
    x = x + _attention(bp["cross_attn"], _ln(x, bp["lnx"], bp["lnxb"]), enc,
                       False, mm)
    return x + _mlp(bp["mlp"], _ln(x, bp["ln2"], bp["ln2b"]), mm)


def xent(logits, labels, vocab_size):
    """Mean token cross-entropy over the real vocabulary (padding masked)."""
    logits = jnp.where(jnp.arange(logits.shape[-1]) < vocab_size, logits,
                       -jnp.inf)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def loss(params, batch, cfg, mm):
    """Training loss of one worker's batch, all in float32; each block's
    intermediates are recomputed in the backward pass to fit one chip."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    e = batch["enc_embeds"].astype(jnp.float32)
    x = e + sinusoids(e.shape[1], cfg["d_model"])
    enc_step = jax.checkpoint(lambda h, bp: (_enc_block(bp, h, mm), None))
    x, _ = jax.lax.scan(enc_step, x, p["enc_blocks"])
    enc = _ln(x, p["ln_enc"], p["ln_encb"])
    tokens = batch["tokens"]
    y = p["tok_embed"][tokens] + p["dec_pos"][:tokens.shape[1]][None]
    dec_step = jax.checkpoint(
        lambda h, bp: (_dec_block(bp, h, enc, mm), None))
    y, _ = jax.lax.scan(dec_step, y, p["dec_blocks"])
    h = _ln(y, p["ln_f"], p["ln_fb"])
    logits = jnp.einsum("bsd,vd->bsv", mm(h), mm(p["tok_embed"]),
                        precision=HI)
    return xent(logits, batch["labels"], cfg["vocab_size"])


def forward_flops(cfg, seq_len: int) -> float:
    """Matrix-product FLOPs (2 per multiply-add) of one sequence's forward
    pass.  Encoder weights are charged to the encoder frames only; the
    cross-attention keys and values are projected from the encoder frames
    in every decoder layer; the head is over the real vocabulary; the
    embeddings are gathers."""
    d, f = cfg["d_model"], cfg["d_ff"]
    a = cfg["num_heads"] * cfg["head_dim"]
    te, td = lengths(cfg, seq_len)
    enc = (2 * te * d * a * 3 + 2 * te * a * d   # q, k, v, o
           + 2 * 2 * te * te * a                 # scores, weights . v
           + 2 * 2 * te * d * f)                 # MLP
    dec = (2 * td * d * a * 3 + 2 * td * a * d   # self q, k, v, o
           + 2 * 2 * td * td * a                 # self scores, weights . v
           + 2 * td * d * a + 2 * td * a * d     # cross q, o
           + 2 * 2 * te * d * a                  # cross k, v from frames
           + 2 * 2 * td * te * a                 # cross scores, weights . v
           + 2 * 2 * td * d * f)                 # MLP
    return (cfg["encoder_layers"] * enc + cfg["num_layers"] * dec
            + 2 * td * d * cfg["vocab_size"])


def train_flops(cfg, traffic) -> float:
    """Model FLOPs of one training step over all workers: forward and
    backward (twice the forward), recomputation not counted."""
    return 3.0 * traffic["global_batch"] * forward_flops(cfg,
                                                         traffic["seq_len"])
