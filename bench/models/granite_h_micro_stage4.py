"""granite-4.0-h-micro, last pipeline stage: layers 30-39, the final norm
and the tied head, as a 2048-token prefill.

Source: huggingface.co/ibm-granite/granite-4.0-h-micro (config.json,
model type granitemoehybrid, no experts).  Input: the hidden states of
one prompt entering layer 30, (seq, 2048).  Output: the next token's
logits over the whole vocabulary, (100352,).

Layers 30-39 follow the pattern ``mmmmmammmm``: Mamba-2 mixers and, at
layer 35, NoPE grouped-query attention; every mixer is followed by a
SwiGLU MLP, both inside residuals scaled by 0.22, behind RMSNorms of eps
1e-5.  The Mamba-2 mixer is written as its sequential recurrence, one
token at a time, and its causal conv as four shifted taps.  Every
contraction goes through ``nn.dense``.  The widths are read from the
parameters' shapes, so the same forward runs at any width.
"""

import jax
import jax.numpy as jnp
from jax import lax

INPUT = ("h", (2048, 2048))
OUTPUT = "logits:out"

PATTERN = "mmmmmammmm"
FIRST = 30
RESIDUAL = 0.22
ATTENTION = 0.015625
LOGITS_SCALING = 8.0
EPS = 1e-5


def params(d=2048, heads=64, head_dim=64, state=128, conv=4, q_heads=32,
           kv_heads=8, attn_head_dim=64, ffn=8192, vocab=100352,
           pattern=PATTERN, first=FIRST):
    """Parameter name -> shape, as the served graph names them; the
    defaults are the published widths."""
    out = {}
    inner = heads * head_dim
    width = inner + 2 * state
    for i, kind in enumerate(pattern):
        p = f"l{first + i}"
        out[f"{p}_in_norm"] = (d,)
        if kind == "m":
            out[f"{p}_in_proj"] = (d, inner + width + heads)
            out[f"{p}_conv_w"] = (conv, width)
            out[f"{p}_conv_b"] = (width,)
            out[f"{p}_A_log"] = (heads,)
            out[f"{p}_dt_bias"] = (heads,)
            out[f"{p}_D"] = (heads,)
            out[f"{p}_gate_norm"] = (inner,)
            out[f"{p}_out_proj"] = (inner, d)
        else:
            out[f"{p}_q"] = (d, q_heads * attn_head_dim)
            out[f"{p}_k"] = (d, kv_heads * attn_head_dim)
            out[f"{p}_v"] = (d, kv_heads * attn_head_dim)
            out[f"{p}_o"] = (q_heads * attn_head_dim, d)
        out[f"{p}_post_norm"] = (d,)
        out[f"{p}_mlp_in"] = (d, 2 * ffn)
        out[f"{p}_mlp_out"] = (ffn, d)
    out["final_norm"] = (d,)
    out["head"] = (d, vocab)
    return out


def _rmsnorm(x, w):
    return w * x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)


def _mamba(p, pre, h, nn):
    """Mamba-2 mixer of one sequence h (s, d), token by token."""
    heads = p[f"{pre}_A_log"].shape[0]
    inner = p[f"{pre}_gate_norm"].shape[0]
    width = p[f"{pre}_conv_b"].shape[0]
    state = (width - inner) // 2
    s = h.shape[0]
    zxbcdt = nn.dense(h, p[f"{pre}_in_proj"])
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:inner + width]
    dt = jax.nn.softplus(zxbcdt[:, inner + width:] + p[f"{pre}_dt_bias"])
    w = p[f"{pre}_conv_w"]
    taps = w.shape[0]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = p[f"{pre}_conv_b"]
    for j in range(taps):
        conv = conv + w[j] * padded[j:j + s]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :inner].reshape(s, heads, inner // heads)
    b, c = xbc[:, inner:inner + state], xbc[:, inner + state:]
    a = -jnp.exp(p[f"{pre}_A_log"])

    def token(hs, t):
        x_t, dt_t, b_t, c_t = t
        hs = (jnp.exp(dt_t * a)[:, None, None] * hs
              + (dt_t[:, None] * x_t)[:, :, None] * b_t)
        return hs, nn.dense(hs, c_t[:, None])[..., 0]

    _, y = lax.scan(token, jnp.zeros((heads, inner // heads, state),
                                     h.dtype), (x, dt, b, c))
    y = (y + p[f"{pre}_D"][:, None] * x).reshape(s, inner)
    y = _rmsnorm(y * jax.nn.silu(z), p[f"{pre}_gate_norm"])
    return nn.dense(y, p[f"{pre}_out_proj"])


def _attention(p, pre, h, nn, hd):
    """Causal grouped-query attention of one sequence h (s, d) with heads
    of width hd, no rotary embedding: query head i reads key/value head
    i // (query heads / key heads)."""
    s = h.shape[0]
    q_heads = p[f"{pre}_q"].shape[1] // hd
    kv_heads = p[f"{pre}_k"].shape[1] // hd
    q = nn.dense(h, p[f"{pre}_q"]).reshape(s, q_heads, hd).transpose(1, 0, 2)
    k = nn.dense(h, p[f"{pre}_k"]).reshape(s, kv_heads, hd).transpose(1, 0, 2)
    v = nn.dense(h, p[f"{pre}_v"]).reshape(s, kv_heads, hd).transpose(1, 0, 2)
    k = jnp.repeat(k, q_heads // kv_heads, axis=0)             # (Hq, s, hd)
    v = jnp.repeat(v, q_heads // kv_heads, axis=0)
    scores = nn.dense(q, k.transpose(0, 2, 1)) * ATTENTION
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = nn.dense(probs, v).transpose(1, 0, 2).reshape(s, q_heads * hd)
    return nn.dense(o, p[f"{pre}_o"])


def _stage(p, h, nn, attn_head_dim):
    layers = sorted({int(k[1:].split("_")[0]) for k in p if k[0] == "l"})
    for i in layers:
        pre = f"l{i}"
        x = _rmsnorm(h, p[f"{pre}_in_norm"])
        if f"{pre}_q" in p:
            y = _attention(p, pre, x, nn, attn_head_dim)
        else:
            y = _mamba(p, pre, x, nn)
        h = h + RESIDUAL * y
        ab = nn.dense(_rmsnorm(h, p[f"{pre}_post_norm"]), p[f"{pre}_mlp_in"])
        ffn = ab.shape[-1] // 2
        y = nn.dense(jax.nn.silu(ab[:, :ffn]) * ab[:, ffn:],
                     p[f"{pre}_mlp_out"])
        h = h + RESIDUAL * y
    last = _rmsnorm(h[-1:], p["final_norm"])
    return nn.dense(last, p["head"])[0] / LOGITS_SCALING


def forward(p, x, nn, attn_head_dim=64):
    """Logits of every sequence of the batch x (n, seq, d), one sequence
    at a time.  The attention heads' width is the one width the
    parameters' shapes do not give."""
    return lax.map(lambda h: _stage(p, h, nn, attn_head_dim), x)
