"""granite-4.0-h-micro's last pipeline stage as an IR graph.

granite-4.0-h-micro (ibm-granite/granite-4.0-h-micro, model type
``granitemoehybrid`` without experts) stacks 40 layers in the pattern
``mmmmmammmm`` x 4: Mamba-2 mixers ("m") and NoPE GQA attention ("a"),
each mixer followed by a SwiGLU MLP, at hidden size 2048.  Deployed as
four pipeline stages of ten layers on four chips, the last stage holds
layers 30-39 (one whole period, attention at 35), the final norm and the
tied head over the whole vocabulary.  This module builds that stage's
prefill: the hidden states of one prompt in, the next token's logits out.

One layer, ``h`` of shape (batch, seq, d)::

    h = h + 0.22 * mixer(rmsnorm_in(h))
    h = h + 0.22 * W_out(silu(a) * b),  [a | b] = rmsnorm_post(h) W_in

Mamba-2 mixer: ``[z | xBC | dt_raw] = h W_in``, ``xBC = silu(conv(xBC))``
(causal depthwise, 4 taps, bias), ``x, B, C = xBC``, the ``ssd`` scan of
``x`` (heads of ``head_dim`` channels, one group of ``state``), a gated
norm ``rmsnorm(y * silu(z))`` over every channel and ``W_o``.  Attention:
``q``, ``k``, ``v`` projections without bias or rotary embedding, query
head ``i`` reading key/value head ``i // (q_heads / kv_heads)``, scores
times the attention multiplier, causal softmax, ``W_o``.  Stage end:
``rmsnorm_final(h)[seq - 1] W_head / logits_scaling``.

Grouped-query attention is laid out by grouping the queries: the query
heads that share a key/value head are stacked along the rows, so the
scores of a group are one (heads_per_group * seq, seq) matrix against its
one key head, and the causal softmax masks column ``c`` of row ``r`` when
``c > r mod seq``.  No key or value is copied.

Every multiplier is an op attribute: the benchmark makes every parameter
a seeded normal, so a constant held as a parameter would be random.
Parameter names carry the published layer index (``l35_q``).
"""

from __future__ import annotations

from repro.core.ir import Graph

DT = "bfloat16"          # the published checkpoint's dtype

NAME = "granite_h_micro_stage4"
PATTERN = "mmmmmammmm"    # layers 30-39 of the published 40
RESIDUAL = 0.22           # residual_multiplier
ATTENTION = 0.015625      # attention_multiplier (1 / head_dim)
LOGITS_SCALING = 8.0
EPS = 1e-5                # rms_norm_eps
CHUNK = 256               # mamba_chunk_size


def granite_h_micro_stage4(seq: int = 2048, d: int = 2048,
                           heads: int = 64, head_dim: int = 64,
                           state: int = 128, conv: int = 4,
                           chunk: int = CHUNK, q_heads: int = 32,
                           kv_heads: int = 8, attn_head_dim: int = 64,
                           ffn: int = 8192, vocab: int = 100352,
                           pattern: str = PATTERN, first: int = 30,
                           batch: int = 1) -> Graph:
    """The stage's prefill graph; every width defaults to the published
    one.  ``pattern`` gives each layer's mixer, from layer ``first``."""
    g = Graph(NAME)
    g.tokens = seq
    x = g.add_input("h", (batch, seq, d), DT)

    def param(name, shape):
        return g.add_param(name, shape, DT)

    def dense(h, cin, cout, name):
        return g.add_op("dense", [h, param(name, (cin, cout))], name=name)

    def norm(h, width, name):
        return g.add_op("rmsnorm", [h, param(name, (width,))], name=name,
                        eps=EPS)

    def cut(h, begin, end, name):
        return g.add_op("slice", [h], name=name, axis=2, begin=begin,
                        end=end)

    def residual(r, h, name):
        h = g.add_op("mul", [h], name=f"{name}_scale", scale=RESIDUAL)
        return g.add_op("add", [r, h], name=name)

    def mamba(h, p):
        inner = heads * head_dim
        width = inner + 2 * state
        zxbcdt = dense(h, d, inner + width + heads, f"{p}_in_proj")
        z = cut(zxbcdt, 0, inner, f"{p}_z")
        xbc = cut(zxbcdt, inner, inner + width, f"{p}_xbc")
        dt = cut(zxbcdt, inner + width, inner + width + heads, f"{p}_dt")
        xbc = g.add_op("causal_conv1d", [xbc, param(f"{p}_conv_w",
                                                    (conv, width)),
                                         param(f"{p}_conv_b", (width,))],
                       name=f"{p}_conv")
        xbc = g.add_op("silu", [xbc], name=f"{p}_conv_act")
        xs = cut(xbc, 0, inner, f"{p}_x")
        b = cut(xbc, inner, inner + state, f"{p}_B")
        c = cut(xbc, inner + state, width, f"{p}_C")
        y = g.add_op("ssd", [xs, dt, b, c, param(f"{p}_A_log", (heads,)),
                             param(f"{p}_dt_bias", (heads,)),
                             param(f"{p}_D", (heads,))],
                     name=f"{p}_ssd", chunk=chunk)
        gate = g.add_op("silu", [z], name=f"{p}_gate")
        y = g.add_op("mul", [y, gate], name=f"{p}_gated")
        y = norm(y, inner, f"{p}_gate_norm")
        return dense(y, inner, d, f"{p}_out_proj")

    def attention(h, p):
        group = q_heads // kv_heads
        hd = attn_head_dim
        q = dense(h, d, q_heads * hd, f"{p}_q")
        k = dense(h, d, kv_heads * hd, f"{p}_k")
        v = dense(h, d, kv_heads * hd, f"{p}_v")
        # queries (b, s, kv, group, hd) -> (b, kv, group * s, hd)
        q = g.add_op("reshape", [q], name=f"{p}_q_heads",
                     shape=(batch, seq, kv_heads, group, hd))
        q = g.add_op("transpose", [q], name=f"{p}_q_perm",
                     perm=(0, 2, 3, 1, 4))
        q = g.add_op("reshape", [q], name=f"{p}_q_rows",
                     shape=(batch, kv_heads, group * seq, hd))
        k = g.add_op("reshape", [k], name=f"{p}_k_heads",
                     shape=(batch, seq, kv_heads, hd))
        kt = g.add_op("transpose", [k], name=f"{p}_k_perm",
                      perm=(0, 2, 3, 1))
        v = g.add_op("reshape", [v], name=f"{p}_v_heads",
                     shape=(batch, seq, kv_heads, hd))
        v = g.add_op("transpose", [v], name=f"{p}_v_perm",
                     perm=(0, 2, 1, 3))
        s = g.add_op("batch_matmul", [q, kt], name=f"{p}_qk")
        s = g.add_op("mul", [s], name=f"{p}_qk_scale", scale=ATTENTION)
        s = g.add_op("softmax", [s], name=f"{p}_attn", causal=True)
        o = g.add_op("batch_matmul", [s, v], name=f"{p}_pv")
        o = g.add_op("reshape", [o], name=f"{p}_o_heads",
                     shape=(batch, kv_heads, group, seq, hd))
        o = g.add_op("transpose", [o], name=f"{p}_o_perm",
                     perm=(0, 3, 1, 2, 4))
        o = g.add_op("reshape", [o], name=f"{p}_o_merge",
                     shape=(batch, seq, q_heads * hd))
        return dense(o, q_heads * hd, d, f"{p}_o")

    h = x
    for i, kind in enumerate(pattern):
        p = f"l{first + i}"
        mixer = {"m": mamba, "a": attention}[kind]
        h = residual(h, mixer(norm(h, d, f"{p}_in_norm"), p), f"{p}_mix_res")
        a_b = dense(norm(h, d, f"{p}_post_norm"), d, 2 * ffn, f"{p}_mlp_in")
        act = g.add_op("silu", [cut(a_b, 0, ffn, f"{p}_mlp_a")],
                       name=f"{p}_mlp_act")
        m = g.add_op("mul", [act, cut(a_b, ffn, 2 * ffn, f"{p}_mlp_b")],
                     name=f"{p}_mlp_glu")
        h = residual(h, dense(m, ffn, d, f"{p}_mlp_out"), f"{p}_mlp_res")
    last = g.add_op("slice", [h], name="last_token", axis=1,
                    begin=seq - 1, end=seq)
    last = g.add_op("reshape", [last], name="last_row", shape=(batch, d))
    logits = dense(norm(last, d, "final_norm"), d, vocab, "head")
    logits = g.add_op("mul", [logits], name="logits",
                      scale=1.0 / LOGITS_SCALING)
    g.mark_output(logits)
    g.validate()
    return g


LM_STAGES = {NAME: granite_h_micro_stage4}
