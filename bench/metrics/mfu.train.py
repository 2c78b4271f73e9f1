"""``mfu.train``: model FLOPs of the tokens the traced window trained, over
the window's length times the chips' bf16 peak. Model FLOPs per token are 6x
the parameters the forward multiplies by (q/k/v, attention output and MLP
projections, and the output head once; the embedding gather counts nothing)
plus 3x the causal forward attention FLOPs. Recomputation does not count."""


def model_flops_per_token(m, seq: int) -> float:
    per_layer = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim \
        + m.n_heads * m.head_dim * m.d_model + 3 * m.d_model * m.d_ff
    matmul_params = m.n_layers * per_layer + m.vocab * m.d_model
    # QK^T and PV: 2 FLOPs x head_dim x heads per (query, key) pair, and a
    # query at position i sees i + 1 keys: (seq + 1) / 2 on average
    attn_fwd = m.n_layers * 2 * 2 * m.n_heads * m.head_dim * (seq + 1) / 2
    return 6.0 * matmul_params + 3.0 * attn_fwd


def read(ctx):
    if ctx.tokens <= 0:
        return None
    flops = model_flops_per_token(ctx.model, ctx.seq) * ctx.tokens
    return 100.0 * flops / (ctx.summary.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
