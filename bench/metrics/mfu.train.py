"""``mfu.train``: model FLOPs of the tokens the traced window trained, over
the window's length times the chips' bf16 peak. The model FLOPs per token
are the cell's reference's count (``model_flops_per_token`` of the module
the configuration names; ``bench/refmodel.py``'s for a dense decoder), which
the harness passes as ``ctx.flops_per_token``."""


def read(ctx):
    if ctx.tokens <= 0:
        return None
    flops = ctx.flops_per_token * ctx.tokens
    return 100.0 * flops / (ctx.summary.window_s * ctx.chips * ctx.peaks["bf16_flops_per_s"])
