"""``opa_fused_roofline``: summed least time of every traced call of the
kernel over their summed device time (see ``bench/work/opa_fused.py``). Left
out where no such call ran in the window."""
from bench.trace_reduce import roofline_share
from bench.work import opa_fused


def read(ctx):
    return roofline_share(ctx.summary, ctx.calls, lambda f: f in opa_fused.FAMILIES,
                          opa_fused.work, ctx.peaks)
