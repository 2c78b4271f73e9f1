"""``mvm_fused_roofline``: summed least time of every traced call of the
kernel over their summed device time (see ``bench/work/mvm_fused.py``). Left
out where no such call ran in the window."""
from bench.trace_reduce import roofline_share
from bench.work import mvm_fused


def read(ctx):
    return roofline_share(ctx.summary, ctx.calls, lambda f: f in mvm_fused.FAMILIES,
                          mvm_fused.work, ctx.peaks)
