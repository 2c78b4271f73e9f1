"""``opa_deposit_roofline``: summed least time of every traced call of the
kernel over their summed device time (see ``bench/work/opa_deposit.py``). Left
out where no such call ran in the window."""
from bench.trace_reduce import roofline_share
from bench.work import opa_deposit


def read(ctx):
    return roofline_share(ctx.summary, ctx.calls, lambda f: f in opa_deposit.FAMILIES,
                          opa_deposit.work, ctx.peaks)
