"""Host time of the scan assembly per query: the self time of the
program's ``exec.scan`` span (SMA pruning, block-cache lookups, block
gathers and their concat, the predicate and SIP mask dispatch) over the
window's queries, in ms."""
from chipbench import spantrace


def read(ctx):
    s = spantrace.recorded_self_s(ctx, "exec.scan")
    return None if s is None else 1e3 * s / len(ctx.queries)
