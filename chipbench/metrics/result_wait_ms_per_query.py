"""Host time spent waiting for a query's answer to reach the host per
query: the self time of the program's ``exec.result_sync`` spans (the
device program finishes, then its result is copied out) over the window's
queries, in ms."""
from chipbench import spantrace


def read(ctx):
    s = spantrace.recorded_self_s(ctx, "exec.result_sync")
    return None if s is None else 1e3 * s / len(ctx.queries)
