"""Host time the code-domain scan waits for its survivor set per query:
the self time of the program's ``exec.cd_sync`` span (the device masks
finish, then the survivor positions come to the host) over the window's
queries, in ms."""
from chipbench import spantrace


def read(ctx):
    s = spantrace.recorded_self_s(ctx, "exec.cd_sync")
    return None if s is None else 1e3 * s / len(ctx.queries)
