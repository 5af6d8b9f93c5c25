"""The profiler window and its reduction to device busy time.

A traced run wraps its measured window in a host span ``window``, each
query in ``query.<template>`` and the client's own work between queries
in ``client`` (``jax.profiler.TraceAnnotation``, so the spans land in the
profiler's trace on the device's clock).  ``read_trace`` pulls from the
``.xplane.pb`` file the device operations of every TPU and those spans;
``reduce`` turns them into:

- ``busy_s``: the union of the intervals in which an operation ran on a
  chip, inside the window, averaged over the chips;
- ``window_s``: the length of the ``window`` span;
- ``idle_gaps``: the window's idle time on the device, summed by the host
  span that covers it (``query.<template>``, or ``client`` between
  queries);
- ``device_ops``: device time summed by operation, named
  ``<program>/<op> <result type>``.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List

WINDOW = "window"
CLIENT = "client"
QUERY = "query."
# the device line that holds one event per executed operation; programs
# (``XLA Modules``) are the fallback where a backend has no op line
OP_LINES = ("XLA Ops", "XLA Modules")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no event per Python call
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def _is_span(name: str) -> bool:
    return name in (WINDOW, CLIENT) or name.startswith(QUERY)


def read_trace(log_dir: str) -> dict:
    """{"device": {chip: [[op, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, List[list]] = {}
    spans: List[list] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            if line is None:
                continue
            modules = sorted((float(e.start_ns), _module_name(e.name))
                             for e in lines[OP_LINES[1]].events) \
                if OP_LINES[1] in lines else []
            starts = [m[0] for m in modules]
            ops = []
            for e in line.events:
                i = bisect.bisect_right(starts, float(e.start_ns)) - 1
                mod = modules[i][1] if i >= 0 else "?"
                ops.append([f"{mod}/{_op_name(e.name)}", float(e.start_ns),
                            float(e.duration_ns)])
            device[plane.name[len("/device:"):]] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _is_span(e.name):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"device": device, "spans": spans}


def _module_name(text: str) -> str:
    """``jit_fused(1676...)`` -> ``jit_fused``."""
    return text.split("(", 1)[0]


def _op_name(text: str) -> str:
    """An HLO op's event text -> ``<op> <result type>``:
    ``%fusion.2 = s32[999424]{0:T(1024)} fusion(...)`` ->
    ``fusion.2 s32[999424]``; a tuple result reads ``tuple``."""
    head, _, rest = text.partition(" = ")
    op = head.lstrip("%")
    if not rest:
        return op
    result = "tuple" if rest.startswith("(") else rest.split("{", 1)[0] \
        .split(" ", 1)[0]
    return f"{op} {result}"


def _union(intervals: List[tuple]) -> List[tuple]:
    out: List[list] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


class Reduced:
    """What ``reduce`` finds in a trace (times in seconds)."""

    def __init__(self, busy_s, window_s, idle_gaps, device_ops):
        self.busy_s = busy_s
        self.window_s = window_s
        self.idle_gaps = idle_gaps
        self.device_ops = device_ops


def reduce(trace: dict, top: int = 10) -> Reduced:
    win = [s for s in trace["spans"] if s[0] == WINDOW]
    if not win:
        raise ValueError("the trace holds no window span")
    w0 = win[0][1]
    w1 = w0 + win[0][2]
    labelled = sorted((s[1], s[1] + s[2], s[0]) for s in trace["spans"]
                      if s[0] != WINDOW)
    starts = [s[0] for s in labelled]

    def attribute(t0: float, t1: float, share: float, into: dict) -> None:
        """Split the idle interval [t0, t1] over the host spans that
        cover it; what no span covers is the client's."""
        i = max(bisect.bisect_right(starts, t0) - 1, 0)
        covered = 0.0
        while i < len(labelled) and labelled[i][0] < t1:
            a, b, name = labelled[i]
            o = min(b, t1) - max(a, t0)
            if o > 0:
                into[name] = into.get(name, 0.0) + o * share
                covered += o
            i += 1
        if t1 - t0 - covered > 0:
            into[CLIENT] = into.get(CLIENT, 0.0) + (t1 - t0 - covered) * share

    busy_total = 0.0
    gaps: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    chips = trace["device"]
    if not chips:
        raise ValueError("the trace holds no TPU operations")
    for events in chips.values():
        clipped = []
        for name, s, d in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        busy = _union(clipped)
        busy_total += sum(b - a for a, b in busy)
        t = w0
        for a, b in busy + [(w1, w1)]:
            if a > t:
                attribute(t, a, 1e-9 / len(chips), gaps)
            t = max(t, b)
    n = len(chips)

    def topn(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:top]]
    return Reduced(busy_total * 1e-9 / n, (w1 - w0) * 1e-9, topn(gaps),
                   topn({k: v / n for k, v in ops.items()}))
