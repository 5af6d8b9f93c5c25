#!/usr/bin/env python3
"""The profiler trace read down to the program's own layers.

``tracing`` reads the benchmark's spans (``window``, ``query.<template>``,
``client``) and the device's ops.  This module reads the same trace with
the program's host spans too (``repro.obs.SPANS``, the ``exec.*`` names)
and, for every device op, the ``jax.named_scope`` it was traced under
(``repro.obs.SCOPES``).  A TPU op event names its HLO instruction but
carries no op-name path, so the scope comes from the op's ``op_name``
metadata in XLA's text dump of the optimized program (``op_scopes``).
``reduce`` gives what ``tracing.reduce`` gives, plus:

- ``idle_gaps``: each idle interval put down to the innermost host span
  that covers it (on a trace with no program spans, exactly
  ``tracing.reduce``'s result);
- ``span_self_s``: seconds of the window per host span name, less the
  part its nested spans cover;
- ``scope_busy_s``: device seconds per scope, the union of its ops'
  intervals, averaged over chips; ops under no scope count under ``""``;
- ``programs``: device programs (``XLA Modules`` events) started in the
  window, averaged over chips.

The harness deletes its trace before the metrics are read, so the
per-layer metrics of the program's spans read what the program kept in
memory of them while the trace ran (``recorded_self_s``).

Run as a script, it runs one cell through ``run.py`` with the trace on,
XLA's dump of every program it compiles (the persistent compile cache
off, so that every program compiles) and the trace read a second time
here before the harness deletes it, and prints, after ``run.py``'s own
result line, one JSON line with the layers (``--out`` writes it to a
file as well):

    python3 chipbench/spantrace.py --workload <cell> --seed <n> \
        --seconds <s> [--out <file>]
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import pathlib
import re
import shutil
import sys
import tempfile
from typing import Dict, List, Optional

if not __package__:               # run as a script: the checkout's root
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
from chipbench import tracing  # noqa: E402


def _program_names():
    """(span names, scope names) the program registers; none where the
    program has no registry."""
    try:
        from repro import obs
    except ImportError:
        return set(), set()
    return set(obs.SPANS), set(obs.SCOPES)


def _scope_of(op_name: str, scopes) -> str:
    """The innermost registered scope on an op-name path:
    ``jit(fused)/jit(join_lookup)/join_probe/while`` -> ``join_probe``."""
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return ""


_INSTR = re.compile(r"^\s*(?:ROOT )?(%?[\w.-]+ = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_scopes(dump_dir: str) -> Dict[str, str]:
    """``<program>/<op> <result type>`` (the key of ``device_ops``) -> the
    registered scope of the instruction, from the ``*after_optimizations
    .txt`` files of XLA's text dump.  Where programs of one name disagree
    on an op, it maps to ``?``."""
    _, scopes = _program_names()
    found: Dict[str, set] = {}
    for path in glob.glob(os.path.join(dump_dir,
                                       "*after_optimizations.txt")):
        with open(path) as f:
            # HloModule jit_fused, is_scheduled=true, ...
            module = f.readline().split(" ", 1)[-1].split(",", 1)[0]
            for line in f:
                m = _INSTR.match(line)
                if m is None:
                    continue
                text = m.group(1)
                meta = _OP_NAME.search(text)
                key = f"{module}/{tracing._op_name(text)}"
                found.setdefault(key, set()).add(
                    _scope_of(meta.group(1), scopes) if meta else "")
    return {k: v.pop() if len(v) == 1 else "?" for k, v in found.items()}


def read_trace(log_dir: str, scopes: Optional[Dict[str, str]] = None
               ) -> dict:
    """{"device": {chip: [[op, start_ns, dur_ns, scope], ...]},
    "programs": {chip: [start_ns, ...]},
    "spans": [[name, start_ns, dur_ns], ...]} from the newest trace;
    ``scopes`` is ``op_scopes``' map (no scope where it is None)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    span_names, _ = _program_names()
    scopes = scopes or {}
    pd = ProfileData.from_file(paths[-1])
    device: Dict[str, List[list]] = {}
    programs: Dict[str, List[float]] = {}
    spans: List[list] = []
    mod_line = tracing.OP_LINES[1]
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in tracing.OP_LINES if n in lines),
                        None)
            if line is None:
                continue
            modules = sorted((float(e.start_ns),
                              tracing._module_name(e.name))
                             for e in lines[mod_line].events) \
                if mod_line in lines else []
            starts = [m[0] for m in modules]
            ops = []
            for e in line.events:
                i = bisect.bisect_right(starts, float(e.start_ns)) - 1
                mod = modules[i][1] if i >= 0 else "?"
                op = f"{mod}/{tracing._op_name(e.name)}"
                ops.append([op, float(e.start_ns), float(e.duration_ns),
                            scopes.get(op, "")])
            chip = plane.name[len("/device:"):]
            device[chip] = ops
            programs[chip] = starts
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if tracing._is_span(e.name) or e.name in span_names:
                        spans.append([e.name, float(e.start_ns),
                                      float(e.duration_ns)])
    return {"device": device, "programs": programs, "spans": spans}


def innermost(spans: List[list]) -> List[list]:
    """Host spans cut into pieces that do not overlap, ``[name, start,
    dur]``: at each instant the covering span that started last (the
    shortest, where two start together), which is the innermost where
    spans nest.  A span that nothing interrupts comes out as it went in."""
    ev = []
    for i, (_, s, d) in enumerate(spans):
        ev.append((s, 1, i))
        ev.append((s + d, 0, i))
    ev.sort()
    active: List[tuple] = []
    ended = set()
    pieces: List[list] = []            # [span index, start, end]
    t = None
    for x, starts, i in ev:
        while active and active[0][2] in ended:
            heapq.heappop(active)
        if active and x > t:
            j = active[0][2]
            if pieces and pieces[-1][0] == j and pieces[-1][2] == t:
                pieces[-1][2] = x
            else:
                pieces.append([j, t, x])
        t = x
        if starts:
            heapq.heappush(active, (-spans[i][1], spans[i][2], i))
        else:
            ended.add(i)
    out = []
    for j, a, b in pieces:
        name, s, d = spans[j]
        out.append([name, a, d if (a == s and b == s + d) else b - a])
    return out


class Reduced(tracing.Reduced):
    """``tracing.Reduced`` with the program's layers (times in seconds);
    ``idle_all`` is ``idle_gaps`` before the cut to the top entries."""

    def __init__(self, base, idle_all, span_self_s, scope_busy_s,
                 programs):
        super().__init__(base.busy_s, base.window_s, base.idle_gaps,
                         base.device_ops)
        self.idle_all = idle_all
        self.span_self_s = span_self_s
        self.scope_busy_s = scope_busy_s
        self.programs = programs


def reduce(trace: dict, top: int = 10) -> Reduced:
    win = [s for s in trace["spans"] if s[0] == tracing.WINDOW]
    pieces = innermost([s for s in trace["spans"]
                        if s[0] != tracing.WINDOW])
    chips = trace["device"]
    base = tracing.reduce({"spans": win[:1] + pieces,
                           "device": {c: [e[:3] for e in ev]
                                      for c, ev in chips.items()}},
                          top=1 << 30)
    idle_all = base.idle_gaps
    base.idle_gaps = idle_all[:top]
    base.device_ops = base.device_ops[:top]
    w0 = win[0][1]
    w1 = w0 + win[0][2]

    span_self: Dict[str, float] = {}
    for name, s, d in pieces:
        o = min(s + d, w1) - max(s, w0)
        if o > 0:
            span_self[name] = span_self.get(name, 0.0) + o * 1e-9
    scope_busy: Dict[str, float] = {}
    for events in chips.values():
        by_scope: Dict[str, list] = {}
        for _, s, d, scope in events:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                by_scope.setdefault(scope, []).append((a, b))
        for scope, iv in by_scope.items():
            busy = sum(b - a for a, b in tracing._union(iv))
            scope_busy[scope] = scope_busy.get(scope, 0.0) \
                + busy * 1e-9 / len(chips)
    starts = trace.get("programs", {})
    programs = sum(sum(1 for t in st if w0 <= t < w1)
                   for st in starts.values()) / max(len(starts), 1)
    return Reduced(base, idle_all, span_self, scope_busy, programs)


def recorded_self_s(ctx, name: str) -> Optional[float]:
    """Self seconds of the program's span ``name`` inside the window of
    ``ctx`` (a ``harness.Context``), from what the program kept in memory
    while the trace ran; None where the program keeps no such record or
    no such span closed in the window."""
    try:
        from repro import obs
    except ImportError:
        return None
    if not ctx.queries:
        return None
    t0 = min(q["end"] - q["latency_s"] for q in ctx.queries)
    t1 = max(q["end"] for q in ctx.queries)
    got = [r[3] for r in obs.recorded()
           if r[0] == name and r[1] >= t0 and r[2] <= t1]
    return sum(got) if got else None


def spans_per_query(spans: List[list]) -> Dict[str, float]:
    """Mean number of program spans inside each ``query.<template>``
    span, by template."""
    span_names, _ = _program_names()
    queries = sorted(s for s in spans if s[0].startswith(tracing.QUERY))
    inner = sorted(s[1] for s in spans if s[0] in span_names)
    counts: Dict[str, list] = {}
    for name, s, d in queries:
        n = bisect.bisect_left(inner, s + d) - bisect.bisect_left(inner, s)
        counts.setdefault(name[len(tracing.QUERY):], []).append(n)
    return {t: sum(c) / len(c) for t, c in counts.items()}


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out")
    args, rest = ap.parse_known_args(argv)
    import jax
    from chipbench import run

    dump = tempfile.mkdtemp(prefix="spantrace_hlo_")
    os.environ["XLA_FLAGS"] = " ".join((
        os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={dump}",
        "--xla_dump_hlo_as_text")).strip()
    jax.config.update("jax_enable_compilation_cache", False)
    kept = {}
    read = tracing.read_trace

    def read_both(log_dir):
        kept["scopes"] = op_scopes(dump)
        kept["trace"] = read_trace(log_dir, kept["scopes"])
        return read(log_dir)

    tracing.read_trace = read_both
    try:
        rc = run.main(rest + ["--trace", "1"])
    finally:
        shutil.rmtree(dump, ignore_errors=True)
    trace = kept["trace"]
    r = reduce(trace)
    from repro.core.database import PROCESS_LOAD
    from repro import obs
    recorded: Dict[str, float] = {}
    for name, _, _, self_s in obs.recorded():
        recorded[name] = recorded.get(name, 0.0) + self_s
    line = {"busy_s": r.busy_s, "window_s": r.window_s,
            "idle_gaps": r.idle_all, "device_ops": r.device_ops,
            "span_self_s": r.span_self_s, "scope_busy_s": r.scope_busy_s,
            "op_scopes": {op: kept["scopes"].get(op, "")
                          for op, _ in r.device_ops},
            "programs": r.programs,
            "spans_per_query": spans_per_query(trace["spans"]),
            "recorded_self_s": recorded,
            "load": {k: getattr(PROCESS_LOAD, k) for k in (
                "rows", "load_s", "segment_s", "sort_s", "sma_s",
                "encode_s")}}
    text = json.dumps(line)
    print(text, flush=True)
    if args.out:
        pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(args.out).write_text(text + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
