"""Host spans of the query path, on the profiler's clock.

``span(name, **attrs)`` wraps ``jax.profiler.TraceAnnotation``: inside a
profiler trace the span lands on the host plane, on the same clock as the
device's ops; outside one it costs one enabled check.  While a trace runs,
every span that closes is also kept in memory (``recorded()``), with its
self time -- the part of it that no nested span covers -- so a reader in
the same process needs no trace file.  A span covers one layer's work,
never one pass of a loop over blocks or containers.

``SPANS`` names every span with its layer, ``SCOPES`` every
``jax.named_scope`` of the device programs; the names follow PERF.md's
list of layers.
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Deque, List, Tuple

from jax.profiler import TraceAnnotation

SPANS = {
    "exec.query": "query",
    "exec.plan": "front end",
    "exec.scan": "scan assembly",
    "exec.dispatch": "scan assembly",
    "exec.cd_mask": "code-domain scan",
    "exec.cd_sync": "code-domain scan",
    "exec.cd_gather": "code-domain scan",
    "exec.join_build": "join",
    "exec.rle": "group-by",
    "exec.result_sync": "finalize",
    "exec.finalize": "finalize",
}

SCOPES = {
    "predicate": "scan assembly",
    "join_probe": "join",
    "groupby": "group-by",
    "cd_decode": "code-domain scan",
    "cd_gather": "code-domain scan",
}

# (name, start, end, self seconds) on ``time.perf_counter``'s clock, for
# the spans closed while a trace ran; the oldest go first
_RECORD: Deque[Tuple[str, float, float, float]] = \
    collections.deque(maxlen=1 << 16)
_OPEN = threading.local()
_OFF = contextlib.nullcontext()


class _Span:
    __slots__ = ("name", "ann", "t0", "inner")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        stack = _OPEN.__dict__.setdefault("stack", [])
        stack.append(self)
        self.inner = 0.0
        self.t0 = time.perf_counter()
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        t1 = time.perf_counter()
        stack = _OPEN.stack
        stack.pop()
        d = t1 - self.t0
        if stack:
            stack[-1].inner += d
        _RECORD.append((self.name, self.t0, t1, d - self.inner))
        return False


def span(name: str, **attrs):
    """A context manager: the host span ``name`` (one of ``SPANS``)."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, attrs)


def recorded() -> List[Tuple[str, float, float, float]]:
    """The spans closed while a trace ran, newest last."""
    return list(_RECORD)
