"""Nested wall-clock spans: per-name totals, an optional JSONL record of
every span, and a profiler annotation of the same name.

A span is one stage of the detection path. The streaming detector opens
one tree per push (``StreamingDetector.push`` / ``StationStream.push``)::

    chunk            root; its id is the ``trace`` of every span below
    ├── ingest       ring framing, block staging (and the dedup below)
    │   └── dedup    the sample-exact duplicate guard over every station
    ├── fused_step   the device step seen from the host
    │   ├── put          host → device of the step's inputs
    │   ├── dispatch     the jitted step call until it returns
    │   ├── wait         block_until_ready on the outputs to pull
    │   └── pull         the one device_get
    ├── host_tail    pair consumption and the rolling filter
    │   └── filter_close   one station's filter-window close
    │       ├── occurrence     the §6.5 filter, per component
    │       ├── merge          the channel sum and threshold
    │       └── cluster        diagonal clustering
    └── detections   poll_detections (StreamingDetector only)

Spans wrap loops over stations, never one station, so their cost does
not grow with the network; a ``filter_close`` tree is one station's,
once per filter window (hours of stream), not once per push. Entering
and leaving a span is one pair of reads of ``clock``
(``time.perf_counter`` unless injected), a dict update and a
``jax.profiler.TraceAnnotation`` enter/exit, which is a cheap call
while no profiler runs; while one does, every span is a host
event of its name in the trace. ``span`` returns the :class:`Span`, whose
``dur_s`` the caller reads after exit and whose ``set`` attaches counts
(bytes put or pulled, pairs consumed, fingerprints flagged).

Per-name totals (``totals``: name → [count, total_s]) accumulate
whatever the sink; ``StageTimes`` in ``core.detect`` and the telemetry
snapshot read them. With ``jsonl_path`` every finished span is kept in
memory and written as one JSON line on ``flush()``/``close()``, or when
``buffer_spans`` are held as the next root span opens, never inside a
span::

    {"name": "pull", "id": 7, "parent": 3, "trace": 1,
     "start_ns": 1754660000100200300, "end_ns": 1754660000100400300,
     "ts": 1754660000.1004003, "path": "chunk/fused_step/pull",
     "depth": 2, "dur_s": 0.0002, "station": "pool", "bytes": 196608}

``start_ns``/``end_ns`` are ``CLOCK_REALTIME`` nanoseconds
(``time.time_ns``), the clock a profile's ``Task Environment`` plane
anchors with ``profile_start_time``: a span's record lies inside its
annotation's event once that is added to the event's start. A span
reads that clock only while the sink is on (a second pair of reads);
``dur_s`` comes from ``clock``. ``ts`` is ``end_ns`` in seconds.

``profile()`` is the optional ``jax.profiler`` hook: when the tracer was
built with ``profile_dir`` it brackets the wrapped region with an XLA
trace dump (viewable in TensorBoard/Perfetto); otherwise it is a no-op
context.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Callable, IO

from jax.profiler import TraceAnnotation

BUFFER_SPANS = 4096


class Span:
    """One timed stage; a context manager that ``SpanTracer.span`` makes."""

    __slots__ = ("tracer", "name", "attrs", "id", "parent", "trace",
                 "depth", "path", "t0", "t1", "start_ns", "end_ns", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str, attrs: dict):
        self.tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "Span":
        tr = self.tracer
        stack = tr._stack
        if not stack and len(tr._buf) >= tr.buffer_spans:
            tr.flush()              # between trees, outside every span
        # the annotation brackets the bookkeeping too, so the clock reads
        # fall strictly inside the profiler's event of this span
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        tr._last_id += 1
        self.id = tr._last_id
        self.depth = len(stack)
        if stack:
            up = stack[-1]
            self.parent, self.trace = up.id, up.trace
        else:
            self.parent, self.trace = None, self.id
        stack.append(self)
        if tr.jsonl_path is not None:
            self.path = (f"{stack[-2].path}/{self.name}" if self.depth
                         else self.name)
            self.start_ns = time.time_ns()
        self.t0 = tr.clock()
        return self

    def __exit__(self, *exc) -> None:
        tr = self.tracer
        self.t1 = tr.clock()
        if tr.jsonl_path is not None:
            self.end_ns = time.time_ns()
            tr._buf.append(self)
        tr._stack.pop()
        tot = tr.totals.get(self.name)
        if tot is None:
            tot = tr.totals[self.name] = [0, 0.0]
        tot[0] += 1
        tot[1] += self.t1 - self.t0
        self._ann.__exit__(*exc)
        self._ann = None

    @property
    def dur_s(self) -> float:
        """Seconds between entry and exit (read after exit)."""
        return self.t1 - self.t0

    def set(self, **attrs) -> None:
        """Attach counts to the span's record."""
        self.attrs.update(attrs)


class SpanTracer:
    buffer_spans = BUFFER_SPANS     # records held before a root writes them

    def __init__(self, jsonl_path: str | None = None,
                 clock: Callable[[], float] = time.perf_counter,
                 profile_dir: str | None = None):
        self.clock = clock
        self.jsonl_path = jsonl_path
        self.profile_dir = profile_dir
        self._fh: IO | None = None
        self._stack: list[Span] = []
        self._buf: list[Span] = []
        self._last_id = 0
        # name -> [count, total_s]; insertion-ordered = first-entered order
        self.totals: dict[str, list] = {}

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def total_s(self, name: str) -> float:
        return self.totals.get(name, (0, 0.0))[1]

    def summary(self) -> dict:
        return {name: {"count": c, "total_s": t}
                for name, (c, t) in self.totals.items()}

    @contextlib.contextmanager
    def profile(self):
        """Bracket a region with a ``jax.profiler`` trace dump (no-op
        unless the tracer was given a ``profile_dir``)."""
        if self.profile_dir is None:
            yield
            return
        import jax
        with jax.profiler.trace(self.profile_dir):
            yield

    def flush(self) -> None:
        """Write out the buffered spans as JSON lines."""
        if not self._buf:
            return
        lines = []
        for sp in self._buf:
            rec = {"name": sp.name, "id": sp.id, "parent": sp.parent,
                   "trace": sp.trace, "start_ns": sp.start_ns,
                   "end_ns": sp.end_ns, "ts": sp.end_ns * 1e-9,
                   "path": sp.path, "depth": sp.depth,
                   "dur_s": sp.t1 - sp.t0}
            rec.update(sp.attrs)
            lines.append(json.dumps(rec))
        self._buf.clear()
        if self._fh is None:
            self._fh = open(self.jsonl_path, "a")
        self._fh.write("\n".join(lines) + "\n")
        self._fh.flush()

    def close(self):
        self.flush()
        if self._fh is not None:
            self._fh.close()
            self._fh = None
