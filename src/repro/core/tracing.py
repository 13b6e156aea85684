"""Extrae/Paraver-style execution tracing (paper §3.3.4, Fig. 10).

The tracer records one event per task attempt (worker, node, task name,
start/end).  From a trace we derive the quantities the paper reads off
Paraver timelines: per-worker utilization, parallel efficiency, and an
ASCII Gantt rendering for quick terminal inspection.  Two file exports: a
minimal ``.prv``-like format familiar to Paraver users, and the Chrome
trace-event JSON (``to_chrome_trace``) that opens directly in Perfetto /
``about:tracing``.

:class:`TaskStream` is the live-telemetry counterpart (DESIGN.md §17): a
*bounded* ring of task-lifecycle events (submit → dispatch → done/fail)
that the dashboard polls incrementally by sequence number, while the
tracer above keeps the unbounded post-mortem record.

:class:`span` and :func:`count` are the program's own spans and counters
(``train.step``, ``data.wait``, ``task.<name>``, ``jit.compiles``...). They
go into one process-wide ring, :data:`SPANS`, and, once the process has
imported JAX, also into the profiler's trace as
``jax.profiler.TraceAnnotation`` spans, on the device trace's clock. This
module imports no JAX itself.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional


@dataclass
class TraceEvent:
    kind: str            # "task": one task attempt
    name: str
    worker: int
    node: int
    t0: float
    t1: float
    task_id: int = -1
    meta: dict = field(default_factory=dict)

    @property
    def dt(self) -> float:
        return self.t1 - self.t0


class Tracer:
    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._events: List[TraceEvent] = []
        self._lock = threading.Lock()
        self.t_start = time.perf_counter()
        self.t_stop: Optional[float] = None

    def record(self, ev: TraceEvent) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._events.append(ev)

    def stop(self) -> None:
        self.t_stop = time.perf_counter()

    def events(self, kind: Optional[str] = None) -> List[TraceEvent]:
        with self._lock:
            evs = list(self._events)
        if kind is not None:
            evs = [e for e in evs if e.kind == kind]
        return evs

    # ------------------------------------------------------------- analysis
    def wallclock(self) -> float:
        end = self.t_stop if self.t_stop is not None else time.perf_counter()
        return end - self.t_start

    def busy_per_worker(self) -> Dict[int, float]:
        busy: Dict[int, float] = {}
        for e in self.events("task"):
            busy[e.worker] = busy.get(e.worker, 0.0) + e.dt
        return busy

    def utilization(self, n_workers: int) -> float:
        wall = self.wallclock()
        if wall <= 0 or n_workers <= 0:
            return 0.0
        return sum(self.busy_per_worker().values()) / (wall * n_workers)

    def task_duration_stats(self) -> Dict[str, dict]:
        per: Dict[str, List[float]] = {}
        for e in self.events("task"):
            per.setdefault(e.name, []).append(e.dt)
        out = {}
        for name, ds in per.items():
            ds.sort()
            out[name] = {
                "count": len(ds),
                "total": sum(ds),
                "mean": sum(ds) / len(ds),
                "p50": ds[len(ds) // 2],
                "max": ds[-1],
            }
        return out

    # -------------------------------------------------------------- exports
    def to_prv(self) -> str:
        """Tiny Paraver-like export: header + one state record per task.

        Events are clamped and ordered defensively: completion threads
        record concurrently, so events may arrive out of submission order
        and a no-op task can carry ``t1 == t0`` (or, on clock hiccups,
        ``t1 < t0``) — Paraver expects ordered records with non-negative
        spans."""
        evs = sorted(self.events("task"), key=lambda e: (e.t0, e.t1))
        dur_us = max(0, int(self.wallclock() * 1e6))
        workers = sorted({e.worker for e in evs}) or [0]
        lines = [f"#Paraver (rjax):{dur_us}_us:1(1):{len(workers)}"]
        for e in evs:
            t0 = max(0, int((e.t0 - self.t_start) * 1e6))
            t1 = max(t0, int((e.t1 - self.t_start) * 1e6))
            # state record: 1:cpu:appl:task:thread:begin:end:state
            lines.append(f"1:{e.worker + 1}:1:1:1:{t0}:{t1}:{e.name}")
        return "\n".join(lines)

    def to_chrome_trace(self) -> str:
        """Chrome trace-event JSON (the ``traceEvents`` format Perfetto
        and ``about:tracing`` open directly): one complete ("X") event
        per recorded trace event, ``pid`` = locality domain / node,
        ``tid`` = worker, timestamps in µs relative to runtime start.
        Metadata records name the node/worker rows."""
        evs = self.events()
        records: List[dict] = []
        for node in sorted({e.node for e in evs}):
            records.append({"name": "process_name", "ph": "M",
                            "pid": int(node), "tid": 0,
                            "args": {"name": f"node {node}"}})
        for node, worker in sorted({(e.node, e.worker) for e in evs}):
            records.append({"name": "thread_name", "ph": "M",
                            "pid": int(node), "tid": int(worker),
                            "args": {"name": f"worker {worker}"}})
        for e in sorted(evs, key=lambda e: (e.t0, e.t1)):
            args = {"task_id": e.task_id}
            for k, v in e.meta.items():
                if isinstance(v, (bool, int, float, str)) or v is None:
                    args[k] = v
            records.append({
                "name": e.name, "cat": e.kind, "ph": "X",
                "ts": round(max(0.0, (e.t0 - self.t_start) * 1e6), 3),
                "dur": round(max(0.0, (e.t1 - e.t0) * 1e6), 3),
                "pid": int(e.node), "tid": int(e.worker),
                "args": args,
            })
        return json.dumps({"traceEvents": records,
                           "displayTimeUnit": "ms"}, indent=1)

    def ascii_gantt(self, width: int = 100) -> str:
        """Terminal Gantt chart — one row per worker (paper Fig. 10 analogue)."""
        evs = self.events("task")
        if not evs:
            return "(empty trace)"
        width = max(2, int(width))
        t0 = min(e.t0 for e in evs)
        t1 = max(max(e.t1, e.t0) for e in evs)
        span = max(t1 - t0, 1e-9)
        rows: Dict[int, List[str]] = {}
        names = sorted({e.name for e in evs})
        glyph = {n: chr(ord("A") + (i % 26)) for i, n in enumerate(names)}
        for e in evs:
            row = rows.setdefault(e.worker, [" "] * width)
            # clamp into [0, width): zero-duration events still paint one
            # cell, events with a skewed/negative span never index out
            a = min(width - 1, max(0, int((e.t0 - t0) / span * (width - 1))))
            b = min(width, max(a + 1, int((e.t1 - t0) / span * (width - 1)) + 1))
            for i in range(a, b):
                row[i] = glyph[e.name]
        legend = "  ".join(f"{g}={n}" for n, g in glyph.items())
        out = [f"trace span: {span*1e3:.2f} ms   [{legend}]"]
        for w in sorted(rows):
            out.append(f"w{w:03d} |{''.join(rows[w])}|")
        return "\n".join(out)


# ------------------------------------------------------- live task stream
# bounded lifecycle ring (DESIGN.md §17); 0/negative = default
RING_CAPACITY = int(os.environ.get("RJAX_TELEMETRY_RING", "0") or 0) or 4096


class TaskStream:
    """Bounded ring buffer of task-lifecycle events (DESIGN.md §17).

    Each event is a plain dict tagged with a monotonically increasing
    ``seq``; the oldest events are evicted once ``capacity`` is reached
    (``dropped`` counts them), so a long-running service holds a sliding
    window instead of growing without bound.  Consumers (the dashboard's
    ``/api/tasks``) poll incrementally with ``since(last_seen_seq)``.
    Appends run on the dispatch/completion hot paths: one short lock hold
    and a deque append, nothing else."""

    def __init__(self, capacity: Optional[int] = None):
        self.capacity = int(capacity) if capacity else RING_CAPACITY
        self.capacity = max(1, self.capacity)
        self._buf: collections.deque = collections.deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._seq = 0
        self._dropped = 0

    def append(self, kind: str, **fields) -> int:
        with self._lock:
            self._seq += 1
            if len(self._buf) == self.capacity:
                self._dropped += 1
            fields["seq"] = self._seq
            fields["kind"] = kind
            self._buf.append(fields)
            return self._seq

    def extend(self, kind: str, rows) -> None:
        """Batch append (fan-out submission): one lock hold for the lot.
        ``rows`` is an iterable of field dicts."""
        with self._lock:
            for fields in rows:
                self._seq += 1
                if len(self._buf) == self.capacity:
                    self._dropped += 1
                fields["seq"] = self._seq
                fields["kind"] = kind
                self._buf.append(fields)

    def since(self, seq: int = 0, limit: Optional[int] = None) -> List[dict]:
        """Events with ``seq`` strictly greater than the given watermark,
        oldest first (capped at ``limit`` newest when given)."""
        with self._lock:
            evs = [dict(e) for e in self._buf if e["seq"] > seq]
        if limit is not None and len(evs) > limit:
            evs = evs[-int(limit):]
        return evs

    @property
    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


# ------------------------------------------------- program spans and counters
# A 51 s window of 0.44 s steps is about 1,200 records (some ten spans a
# step); set-up adds a few hundred compile counts. The ring holds several
# times that, so a window's spans are never evicted before they are read.
SPAN_RING = 8192
SPANS = TaskStream(SPAN_RING)

_local = threading.local()
_span_ids = itertools.count(1)
_run_ids = itertools.count(1)

# JAX's compile phases as ``jax.monitoring`` reports them. The backend
# compile's duration also covers a load from the persistent compilation
# cache (``/jax/compilation_cache/cache_retrieval_time_sec``), which is
# therefore not added a second time.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_PHASES = (_TRACE, _LOWER, _BACKEND)

_annotations = None          # (TraceAnnotation, StepTraceAnnotation) once JAX is in
_annotations_lock = threading.Lock()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _jax_annotations():
    """JAX's annotation classes once the process has imported JAX, else None.
    The first time they are found, the compile listeners are registered."""
    global _annotations
    if _annotations is None and "jax.monitoring" in sys.modules \
            and "jax.profiler" in sys.modules:
        with _annotations_lock:
            if _annotations is None:
                prof, mon = sys.modules["jax.profiler"], sys.modules["jax.monitoring"]
                try:
                    found = (prof.TraceAnnotation, prof.StepTraceAnnotation)
                    mon.register_scalar_listener(_compile_phase_begins)
                    mon.register_event_duration_secs_listener(_compile_phase_ends)
                except AttributeError:      # JAX is still being imported
                    return None
                _annotations = found
    return _annotations


def new_run() -> int:
    """A fresh run id, for the step spans of one training call."""
    return next(_run_ids)


class span:
    """A named span of the program, as a context manager::

        with span("train.step", step=k, run=r):
            with span("train.sync"):      # inherits step k and run r
                ...

    On exit, by an exception too (which propagates), it appends to
    :data:`SPANS` one record: ``name``, ``t0``, ``t1`` (``perf_counter``),
    ``thread``, its ``id`` and ``parent`` (the enclosing span of this
    thread), ``step`` and ``run`` (given, or the enclosing span's), ``args``
    and ``error`` (the exception's type name, or None). Once JAX is
    imported it is also a ``jax.profiler.TraceAnnotation`` (a
    ``StepTraceAnnotation`` where ``step`` is given), which reaches a trace
    only while the profiler runs."""

    __slots__ = ("name", "step", "run", "args", "id", "parent", "t0", "t1", "_ann")

    def __init__(self, name: str, step: Optional[int] = None,
                 run: Optional[int] = None, **args):
        self.name, self.step, self.run, self.args = name, step, run, args

    def __enter__(self) -> "span":
        stack = _stack()
        outer = stack[-1] if stack else None
        self.id, self.parent = next(_span_ids), outer.id if outer else None
        is_step = self.step is not None
        if not is_step and outer is not None:
            self.step, self.run = outer.step, outer.run
        ann = _jax_annotations()
        self._ann = None
        if ann is not None:
            meta = dict(self.args)
            if self.run is not None:
                meta["run"] = self.run
            if is_step:
                self._ann = ann[1](self.name, step_num=self.step, **meta)
            else:
                if self.step is not None:
                    meta["step"] = self.step
                self._ann = ann[0](self.name, **meta)
            self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        _stack().pop()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        SPANS.append("span", name=self.name, t0=self.t0, t1=self.t1,
                     thread=threading.get_ident(), id=self.id, parent=self.parent,
                     step=self.step, run=self.run, args=self.args,
                     error=exc_type.__name__ if exc_type else None)
        return False


_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name: str, **args):
    """A span on the profiler's clock alone: a ``jax.profiler.TraceAnnotation``
    once JAX is imported, else a context manager that does nothing. Nothing
    goes into :data:`SPANS`; for spans too many for the ring that only a
    trace reads, such as the runtime's task bodies."""
    ann = _jax_annotations()
    return _NO_ANNOTATION if ann is None else ann[0](name, **args)


def count(name: str, value: float = 1, **args) -> None:
    """Append a counter record to :data:`SPANS`, tagged like a span opened
    here would be: ``parent``, ``step`` and ``run`` of this thread's
    innermost span."""
    stack = _stack()
    outer = stack[-1] if stack else None
    SPANS.append("count", name=name, value=value, t=time.perf_counter(),
                 thread=threading.get_ident(),
                 parent=outer.id if outer else None,
                 step=outer.step if outer else None,
                 run=outer.run if outer else None, args=args)


def _compile_phase_begins(event: str, value, **kw) -> None:
    if event in _PHASES:
        _local.compiling = getattr(_local, "compiling", 0) + 1


def _compile_phase_ends(event: str, secs: float, **kw) -> None:
    """``jit.compiles`` counts backend compiles (a cache load included);
    ``jit.compile_s`` sums the outermost compile phases of this thread, as
    tracing a function traces the functions it calls, each timed apart."""
    if event not in _PHASES:
        return
    depth = max(getattr(_local, "compiling", 1) - 1, 0)
    _local.compiling = depth
    fun = kw.get("fun_name")
    if event == _BACKEND:
        count("jit.compiles", fun=fun)
    if depth == 0:
        count("jit.compile_s", secs, fun=fun)
