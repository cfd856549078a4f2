"""Nestable span recorder emitting Chrome-trace / Perfetto JSON, and the
same spans as annotations on the ``jax.profiler`` timeline.

Records the serving pipeline's stage structure — pack -> host-to-device ->
megakernel dispatch -> device compute -> interaction head — as *complete*
("ph": "X") events that ``chrome://tracing`` and https://ui.perfetto.dev load
directly.  Nesting needs no explicit parent links: the Trace Event Format
reconstructs the flame from [ts, ts+dur) containment per (pid, tid), and the
recorder keeps a thread-local stack only so each event can also carry its
depth in ``args`` (handy for tests and offline tools).

Every span also enters a ``jax.profiler.TraceAnnotation`` of its name, its
args as the annotation's metadata; a ``batch`` span that carries a batch
number enters a ``jax.profiler.StepTraceAnnotation`` instead, so profile
viewers mark step boundaries.  Outside a profiler capture an annotation
costs a flag check; inside one, the spans sit on the host rows of the
profile, on the profiler's clock, beside the device ops.

Device work enqueued by jax is asynchronous, so a span around a dispatch call
measures *enqueue* cost unless the caller fences.  ``serve_rec`` offers
both views: ``serve_rec --trace-out`` fences each stage with
``jax.block_until_ready`` and writes this recorder's host-only Chrome JSON,
trading pipeline overlap for honest per-stage durations; ``serve_rec
--profile-dir`` runs unfenced under ``jax.profiler.trace`` and writes the
profile, where device time is read from the device's own rows.

Timestamps in the Chrome JSON are microseconds from the tracer's
construction (``perf_counter`` based), matching the format's expectation of
monotonic us.
"""

from __future__ import annotations

import json
import os
import threading
import time

from jax import profiler as jax_profiler

# A span of this name that carries a ``batch`` arg marks one step.
STEP_SPAN = "batch"


def _annotation(name: str, args: dict | None):
    """The profiler annotation of a span: a step marker for a ``batch``
    span with a batch number, else a ``TraceAnnotation``; ``args`` become
    its metadata."""
    args = args or {}
    if name == STEP_SPAN and "batch" in args:
        return jax_profiler.StepTraceAnnotation(
            name, step_num=args["batch"], **args)
    return jax_profiler.TraceAnnotation(name, **args)


class _Span:
    """Context manager for one complete event (allocated only when enabled)."""

    __slots__ = ("tracer", "name", "cat", "args", "t0", "note")

    def __init__(self, tracer: "Tracer", name: str, cat: str, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args

    def __enter__(self) -> "_Span":
        self.note = _annotation(self.name, self.args)
        self.note.__enter__()
        self.t0 = time.perf_counter()
        self.tracer._stack().append(self)
        return self

    def set(self, **args) -> None:
        """Add ``args`` to the span, known only once its work has run; they
        reach both its event and its annotation."""
        self.args = {**(self.args or {}), **args}
        self.note.set_metadata(**args)

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        stack = self.tracer._stack()
        depth = len(stack) - 1
        if stack and stack[-1] is self:
            stack.pop()
        tr = self.tracer
        args = {"depth": depth}
        if self.args:
            args.update(self.args)
        tr.events.append({
            "name": self.name,
            "cat": self.cat,
            "ph": "X",
            "ts": (self.t0 - tr.origin) * 1e6,
            "dur": (t1 - self.t0) * 1e6,
            "pid": tr.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args,
        })
        self.note.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Append-only event buffer + span factory for one process."""

    def __init__(self):
        self.pid = os.getpid()
        self.origin = time.perf_counter()
        self.events: list[dict] = []
        self._local = threading.local()

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def reset(self) -> None:
        self.origin = time.perf_counter()
        self.events.clear()

    def span(self, name: str, cat: str = "serve", args: dict | None = None
             ) -> _Span:
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "serve",
                args: dict | None = None) -> None:
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": (time.perf_counter() - self.origin) * 1e6,
            "pid": self.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": args or {},
        })

    def counter(self, name: str, values: dict) -> None:
        """Chrome counter-track sample ("ph": "C") — e.g. cache hit rate."""
        self.events.append({
            "name": name, "cat": "metrics", "ph": "C",
            "ts": (time.perf_counter() - self.origin) * 1e6,
            "pid": self.pid,
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "args": {k: float(v) for k, v in values.items()},
        })

    def to_chrome(self, *, metadata: dict | None = None) -> dict:
        """The JSON object ``chrome://tracing`` / Perfetto load."""
        events = [
            {
                "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
                "args": {"name": "repro.serve"},
            },
        ] + self.events
        out = {"traceEvents": events, "displayTimeUnit": "ms"}
        if metadata:
            out["otherData"] = metadata
        return out

    def write(self, path: str, *, metadata: dict | None = None) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(metadata=metadata), f, indent=1)
