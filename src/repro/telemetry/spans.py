"""Nestable wall-clock spans with a thread-safe in-process collector
(DESIGN.md §7).

The whole subsystem is OFF by default: `span(...)` returns a shared no-op
context manager until `enable()` installs a collector, so instrumented hot
paths (the survey dispatch loop, the sharded driver) pay a dict lookup and
nothing else — the <2% fig13 overhead budget of ISSUE 10.

Two measurement regimes, both first-class:

  host spans      `with span("survey.dispatch", bucket=key):` around eager
                  host code.  JAX dispatch is ASYNC, so a span that should
                  time device work must sync: `span(..., device_sync=x)`
                  or `sp.sync(result)` registers pytrees on which the span
                  calls `jax.block_until_ready` at exit — otherwise the
                  span times the enqueue, not the compute.
  trace spans     `with annotate("ops.tile_pass", T=4):` inside traced
                  functions.  Under `jit` the body runs once per trace, so
                  the recorded span measures TRACING time (the
                  trace/compile-vs-execute split of ISSUE 10) and the
                  region additionally enters `jax.named_scope`, naming the
                  ops in jaxprs/HLO.  Eagerly it times execution like any
                  span.

With `enable(jax_profiler=True)` (or ``REPRO_TELEMETRY_JAX=1``) every span
also enters `jax.profiler.TraceAnnotation`, so the same names land on the
TensorBoard/Perfetto host timeline when a `jax.profiler.trace` is active,
on the device trace's clock.

Each record names its `parent`, the span open around it on the same
thread, so a layer's self time is its span minus its children.  A span
opened with `count_compiles=True` records as `compiles` the programs JAX
compiled or loaded from its persistent cache while it was open (one
`jax.monitoring` listener per process, counting only while a collector
is installed).

Exporter: `chrome_trace()` emits the Chrome ``chrome://tracing`` /
Perfetto JSON (phase-"X" complete events, microsecond timestamps);
`export(path)` writes it.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class SpanRecord:
    """One completed span (durations in seconds, starts relative to the
    collector's epoch so traces from one process line up).  `parent` is
    the name of the span open around it on the same thread, else None."""

    __slots__ = ("name", "start", "dur", "depth", "tid", "attrs", "parent")

    def __init__(self, name: str, start: float, dur: float, depth: int,
                 tid: int, attrs: Dict[str, Any],
                 parent: Optional[str] = None):
        self.name = name
        self.start = start
        self.dur = dur
        self.depth = depth
        self.tid = tid
        self.attrs = attrs
        self.parent = parent

    def __repr__(self):
        return (f"SpanRecord({self.name!r}, start={self.start:.6f}, "
                f"dur={self.dur:.6f}, depth={self.depth}, "
                f"parent={self.parent!r})")


def _jsonable(v):
    """Attrs must survive json.dump (tuples of ints, numpy scalars...)."""
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    try:
        import numpy as np
        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, np.floating):
            return float(v)
    except ImportError:  # pragma: no cover
        pass
    return repr(v)


class _Span:
    """The live context-manager object `span()` yields while collecting."""

    __slots__ = ("_collector", "name", "attrs", "_sync", "_t0",
                 "_cancelled", "_annos", "_compiles0")

    def __init__(self, collector: "SpanCollector", name: str,
                 device_sync=None, attrs: Optional[dict] = None,
                 count_compiles: bool = False):
        self._collector = collector
        self.name = name
        self.attrs = attrs or {}
        self._sync = [] if device_sync is None else [device_sync]
        self._t0 = None
        self._cancelled = False
        self._annos = None
        self._compiles0 = 0 if count_compiles else None

    def sync(self, value):
        """Register a pytree to `jax.block_until_ready` at span exit (so
        the span times device compute, not async enqueue).  Returns the
        value unchanged for inline use."""
        self._sync.append(value)
        return value

    def set(self, **attrs):
        """Add attributes known only inside the span (counts, sizes)."""
        self.attrs.update(attrs)

    def cancel(self):
        """Drop this span: nothing is recorded at exit."""
        self._cancelled = True

    def __enter__(self):
        c = self._collector
        if c.jax_profiler:
            import jax
            self._annos = jax.profiler.TraceAnnotation(self.name)
            self._annos.__enter__()
        c._enter(self.name)
        if self._compiles0 is not None:
            self._compiles0 = c.compiles
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._sync:
            import jax
            for v in self._sync:
                jax.block_until_ready(v() if callable(v) else v)
        t1 = time.perf_counter()
        c = self._collector
        c._exit()
        if self._annos is not None:
            self._annos.__exit__(exc_type, exc, tb)
        if self._compiles0 is not None:
            self.attrs["compiles"] = c.compiles - self._compiles0
        if not self._cancelled:
            stack = c._open()
            rec = SpanRecord(self.name, self._t0 - c.epoch, t1 - self._t0,
                             len(stack), threading.get_ident(),
                             _jsonable(self.attrs),
                             stack[-1] if stack else None)
            with c._lock:
                c._records.append(rec)
        return False


class _NullSpan:
    """Shared no-op stand-in when telemetry is disabled."""

    __slots__ = ()

    def sync(self, value):
        return value

    def set(self, **attrs):
        pass

    def cancel(self):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()

# the event JAX records around every compile-or-load of a program: it wraps
# the persistent-cache lookup, so it fires on a cache load too (the cache's
# own retrieval event fires inside it, and is not counted again)
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class SpanCollector:
    """Thread-safe in-process span store.

    Spans nest per thread (a thread-local stack of open span names);
    records carry (name, start, dur, depth, tid, attrs, parent) and export
    as a Chrome-trace/Perfetto event stream.  `compiles` counts the
    programs compiled or loaded in the process since the collector was
    installed.
    """

    def __init__(self, jax_profiler: bool = False):
        self.jax_profiler = bool(jax_profiler)
        self.epoch = time.perf_counter()
        self.compiles = 0
        self._records: List[SpanRecord] = []
        self._lock = threading.Lock()
        self._local = threading.local()

    # --- nesting bookkeeping ------------------------------------------------

    def _open(self) -> List[str]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _enter(self, name: str):
        self._open().append(name)

    def _exit(self):
        self._open().pop()

    def _count_compile(self):
        with self._lock:
            self.compiles += 1

    # --- recording ----------------------------------------------------------

    def span(self, name: str, device_sync=None, count_compiles=False,
             **attrs) -> _Span:
        return _Span(self, name, device_sync=device_sync, attrs=attrs,
                     count_compiles=count_compiles)

    # --- reading / exporting ------------------------------------------------

    def records(self) -> List[SpanRecord]:
        with self._lock:
            return list(self._records)

    def names(self) -> List[str]:
        return [r.name for r in self.records()]

    def clear(self):
        with self._lock:
            self._records.clear()

    def chrome_trace(self) -> dict:
        """Chrome ``chrome://tracing`` / Perfetto JSON object format:
        phase-"X" (complete) events with microsecond ts/dur — nesting is
        reconstructed by the viewer from containment per tid."""
        pid = os.getpid()
        events = []
        for r in sorted(self.records(), key=lambda r: (r.start, -r.dur)):
            events.append({
                "name": r.name, "ph": "X", "cat": r.name.split(".")[0],
                "ts": r.start * 1e6, "dur": r.dur * 1e6,
                "pid": pid, "tid": r.tid, "args": r.attrs,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON; returns the path."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f, indent=1)
        return path


# ---------------------------------------------------------------------------
# Module-level switchboard (the API call sites use)
# ---------------------------------------------------------------------------

_ACTIVE: Optional[SpanCollector] = None
_LISTENING = False


def _on_event_duration(event: str, secs: float, **kw):
    c = _ACTIVE
    if c is not None and event == BACKEND_COMPILE:
        c._count_compile()


def enable(jax_profiler: Optional[bool] = None) -> SpanCollector:
    """Install (and return) a fresh process-wide collector.  `jax_profiler`
    defaults from ``REPRO_TELEMETRY_JAX`` (truthy -> every span also enters
    `jax.profiler.TraceAnnotation`).  The first call registers the
    process's one compile listener."""
    global _ACTIVE, _LISTENING
    if jax_profiler is None:
        jax_profiler = os.environ.get("REPRO_TELEMETRY_JAX", "") not in \
            ("", "0", "false")
    if not _LISTENING:
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_event_duration)
        _LISTENING = True
    _ACTIVE = SpanCollector(jax_profiler=jax_profiler)
    return _ACTIVE


def disable():
    global _ACTIVE
    _ACTIVE = None


def collector() -> Optional[SpanCollector]:
    return _ACTIVE


def active() -> bool:
    return _ACTIVE is not None


def span(name: str, device_sync=None, count_compiles=False, **attrs):
    """A timing span — no-op (shared null object) when telemetry is off.
    `count_compiles=True` records the compiles seen while it was open."""
    c = _ACTIVE
    if c is None:
        return _NULL
    return c.span(name, device_sync=device_sync,
                  count_compiles=count_compiles, **attrs)


class _Annotate:
    """`annotate()` region: a span that ALSO enters `jax.named_scope`, so
    inside traced code the name lands in the jaxpr/HLO while the recorded
    wall time is the region's TRACING time."""

    __slots__ = ("_span", "_scope")

    def __init__(self, sp: _Span):
        self._span = sp
        self._scope = None

    def __enter__(self):
        import jax
        self._scope = jax.named_scope(self._span.name)
        self._scope.__enter__()
        self._span.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        self._span.__exit__(exc_type, exc, tb)
        return self._scope.__exit__(exc_type, exc, tb)


def annotate(name: str, **attrs):
    """Span + `jax.named_scope` for regions that may run under tracing.
    No-op when telemetry is off (traced hot loops stay unannotated and
    compile identically)."""
    c = _ACTIVE
    if c is None:
        return _NULL
    return _Annotate(c.span(name, trace_region=True, **attrs))


__all__ = ["BACKEND_COMPILE", "SpanCollector", "SpanRecord", "enable",
           "disable", "collector", "active", "span", "annotate"]
