"""Zero-dependency span tracer with a no-op fast path (DESIGN.md §13.1).

Design constraints, in order:

1. **Disabled mode costs nothing.**  When ``REPRO_TRACE`` is unset (or
   ``"0"``), no sink, buffer or lock is ever allocated; :func:`span` returns
   a shared null context manager and :func:`event` is a single attribute
   load + ``is None`` test.  Measured on a TPU v5e with TPC-H Q1 over
   5.9M rows (eight spans a query; PERF.md): with tracing off the
   instrumented query runs as fast as the uninstrumented one (median
   9.75e7 against 9.74e7 rows/s, p95 61.87 against 62.21 ms, ten 30-s
   runs each); with tracing on and the profiler annotations written, a
   query takes 64-66 ms against 60-61 ms.
2. **Honest clocks.**  Durations come from ``time.perf_counter_ns`` (the
   monotonic clock); each record also carries a wall-clock ``ts`` so traces
   from different processes can be laid side by side.
3. **Thread- and task-safe.**  The span stack is a
   :class:`contextvars.ContextVar`: nesting is per thread and per asyncio
   task, so concurrent coroutines on one event loop do not nest in each
   other.  The JSONL sink and in-memory buffer are lock-protected.

Enabling:

* ``REPRO_TRACE=1``           — in-memory buffer only (``events()``);
* ``REPRO_TRACE=/path.jsonl`` — buffer + append-mode JSONL sink;
* :func:`configure`           — explicit programmatic control (tests).

Record schema (one JSON object per line; the contract §13.2 relies on):

  {"kind": "span"|"event", "name": str, "ts": float unix seconds,
   "dur_ns": int (spans only), "span_id": int, "parent_id": int|null,
   "root_id": int, "depth": int, "thread": int, "attrs": {...}}

``root_id`` is the ``span_id`` of the outermost span open when the record
was made (a root span's own id): the request's identifier, shared by every
record of one query.

The optional ``jax.profiler.TraceAnnotation`` passthrough makes enabled
spans visible in XLA profiler timelines, each annotation carrying the
span's ``span_id`` and ``root_id`` as metadata so that a record joins its
own event in the ``.xplane.pb`` by id; it is off unless requested
(``configure(jax_annotations=True)`` or ``REPRO_TRACE_JAX=1``) because the
profiler hooks are not free.

While tracing is on, every JAX compile phase (jaxpr trace, lowering to
MLIR, backend compile — a persistent-cache hit reports its load as a
backend compile) becomes a ``jax.compile`` event with attributes
``phase``, ``seconds``, ``fun`` and ``span``, the name of the innermost
open span.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time

__all__ = [
    "TRACE_ENV", "TRACE_JAX_ENV", "enabled", "configure", "disable",
    "span", "event", "events", "flush", "sink_path",
]

TRACE_ENV = "REPRO_TRACE"
TRACE_JAX_ENV = "REPRO_TRACE_JAX"

_BUFFER_CAP = 1 << 16       # in-memory ring; the JSONL sink is unbounded

#: the open spans, outermost first.  A context variable, created once at
#: module level as the ``contextvars`` documentation asks: each thread and
#: each asyncio task sees its own stack
_STACK: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_trace_stack", default=())

#: ``jax.monitoring`` duration events of a compile -> the ``phase`` of the
#: ``jax.compile`` event they become
COMPILE_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir_module",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
_compile_listener_registered = False


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()


class _TraceState:
    """All tracer state; exists only while tracing is enabled."""

    def __init__(self, path: str | None, jax_annotations: bool):
        self.path = path
        self.jax_annotations = jax_annotations
        self.lock = threading.Lock()
        self.buffer: list[dict] = []
        self.next_id = 0
        self._fh = None
        if jax_annotations:
            try:
                from jax.profiler import TraceAnnotation
                self.annotation_cls = TraceAnnotation
            except Exception:           # profiler unavailable: degrade
                self.annotation_cls = None
        else:
            self.annotation_cls = None

    def alloc_id(self) -> int:
        with self.lock:
            i = self.next_id
            self.next_id += 1
            return i

    def emit(self, record: dict) -> None:
        line = None
        if self.path is not None:
            line = json.dumps(record, default=str)
        with self.lock:
            if len(self.buffer) < _BUFFER_CAP:
                self.buffer.append(record)
            if line is not None:
                if self._fh is None:
                    d = os.path.dirname(os.path.abspath(self.path))
                    os.makedirs(d, exist_ok=True)
                    self._fh = open(self.path, "a")
                self._fh.write(line + "\n")

    def flush(self) -> None:
        with self.lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self.lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


_state: _TraceState | None = None


def _init_from_env() -> None:
    val = os.environ.get(TRACE_ENV, "")
    if val in ("", "0"):
        return
    jax_ann = os.environ.get(TRACE_JAX_ENV, "") not in ("", "0")
    configure(path=None if val == "1" else val, jax_annotations=jax_ann)


def enabled() -> bool:
    return _state is not None


def sink_path() -> str | None:
    """The active JSONL sink path, or None (disabled / buffer-only)."""
    return _state.path if _state is not None else None


def configure(path: str | None = None,
              jax_annotations: bool = False) -> None:
    """Enable tracing (programmatic override of ``REPRO_TRACE``)."""
    global _state
    if _state is not None:
        _state.close()
    _state = _TraceState(path, jax_annotations)
    _register_compile_listener()


def _on_duration(name: str, seconds: float, **kwargs) -> None:
    """``jax.monitoring`` listener: a compile phase -> a ``jax.compile``
    event, named by the innermost open span."""
    phase = COMPILE_PHASES.get(name)
    if phase is None or _state is None:
        return
    stack = _STACK.get()
    event("jax.compile", phase=phase, seconds=seconds,
          fun=kwargs.get("fun_name"),
          span=stack[-1].name if stack else None)


def _register_compile_listener() -> None:
    """Register :func:`_on_duration` once per process (``jax.monitoring``
    keeps every listener; it does nothing while tracing is off)."""
    global _compile_listener_registered
    if _compile_listener_registered:
        return
    try:
        from jax import monitoring
    except ImportError:         # the tracer itself needs no JAX
        return
    monitoring.register_event_duration_secs_listener(_on_duration)
    _compile_listener_registered = True


def disable() -> None:
    """Disable tracing and drop every allocated resource."""
    global _state
    if _state is not None:
        _state.close()
    _state = None


class _Span:
    """A live span: times itself, tracks nesting, emits one record on exit."""

    __slots__ = ("name", "attrs", "span_id", "parent_id", "root_id",
                 "depth", "_t0", "_ts", "_annotation")

    def __init__(self, state: _TraceState, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.span_id = state.alloc_id()
        stack = _STACK.get()
        self.parent_id = stack[-1].span_id if stack else None
        self.root_id = stack[0].span_id if stack else self.span_id
        self.depth = len(stack)
        self._annotation = (
            state.annotation_cls(name, span_id=self.span_id,
                                 root_id=self.root_id)
            if state.annotation_cls is not None else None)

    def set(self, **attrs):
        """Attach attributes discovered mid-span."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        _STACK.set(_STACK.get() + (self,))
        if self._annotation is not None:
            self._annotation.__enter__()
        self._ts = time.time()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter_ns() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        stack = _STACK.get()
        if stack and stack[-1] is self:
            _STACK.set(stack[:-1])
        st = _state
        if st is not None:
            if exc_type is not None:
                self.attrs["error"] = exc_type.__name__
            st.emit({"kind": "span", "name": self.name, "ts": self._ts,
                     "dur_ns": dur, "span_id": self.span_id,
                     "parent_id": self.parent_id, "root_id": self.root_id,
                     "depth": self.depth, "thread": threading.get_ident(),
                     "attrs": self.attrs})
        return False


def span(name: str, **attrs):
    """Context manager timing a named region; no-op when disabled."""
    st = _state
    if st is None:
        return _NULL_SPAN
    return _Span(st, name, attrs)


def event(name: str, **attrs) -> None:
    """Emit a point event; no-op when disabled."""
    st = _state
    if st is None:
        return
    stack = _STACK.get()
    span_id = st.alloc_id()
    st.emit({"kind": "event", "name": name, "ts": time.time(),
             "span_id": span_id,
             "parent_id": stack[-1].span_id if stack else None,
             "root_id": stack[0].span_id if stack else span_id,
             "depth": len(stack), "thread": threading.get_ident(),
             "attrs": attrs})


def events() -> list[dict]:
    """Copy of the in-memory record buffer (empty when disabled)."""
    st = _state
    if st is None:
        return []
    with st.lock:
        return list(st.buffer)


def flush() -> None:
    if _state is not None:
        _state.flush()


_init_from_env()
