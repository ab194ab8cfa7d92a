"""Host spans and counters of the search path.

``span(name)`` marks a step of ``nn_search`` as ``repro.<name>`` in a
``jax.profiler`` trace (a ``TraceAnnotation``).  Device events in this
runtime's traces carry no scope, so a span names device work only
through the host: a trace reader credits each device program to the
spans open when the host enqueued it (the ``run_id`` that the host's
``DoEnqueueProgram`` and the device's ``XLA Modules`` event share;
``bench/span_reduce.py``).

Counters, process-wide, read with ``snapshot()``:

  calls          outermost spans entered (``nn_search`` calls)
  lowerings      jaxprs lowered to MLIR while a span was open
  lowering_s     seconds of jaxpr tracing and lowering while a span was
                 open; both also by the innermost open span
                 (``lowerings_by_span``, ``lowering_s_by_span``), and
                 counted as that span closes
  verify_rounds  rounds of the engine's verification loop: each call's
                 round scalar waits in a queue until its device has it
                 ready, then is added up on the host as a Python int;
                 ``snapshot()`` waits for the rest, so counting adds no
                 host sync, device program or device affinity to a search
  span_s         wall time of each span, timed only while a profiler
                 trace runs
  kernel_fallbacks  kernel op calls served by their jnp reference, by
                 kernel (``kernels/ops.py``)
  bound_grid     calls of the L-blockable bound ops (LB_Keogh and both
                 LB_ENHANCED kernels), by grid: ``resident`` (whole rows
                 in one block) or ``l_blocked`` (a reduction axis over L
                 blocks)
  verify_grid    calls of the banded-DTW op, by grid: ``resident`` or
                 ``stream``
  bound_pass     the engine's bound passes, by how they ran: ``program``
                 (concrete inputs, one cached program per static key) or
                 ``inline`` (traced inputs, counted as they are traced)

The keyed counters are counted by ``count``.  An op traced into a
compiled program counts nothing at trace time, except inside
``recording()``: there its counts are kept, and the program's caller
``replay``s them on every call of the program (``engine._call``).

``snapshot()["trace"]`` holds the same counters over the calls made
since the running (or last) profiler trace began.  Tracing is on
exactly when a profiler trace runs.  With none, a span costs a
``TraceAnnotation`` entry and a list append; a lock is taken as the
outermost span opens, to count the call, and as a span that lowered
closes.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax
from jax import monitoring
from jax.profiler import TraceAnnotation

PREFIX = "repro."
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class _Counts:
    def __init__(self):
        self.calls = 0
        self.lowerings = 0
        self.lowering_s = 0.0
        self.lowerings_by_span = collections.Counter()
        self.lowering_s_by_span = collections.Counter()
        self.span_s = collections.Counter()
        self.verify_rounds = 0
        self.keyed = {name: collections.Counter() for name in KEYED}

    def read(self) -> dict:
        return {"calls": self.calls, "lowerings": self.lowerings,
                "lowering_s": self.lowering_s,
                "lowerings_by_span": dict(self.lowerings_by_span),
                "lowering_s_by_span": dict(self.lowering_s_by_span),
                "verify_rounds": self.verify_rounds,
                "span_s": dict(self.span_s),
                **{name: dict(c) for name, c in self.keyed.items()}}


# the counters kept by key (module docstring)
KEYED = ("kernel_fallbacks", "bound_grid", "verify_grid", "bound_pass")


_lock = threading.Lock()
_local = threading.local()
_total = _Counts()
_trace = _Counts()
_tracing = False
# (round scalar, the counters it adds to) not yet added up; appended
# without the lock, drained under it
_rounds = collections.deque()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _active() -> tuple[_Counts, ...]:
    return (_total, _trace) if _tracing else (_total,)


def _begin_call() -> None:
    global _trace, _tracing
    on = TraceAnnotation.is_enabled()
    with _lock:
        if on and not _tracing:
            _trace = _Counts()
        _tracing = on
        for c in _active():
            c.calls += 1
        _drain(wait=False)


def _drain(wait: bool) -> None:
    """Add up the queued round scalars that are ready, or all of them
    when ``wait``; the caller holds ``_lock``."""
    for _ in range(len(_rounds)):
        r, into = _rounds.popleft()
        if not (wait or r.is_ready()):
            _rounds.append((r, into))
            continue
        n = int(r)
        for c in into:
            c.verify_rounds += n


class span:
    """``repro.<name>`` in a profiler trace, and on this thread's stack of
    open spans, which the lowering counters are keyed by."""

    def __init__(self, name: str):
        self.name = PREFIX + name

    def __enter__(self):
        stack = _stack()
        if not stack:
            _begin_call()
        ann = TraceAnnotation(self.name)
        ann.__enter__()
        # name, annotation, start (timed only while tracing), and the
        # lowering seconds and lowerings seen while innermost
        stack.append([self.name, ann,
                      time.perf_counter() if _tracing else None, 0.0, 0])
        return self

    def __exit__(self, *exc):
        name, ann, t0, low_s, lows = _stack().pop()
        ann.__exit__(*exc)
        if t0 is None and not low_s:
            return False
        dt = None if t0 is None else time.perf_counter() - t0
        with _lock:
            for c in _active():
                if dt is not None:
                    c.span_s[name] += dt
                if low_s:
                    c.lowering_s += low_s
                    c.lowering_s_by_span[name] += low_s
                if lows:
                    c.lowerings += lows
                    c.lowerings_by_span[name] += lows
        return False


def _on_duration(event: str, secs: float, **_kw) -> None:
    # kept on the innermost span's frame, added up as that span closes
    if event != _TRACE_EVENT and event != _LOWER_EVENT:
        return
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    frame = stack[-1]
    frame[3] += secs
    if event == _LOWER_EVENT:
        frame[4] += 1


monitoring.register_event_duration_secs_listener(_on_duration)


def count_rounds(r) -> None:
    """Queue a verification loop's final round counter (a device scalar)
    to be added up on the host once its device has it; a traced value
    under ``jit`` / ``shard_map`` is no count and is skipped."""
    if isinstance(r, jax.core.Tracer):
        return
    r.copy_to_host_async()
    _rounds.append((r, _active()))


def count(name: str, key: str, *, traced: bool = False) -> None:
    """Add one to the keyed counter ``name`` (one of ``KEYED``) under
    ``key``.  ``traced``: the op runs on traced values, so it is counted
    only inside ``recording()``, which keeps it for ``replay``."""
    rec = getattr(_local, "recording", None)
    if rec is not None:
        rec.append((name, key))
        return
    if traced:
        return
    with _lock:
        for c in _active():
            c.keyed[name][key] += 1


@contextlib.contextmanager
def recording():
    """Keep this thread's ``count`` calls in the list yielded instead of
    adding them up: a program traced inside counts its ops once per call
    by ``replay``-ing the list."""
    prev = getattr(_local, "recording", None)
    _local.recording = rec = []
    try:
        yield rec
    finally:
        _local.recording = prev


def replay(records) -> None:
    """Count again what a ``recording()`` kept."""
    for name, key in records:
        count(name, key)


def snapshot() -> dict:
    """The counters so far, and under ``"trace"`` those of the calls
    made since the running (or last) profiler trace began.  Reading
    waits for the round counters still queued."""
    with _lock:
        _drain(wait=True)
        return dict(_total.read(), trace=_trace.read())
