"""Hierarchical span tracer with Chrome trace-event export.

Design constraints (ISSUE 5 tentpole):

- **Low overhead.** A disabled ``span()`` is one attribute read, one
  bool test, and a shared no-op context manager — no allocation, no
  clock read. An enabled span costs two ``perf_counter`` reads, one
  small dict, and one lock-free ``deque.append``. The bench's ``obs``
  section pins the enabled overhead against idle cycles.
- **Thread-aware hierarchy.** Each thread keeps its own span stack
  (``threading.local``), so spans opened on the overlap window's worker
  threads (native solve worker, cache side-effect pool, tensorize
  chunk pool) nest correctly. Cross-thread parentage — a worker span
  belonging to the scheduler thread's cycle — uses an explicit capture/
  adopt handshake: the submitting thread calls :meth:`Tracer.capture`
  and the worker wraps its work in ``with TRACER.adopt(token):``.
- **True concurrency in the export.** Events are Chrome trace "X"
  (complete) events keyed by real thread id, so Perfetto renders the
  overlapped solve/apply window as concurrent tracks; ``args`` carry
  the owning cycle and parent span id for programmatic assertions.

- **Where the time went, not only how long.** :meth:`Tracer.stage`
  sums wall time and a count per named stage into the innermost open
  span of the calling thread, and thread CPU time (``time.thread_time``)
  over every ``CPU_EVERY``-th entry, less the collections and clock
  reads inside the entry; a span opened with ``cpu=True`` also
  records its own thread-CPU delta (``cpu_s``). Wall time alone blames
  whichever stage happened to wait for the GIL or a lock; CPU time does
  not depend on who waits.
- **The runtime's own pauses.** While enabled, a ``gc.callbacks`` hook
  records a ``gc`` span per collection on the collecting thread, and a
  ``jax.monitoring`` listener records ``compile`` and
  ``compile_cache_load`` spans. Every span is on ``time.perf_counter``.

``KBT_TRACE_DIR`` enables tracing process-wide (the scheduler loop and
the guarded error path export there); bench ``--trace`` and sim
``--trace-out`` enable it explicitly for one run. ``KBT_TRACE_JAX=1``
additionally wraps solver-stage spans in
``jax.profiler.TraceAnnotation`` so they show up inside XLA profiles.
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

# Clock reads on the recording paths, bound once.
_perf = time.perf_counter
_cpu = time.thread_time

TRACE_DIR_ENV = "KBT_TRACE_DIR"
TRACE_JAX_ENV = "KBT_TRACE_JAX"
# Ring bound on buffered events: a week-long scheduler run with tracing
# left on must stay at a fixed memory footprint (oldest spans drop, the
# `dropped` stat records how many).
DEFAULT_CAPACITY = 200_000


def trace_dir_from_env() -> Optional[str]:
    """The process-wide trace directory, or None when tracing is off."""
    return os.environ.get(TRACE_DIR_ENV) or None


class _NullSpan:
    """Shared no-op context manager for the disabled path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullSpan()


# jax.monitoring duration events recorded as spans, by span name.
_JAX_SPANS = {
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile_cache_load",
}

# A stage reads the thread-CPU clock on every CPU_EVERY-th entry only
# (the first included): on the v5e host a ``thread_time`` read costs
# 5.6–6.6 µs against 0.13–0.16 µs for ``perf_counter``, which made per-bind
# stages stretch the bind drain. ``<name>_cpu_n`` counts the read entries;
# ``<name>_cpu_s * <name>_n / <name>_cpu_n`` estimates the stage's CPU.
# A read entry leaves out what is not the stage's own work and would be
# scaled by CPU_EVERY with it: the collections its thread ran inside it
# (a full one takes over a second at 50k pods; ``gc`` spans report it),
# and the clock reads themselves, its own and those of read entries of
# other stages nested in it. A read's cost is its wall time, taken in
# place (contention on the v5e host stretches it).
CPU_EVERY = 8

# stage name -> its (wall, count, thread-CPU, CPU-read count) arg keys.
_STAGE_KEYS: dict = {}


def _stage_keys(name: str) -> tuple:
    keys = _STAGE_KEYS.get(name)
    if keys is None:
        keys = _STAGE_KEYS[name] = (
            f"{name}_s", f"{name}_n", f"{name}_cpu_s", f"{name}_cpu_n")
    return keys


# Sentinel distinguishing "no adopted cycle override" from an adopted
# cycle that is legitimately None.
_UNSET = object()


class _Span:
    __slots__ = (
        "tracer", "name", "args", "sid", "parent", "cycle", "t0", "c0",
        "stages", "_jax_ctx",
    )

    def __init__(self, tracer: "Tracer", name: str, args, jax_annotate,
                 cpu=False):
        self.tracer = tracer
        self.name = name
        self.args = args
        # Thread-CPU clock at entry when the span records ``cpu_s``.
        self.c0 = 0.0 if cpu else None
        self.stages = None  # name -> _Stage, built on first entry
        self._jax_ctx = None
        if jax_annotate and tracer.jax_annotations:
            try:
                import jax

                self._jax_ctx = jax.profiler.TraceAnnotation(name)
            except Exception:  # pragma: no cover - jax absent/old
                self._jax_ctx = None

    def __enter__(self):
        t = self.tracer
        tls = t._tls
        stack = getattr(tls, "stack", None)
        if stack is None:
            stack = tls.stack = []
        self.sid = next(t._ids)
        self.parent = (
            stack[-1].sid if stack else getattr(tls, "adopted", 0)
        )
        # Owning cycle, resolved at ENTRY: an adopted worker span (and
        # anything nested under it) belongs to the cycle that queued
        # it, even when the scheduler thread has already advanced the
        # global cycle counter by the time the worker drains.
        override = getattr(tls, "adopted_cycle", _UNSET)
        self.cycle = t.cycle if override is _UNSET else override
        stack.append(self)
        if self._jax_ctx is not None:
            self._jax_ctx.__enter__()
        # Wall clock outside the CPU clock, at both ends: the CPU
        # interval lies inside the wall one.
        self.t0 = _perf()
        if self.c0 is not None:
            self.c0 = _cpu()
        return self

    def __exit__(self, *exc):
        c0 = self.c0
        cpu_s = None if c0 is None else _cpu() - c0
        t1 = _perf()
        if cpu_s is not None or self.stages:
            if self.args is None:
                self.args = {}
            if cpu_s is not None:
                self.args["cpu_s"] = cpu_s
            if self.stages:
                for stage in self.stages.values():
                    stage.write(self.args)
        t = self.tracer
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
        stack = t._tls.stack
        if stack and stack[-1] is self:
            stack.pop()
        t._record(
            self.name, self.t0, t1, self.sid, self.parent, self.cycle,
            self.args,
        )
        return False


class _Stage:
    """A named stage of one span: sums each entry's wall time and a count,
    and on every ``CPU_EVERY``-th entry its thread CPU time less the
    collections and clock reads inside it; the span writes them into its
    args at exit (``<name>_s``, ``<name>_n``, ``<name>_cpu_s``,
    ``<name>_cpu_n``). One object per span and name, entered again each
    time (a name does not nest in itself)."""

    __slots__ = ("tls", "keys", "seen", "wall", "cpu", "cpu_n", "t0", "c0",
                 "x0")

    def __init__(self, tls, name: str):
        self.tls = tls
        self.keys = _stage_keys(name)
        self.seen = 0
        self.wall = 0.0
        self.cpu = 0.0
        self.cpu_n = 0
        self.c0 = None

    def __enter__(self):
        self.t0 = _perf()
        # CPU read inside the wall reads, on the sampled entries.
        if self.seen % CPU_EVERY:
            self.c0 = None
        else:
            self.x0 = getattr(self.tls, "cpu_excluded", 0.0)
            self.c0 = _cpu()
        self.seen += 1
        return self

    def __exit__(self, *exc):
        c0 = self.c0
        if c0 is None:
            self.wall += _perf() - self.t0
            return False
        r0 = _perf()
        c1 = _cpu()
        t1 = _perf()
        read = t1 - r0
        tls = self.tls
        excluded = getattr(tls, "cpu_excluded", 0.0)
        # The span between the two reads holds about one read's cost.
        self.cpu += c1 - c0 - (excluded - self.x0) - read
        self.cpu_n += 1
        # Both reads lie inside any read entry this one is nested in.
        tls.cpu_excluded = excluded + 2 * read
        self.wall += t1 - self.t0
        return False

    def write(self, args: dict) -> None:
        k_s, k_n, k_cpu, k_cpu_n = self.keys
        args[k_s] = self.wall
        args[k_n] = self.seen
        if self.cpu_n:
            args[k_cpu] = self.cpu
            args[k_cpu_n] = self.cpu_n


class _TimedAcquire:
    """Takes ``lock`` with the wait added to the ``mutex_wait`` stage as
    wall time and a count (a blocked wait burns no CPU, so none is read),
    and releases it at exit. An uncontended lock is taken without a wait
    to time."""

    __slots__ = ("stage", "lock")

    def __init__(self, stage: _Stage, lock):
        self.stage = stage
        self.lock = lock

    def __enter__(self):
        lock = self.lock
        stage = self.stage
        if not lock.acquire(False):
            t0 = _perf()
            lock.acquire()
            stage.wall += _perf() - t0
        stage.seen += 1
        return self

    def __exit__(self, *exc):
        self.lock.release()
        return False


class _Adopt:
    """Context manager installing a cross-thread parent span id (and
    the owning cycle) captured by :meth:`Tracer.capture`."""

    __slots__ = ("tracer", "token", "_prev", "_prev_cycle")

    def __init__(self, tracer: "Tracer", token):
        self.tracer = tracer
        self.token = token

    def __enter__(self):
        tls = self.tracer._tls
        self._prev = getattr(tls, "adopted", 0)
        self._prev_cycle = getattr(tls, "adopted_cycle", _UNSET)
        sid, cycle = self.token
        tls.adopted = sid or 0
        tls.adopted_cycle = cycle
        return self

    def __exit__(self, *exc):
        tls = self.tracer._tls
        tls.adopted = self._prev
        tls.adopted_cycle = self._prev_cycle
        return False


class Tracer:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self.capacity = capacity
        self.cycle = None              # stamped by the scheduler loop
        self.annotator = None          # e.g. the sim's virtual-time stamp
        self.jax_annotations = os.environ.get(TRACE_JAX_ENV) == "1"
        self.spans_recorded = 0
        self._events: deque = deque(maxlen=capacity)
        self._thread_names: dict = {}
        self._tls = threading.local()
        self._ids = itertools.count(1)  # count().__next__ is atomic
        self._epoch = time.perf_counter()
        self._pid = os.getpid()
        self._gc_t0 = None
        self._gc_c0 = 0.0
        self._jax_listening = False

    # -- lifecycle ----------------------------------------------------------

    def enable(self) -> None:
        """Start recording, with the GC hook installed and (once per
        tracer) the JAX compile listener registered."""
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)
        if not self._jax_listening:
            self._jax_listening = True
            try:
                import jax.monitoring
            except ImportError:  # pragma: no cover - jax absent
                pass
            else:
                jax.monitoring.register_event_duration_secs_listener(
                    self._on_jax_duration)
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def reset(self) -> None:
        """Drop buffered events and stats (keeps enabled state).
        Thread names are kept: threads cache their tid in TLS and
        register the name only once, so clearing the map would leave
        later exports without thread_name metadata."""
        self._events.clear()
        self.spans_recorded = 0
        self.cycle = None

    # -- spans --------------------------------------------------------------

    def span(self, name: str, jax_annotate: bool = False, cpu: bool = False,
             **args) -> "_Span | _NullSpan":
        if not self.enabled:
            return _NULL
        return _Span(self, name, args or None, jax_annotate, cpu)

    def _stage(self, name: str) -> Optional[_Stage]:
        """Stage ``name`` of the current thread's innermost open span,
        made on first use; None with no open span."""
        stack = getattr(self._tls, "stack", None)
        if not stack:
            return None
        span = stack[-1]
        stages = span.stages
        if stages is None:
            stages = span.stages = {}
        stage = stages.get(name)
        if stage is None:
            stage = stages[name] = _Stage(self._tls, name)
        return stage

    def stage(self, name: str) -> "_Stage | _NullSpan":
        """Add this block's wall time and a count to the innermost open
        span of the current thread, as that span's ``<name>_s`` and
        ``<name>_n`` args, and on every ``CPU_EVERY``-th entry its thread
        CPU time outside collections and clock reads (``<name>_cpu_s``,
        ``<name>_cpu_n``). Stages of different names nest (each is its own
        sum); a name must not nest inside itself. With no open span on the
        thread, or disabled, it records nothing."""
        if not self.enabled:
            return _NULL
        stage = self._stage(name)
        return _NULL if stage is None else stage

    def acquire(self, lock: object) -> "_TimedAcquire | _NullSpan":
        """Take ``lock`` with the wait summed as stage ``mutex_wait`` (wall
        time and count only) and hold it to the block's end. For a
        re-entrant lock the caller writes ``with TRACER.acquire(lock),
        lock:`` so the locked region stays visible as a ``with lock``
        block; the inner entry is a re-entry."""
        if not self.enabled:
            return _NULL
        stage = self._stage("mutex_wait")
        return _NULL if stage is None else _TimedAcquire(stage, lock)

    def begin_cycle(self, cycle: object) -> None:
        """Stamp the cycle id every subsequent span's args carry (worker
        threads included, via capture/adopt)."""
        self.cycle = cycle

    def _record(self, name, t0, t1, sid, parent, cycle, span_args) -> None:
        """Shared recording tail of ``_Span.__exit__`` and
        :meth:`complete`: annotator resolution, TLS-cached tid (the
        current_thread().name lookup costs microseconds and only needs
        to run once per thread), and the flat-tuple append — deque
        appends are atomic, so the hot path takes no lock; the Chrome
        event dicts are built at export time."""
        extra = self.annotator
        if extra is not None:
            try:
                extra = extra()
            except Exception:  # pragma: no cover - annotator bug
                extra = None
        tls = self._tls
        tid = getattr(tls, "tid", None)
        if tid is None:
            tid = tls.tid = threading.get_ident()
            self._thread_names[tid] = threading.current_thread().name
        self._events.append((
            name, t0, t1, tid, sid, parent, cycle, span_args, extra,
        ))
        self.spans_recorded += 1

    def complete(self, name: str, t0: float, t1: Optional[float] = None,
                 **args) -> None:
        """Record an already-timed interval as a span — for phases whose
        begin/end are measured with explicit ``perf_counter`` reads
        (the allocate_tpu apply/epilogue blocks). The current thread's
        innermost open span is taken as the parent."""
        if not self.enabled:
            return
        if t1 is None:
            t1 = time.perf_counter()
        tls = self._tls
        stack = getattr(tls, "stack", None)
        parent = stack[-1].sid if stack else getattr(tls, "adopted", 0)
        override = getattr(tls, "adopted_cycle", _UNSET)
        cycle = self.cycle if override is _UNSET else override
        self._record(name, t0, t1, next(self._ids), parent, cycle,
                     args or None)

    def capture(self) -> tuple:
        """Opaque token — (current span id, owning cycle) of THIS
        thread — for a worker to ``adopt`` so its spans nest under the
        submitting span AND keep the submitting cycle's stamp even when
        they drain after the scheduler thread advanced the counter
        (async binds deliberately drain in the NEXT cycle's overlap
        window)."""
        tls = self._tls
        override = getattr(tls, "adopted_cycle", _UNSET)
        cycle = self.cycle if override is _UNSET else override
        stack = getattr(tls, "stack", None)
        if stack:
            return (stack[-1].sid, cycle)
        return (getattr(tls, "adopted", 0), cycle)

    def adopt(self, token: tuple) -> _Adopt:
        return _Adopt(self, token)

    # -- runtime hooks ------------------------------------------------------

    def _on_gc(self, phase: str, info: dict) -> None:
        """``gc.callbacks`` hook: one ``gc`` span per collection, and the
        collection's thread CPU added to what stages on the thread leave
        out. CPython runs one collection at a time, on the
        thread that triggered it."""
        if phase == "start":
            self._gc_t0 = _perf()
            self._gc_c0 = _cpu()
            return
        t0, self._gc_t0 = self._gc_t0, None
        if t0 is None:
            return
        tls = self._tls
        tls.cpu_excluded = getattr(tls, "cpu_excluded", 0.0) + (
            _cpu() - self._gc_c0)
        self.complete("gc", t0, _perf(), generation=info["generation"],
                      collected=info["collected"])

    def _on_jax_duration(self, event: str, duration: float, **kwargs) -> None:
        """``jax.monitoring`` listener: a compile or a persistent-cache
        load as a span ending now, on this tracer's clock."""
        name = _JAX_SPANS.get(event)
        if name is None or not self.enabled:
            return
        t1 = time.perf_counter()
        if name == "compile":
            self.complete(name, t1 - duration, t1,
                          fun=kwargs.get("fun_name"))
        else:
            self.complete(name, t1 - duration, t1)

    # -- export -------------------------------------------------------------

    def _to_event(self, rec) -> dict:
        name, t0, t1, tid, sid, parent, cycle, span_args, extra = rec
        args = {"sid": sid, "parent": parent, "cycle": cycle}
        if span_args:
            args.update(span_args)
        if extra:
            args.update(extra)
        return {
            "name": name,
            "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,
            "dur": (t1 - t0) * 1e6,
            "pid": self._pid,
            "tid": tid,
            "args": args,
        }

    def events(self) -> list:
        """Buffered spans as Chrome trace-event dicts (built lazily —
        the recording hot path stores flat tuples)."""
        return [self._to_event(rec) for rec in list(self._events)]

    @property
    def dropped(self) -> int:
        return max(0, self.spans_recorded - len(self._events))

    def export(self, path: str) -> str:
        """Write the buffered spans as a Chrome trace-event JSON file
        (load in Perfetto / chrome://tracing). Returns the path."""
        events = self.events()
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": self._pid,
                "tid": tid,
                "args": {"name": name},
            }
            for tid, name in sorted(self._thread_names.items())
        ]
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w") as f:
            json.dump(
                {"traceEvents": meta + events, "displayTimeUnit": "ms"},
                f,
            )
        return path


TRACER = Tracer()


def span(name: str, jax_annotate: bool = False, cpu: bool = False,
         **args) -> "_Span | _NullSpan":
    """Module-level convenience: ``with obs.span("solve"): ...``."""
    t = TRACER
    if not t.enabled:
        return _NULL
    return _Span(t, name, args or None, jax_annotate, cpu)


def export_trace(path: Optional[str] = None, tag: str = "trace") -> Optional[str]:
    """Export the global tracer's buffer.

    With an explicit ``path``, write there. Otherwise write
    ``<KBT_TRACE_DIR>/<tag>-<pid>.json`` when the env dir is set, else
    do nothing (returns None)."""
    if path is None:
        trace_dir = trace_dir_from_env()
        if trace_dir is None:
            return None
        path = os.path.join(trace_dir, f"{tag}-{os.getpid()}.json")
    return TRACER.export(path)


def maybe_enable_from_env() -> bool:
    """Enable the global tracer iff ``KBT_TRACE_DIR`` is set (called by
    the scheduler/server startup paths). Returns the enabled state."""
    if trace_dir_from_env() is not None:
        TRACER.enable()
    return TRACER.enabled
