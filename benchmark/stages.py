"""The traced window's spans with their args, for the readers of the program's
stage counters (``metrics/bind_*``, ``loop_busy_pct.steady``,
``snapshot_wait_ms.steady``, ``compile_ms.steady``, ``gc_ms.*``).

The program's tracer (``kube_batch_tpu/obs/tracer.py``) keeps each span as
a flat tuple whose eighth field is its args. A stage adds ``<stage>_s``
(wall) and ``<stage>_n`` (count) to the innermost open span of its thread,
and ``<stage>_cpu_s`` (thread CPU outside collections) over the
``<stage>_cpu_n`` entries that read that clock (every eighth); a span
opened with ``cpu=True`` carries its own ``cpu_s``, collections included.

The window's spans are those the harness took at the window's end
(``run.spans``); their args are looked up in the tracer's ring when read.
A program that records no stage counters (nor, with them, GC and compile
spans) gives None, as does a ring that dropped spans since the window
began: the tracer is reset just before it, so any drop lost window spans.
"""

BIND_SPANS = ("cache_side_effect", "cache_bookkeeping")
LOOP_WAITS = ("loop_wait", "micro_park", "micro_coalesce")


def window_spans(run):
    """(name, t0, t1, cycle, args) of the window's spans, or None."""
    if not run.trace or not run.spans:
        return None
    cached = getattr(run, "_window_spans", None)
    if cached is not None:
        return cached
    from kube_batch_tpu.obs.tracer import TRACER

    if TRACER.dropped:
        return None
    args_of = {rec[:3]: rec[7] for rec in list(TRACER._events)}
    spans = [(name, t0, t1, cycle, args_of.get((name, t0, t1)) or {})
             for name, t0, t1, cycle in run.spans]
    if not any("cpu_s" in args for *_, args in spans):
        return None
    run._window_spans = spans
    return spans


def _per_cycle(run, names):
    """(number of the window's cycles, the named spans of those cycles),
    or None. A cycle is one ``cycle`` span; worker spans carry the cycle
    that queued them."""
    spans = window_spans(run)
    if not spans:
        return None
    cycles = {c for name, _, _, c, _ in spans if name == "cycle"}
    if not cycles:
        return None
    return len(cycles), [(t0, t1, args) for name, t0, t1, c, args in spans
                         if name in names and c in cycles]


def stage_cpu(args, name):
    """Thread CPU of stage ``name`` in one span's args, seconds: the CPU of
    the entries that read the clock, scaled to all of its entries."""
    read = args.get(f"{name}_cpu_n")
    if not read:
        return 0.0
    return args[f"{name}_cpu_s"] * args[f"{name}_n"] / read


def ms_per_cycle(run, names, value):
    """Mean over the window's cycles of ``value(args)`` (seconds) summed
    over the named spans of each cycle, in ms; None where ``value`` gives
    None for every such span."""
    found = _per_cycle(run, names)
    if found is None:
        return None
    n, picked = found
    values = [value(args) for _, _, args in picked]
    values = [v for v in values if v is not None]
    if not values:
        return None
    return sum(values) / n * 1e3


def span_ms_per_cycle(run, *names):
    """Mean over the window's cycles of the named spans' summed duration
    in each cycle, ms."""
    found = _per_cycle(run, names)
    if found is None:
        return None
    n, picked = found
    return sum(t1 - t0 for t0, t1, _ in picked) / n * 1e3


def span_ms(run, *names):
    """Summed duration of the named spans in the window, ms."""
    spans = window_spans(run)
    if not spans:
        return None
    return sum(t1 - t0 for name, t0, t1, _, _ in spans if name in names) * 1e3


def span_ms_per_burst(run, name):
    """Time under the named spans inside each burst's [t0, t2] (run_once's
    start to its binds drained), mean over the window's bursts, ms."""
    spans = window_spans(run)
    if not spans or not run.cycles:
        return None
    total = 0.0
    for cyc in run.cycles:
        lo, hi = cyc["t0"], cyc["t2"]
        total += sum(max(0.0, min(t1, hi) - max(t0, lo))
                     for n, t0, t1, _, _ in spans if n == name)
    return total / len(run.cycles) * 1e3
