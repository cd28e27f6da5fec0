"""snapshot_wait_ms.steady: per cycle, the wall time the session's
``snapshot`` span waited: stage ``bookkeeping_wait`` (the barrier on bind
bookkeeping) plus stage ``mutex_wait`` (taking ``cache.mutex``), mean over
the window's cycles (program_span, ms)."""

from stages import ms_per_cycle


def wait_s(args):
    if "bookkeeping_wait_s" not in args and "mutex_wait_s" not in args:
        return None
    return args.get("bookkeeping_wait_s", 0.0) + args.get("mutex_wait_s", 0.0)


def read(run):
    return ms_per_cycle(run, ("snapshot",), wait_s)
