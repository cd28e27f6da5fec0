"""pods_bound_per_s: pod binds seen on the watch inside the window over
the window's seconds; host clock."""


def read(run):
    if run.window is None:
        return None
    return run.binds_in_window / (run.window[1] - run.window[0])
