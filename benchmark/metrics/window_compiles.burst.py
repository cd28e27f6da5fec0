"""window_compiles: backend compiles inside the measured window."""


def read(run):
    return run.compiles_in_window
