"""setup_s: process start to the window's start (import, deployment,
ingest, warm-up, and compiling where the cache is cold), host clock."""


def read(run):
    return run.setup_s
