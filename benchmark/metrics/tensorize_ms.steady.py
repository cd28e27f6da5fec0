"""tensorize_ms: the tensorize spans per cycle (solver/snapshot.py,
topk.py, select_device.py, device_cache.py), mean over the traced window."""

from readers import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "tensorize")
