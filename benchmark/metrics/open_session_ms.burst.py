"""open_session_ms: the open_session span per cycle (framework/session.py
with the drf and proportion folds), mean over the traced window."""

from readers import span_ms_per_cycle


def read(run):
    return span_ms_per_cycle(run, "open_session")
