"""Calls per pipeline pass that the runtime scheduler coalesced
(core/runtime.py): ``inc_pipeline_batch_calls`` sum over count."""
from readings import histogram


def read(rec):
    h = histogram(rec, "inc_pipeline_batch_calls")
    return h[0] / h[1] if h and h[1] else None
