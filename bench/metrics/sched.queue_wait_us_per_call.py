"""Microseconds a call waits in the runtime scheduler's queue
(core/runtime.py), from submit to the pick of its batch:
``inc_queue_wait_us`` sum over its count (one sample a call)."""
from readings import histogram


def read(rec):
    h = histogram(rec, "inc_queue_wait_us")
    return h[0] / h[1] if h and h[1] else None
