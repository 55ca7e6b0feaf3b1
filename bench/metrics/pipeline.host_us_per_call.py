"""Host microseconds a pipeline pass (core/rpc.py) spends per call:
``inc_pipeline_pass_us`` sum over ``inc_pipeline_calls_total``."""
from readings import counter, histogram


def read(rec):
    h = histogram(rec, "inc_pipeline_pass_us")
    calls = counter(rec, "inc_pipeline_calls_total")
    return h[0] / calls if h and calls else None
