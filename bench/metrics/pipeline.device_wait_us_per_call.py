"""Host microseconds a call spends in device-to-host copies, which wait
for the work queued before them on the chip: ``inc_xfer_us{dir="d2h"}``
sum over ``inc_pipeline_calls_total``."""
from readings import counter


def read(rec):
    snap = rec.get("obs")
    if not snap:
        return None
    waits = [h["sum"] for k, h in snap["histograms"].items()
             if k.startswith("inc_xfer_us{") and 'dir="d2h"' in k]
    calls = counter(rec, "inc_pipeline_calls_total")
    return sum(waits) / calls if waits and calls else None
