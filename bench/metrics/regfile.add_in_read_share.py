"""Share of the dense fp32 addTos of a device segment that ran inside the
program of the read that followed them (core/inc_map.py
``DeviceSegment.stage``), the rest each in a launch of its own:
``inc_regfile_adds_total{lane="read"}`` over both lanes, in %."""
from readings import counter


def read(rec):
    fused = counter(rec, "inc_regfile_adds_total", lane="read")
    alone = counter(rec, "inc_regfile_adds_total", lane="alone")
    total = (fused or 0.0) + (alone or 0.0)
    return 100.0 * (fused or 0.0) / total if total else None
