"""Share of the request elements of the served path's addTo flushes that
reached the register file as the caller's device buffer, with no host
copy (core/inc_map.py ``SwitchMemory.addto_f32``), the rest through a
host copy or the host quantizer: ``inc_request_elems_total{lane="device"}``
over both lanes, in %."""
from readings import counter


def read(rec):
    dev = counter(rec, "inc_request_elems_total", lane="device")
    host = counter(rec, "inc_request_elems_total", lane="host")
    total = (dev or 0.0) + (host or 0.0)
    return 100.0 * (dev or 0.0) / total if total else None
