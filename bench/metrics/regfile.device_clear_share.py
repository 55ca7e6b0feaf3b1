"""Share of the registers a reply's clear emptied inside the program that
read them (core/inc_map.py ``SwitchMemory.read_f32``), the rest being
cleared after the read by a clear verb:
``inc_regfile_clear_elems_total{lane="device"}`` over both lanes, in %."""
from readings import counter


def read(rec):
    device = counter(rec, "inc_regfile_clear_elems_total", lane="device")
    host = counter(rec, "inc_regfile_clear_elems_total", lane="host")
    total = (device or 0.0) + (host or 0.0)
    return 100.0 * (device or 0.0) / total if total else None
