"""Bytes of array buffers that cross the host link per element served:
``inc_xfer_bytes_total`` (every host<->device copy site of the served
path) over ``inc_gpv_elems_total``."""
from readings import counter


def read(rec):
    moved = counter(rec, "inc_xfer_bytes_total")
    elems = counter(rec, "inc_gpv_elems_total")
    return moved / elems if moved is not None and elems else None
