"""Roofline share of the serial saturating scatter-add of int32 streams
(kernels/sparse_addto.py behind ``ops.device_addto_int``: clear
write-backs and host-quantized updates of a device segment): 16 bytes
per stream entry (index, value, register read and write; bench/costs.py)
over the device time of its jitted program, against HBM bandwidth."""
from costs import scatter_addto_bytes
from readings import counter, module_seconds, roofline_pct


def read(rec):
    entries = counter(rec, "inc_kernel_elems_total", kernel="sparse_addto")
    if not entries:
        return None
    return roofline_pct(rec, scatter_addto_bytes(entries),
                        module_seconds(rec, "jit__device_scatter_int_jit"))
