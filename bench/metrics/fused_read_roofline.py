"""Roofline share of the fused gather + dequantize Get reply
(kernels/fused_gpv.py behind ``ops.device_read_dense``): 9 bytes per
element (bench/costs.py) over the device time of its jitted program."""
from costs import read_bytes
from readings import counter, module_seconds, roofline_pct


def read(rec):
    elems = counter(rec, "inc_kernel_elems_total", kernel="fused_read")
    if not elems:
        return None
    return roofline_pct(rec, read_bytes(elems),
                        module_seconds(rec, "jit__fused_read_jit"))
