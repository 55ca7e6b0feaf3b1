"""Roofline share of the fused quantize + saturating addTo
(kernels/fused_gpv.py behind ``ops.device_addto_dense``): the bytes its
launches of the window needed (12 per element, bench/costs.py; elements
from ``inc_kernel_elems_total``) over the device time of its jitted
program, against the chip's HBM bandwidth."""
from costs import fused_addto_bytes
from readings import counter, module_seconds, roofline_pct


def read(rec):
    elems = counter(rec, "inc_kernel_elems_total", kernel="fused_addto")
    if not elems:
        return None
    return roofline_pct(rec, fused_addto_bytes(elems),
                        module_seconds(rec, "jit__fused_addto_jit"))
