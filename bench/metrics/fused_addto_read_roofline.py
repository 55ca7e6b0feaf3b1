"""Roofline share of the fused addTo and the read of the same run in one
program (kernels/fused_gpv.py ``fused_addto_read_pallas`` behind
``ops.device_addto_read_dense``): the bytes a SyncAgtr call needs (21 per
element, bench/costs.py; elements from ``inc_kernel_elems_total``) over
the device time of its jitted program, against the chip's HBM
bandwidth."""
from costs import agtr_call_bytes
from readings import counter, module_seconds, roofline_pct


def read(rec):
    elems = counter(rec, "inc_kernel_elems_total", kernel="fused_addto_read")
    if not elems:
        return None
    return roofline_pct(rec, agtr_call_bytes(elems),
                        module_seconds(rec, "jit__fused_addto_read_jit"))
