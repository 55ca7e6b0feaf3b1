"""The whole served path's share of the chip's HBM bandwidth: elements
delivered in the window times the bytes one call needs by its shape
(addTo 12 plus reply 9 per element, bench/costs.py), over the window
times the published bandwidth. It bounds any claim on the path, whatever
kernels a later change adds or removes."""
from costs import agtr_call_bytes
from readings import roofline_pct


def read(rec):
    w = rec["window"]
    if not w["calls"]:
        return None
    return roofline_pct(rec, w["calls"] * agtr_call_bytes(w["elems"]),
                        w["seconds"])
