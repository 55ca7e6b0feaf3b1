"""Host microseconds a call spends in the register file's data-path
verbs (core/inc_map.py ServerAgent), less the copies made inside them:
``inc_regfile_us`` sum, less ``inc_xfer_us`` of the ``regfile.*`` sites,
over ``inc_pipeline_calls_total``."""
from readings import counter, histogram


def read(rec):
    verbs = histogram(rec, "inc_regfile_us")
    calls = counter(rec, "inc_pipeline_calls_total")
    if not verbs or not calls:
        return None
    copies = sum(h["sum"] for k, h in rec["obs"]["histograms"].items()
                 if k.startswith("inc_xfer_us{") and 'site="regfile.' in k)
    return (verbs[0] - copies) / calls
