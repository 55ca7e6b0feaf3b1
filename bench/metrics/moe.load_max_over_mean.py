"""How uneven the held experts' loads are: the fullest held expert's
routed rows over the held experts' mean, summed over the window's steps
and MoE layers (``inc_moe_held_max_rows_total`` against
``inc_moe_held_rows_total`` over the experts held). 1 is even; the step's
grouped product runs as many tiles as the rows need, so the ratio moves
the time of the expert layer, not whether a token is dropped."""
from readings import counter


def read(rec):
    worst = counter(rec, "inc_moe_held_max_rows_total")
    held = counter(rec, "inc_moe_held_rows_total")
    n_held = rec["window"].get("arch", {}).get("n_routed_experts")
    if worst is None or not held or not n_held:
        return None
    return worst / (held / n_held)
