"""Share of the rows the held experts' grouped product computed that
carry no token: each expert's rows are cut into whole tiles, so its last
tile is padded (``inc_moe_computed_rows_total`` against
``inc_moe_held_rows_total``, over the window's MoE layers)."""
from readings import counter


def read(rec):
    computed = counter(rec, "inc_moe_computed_rows_total")
    held = counter(rec, "inc_moe_held_rows_total")
    if not computed or held is None:
        return None
    return 100.0 * (computed - held) / computed
