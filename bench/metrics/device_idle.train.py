"""Share of the traced training window in which no op ran on a chip,
averaged over the chips of the cell."""
from readings import idle_pct


def read(rec):
    return idle_pct(rec)
