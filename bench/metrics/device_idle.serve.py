"""Share of the traced serve window in which no op ran on the chip."""
from readings import idle_pct


def read(rec):
    return idle_pct(rec)
