"""Share of the traced window with no device operation (offline cells)."""
from readers import idle_share_pct


def read(run):
    return idle_share_pct(run)
