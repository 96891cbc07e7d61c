"""Roofline share of the mpGeMM kernel calls (offline cells)."""
from readers import mpgemm_roofline_pct


def read(run):
    return mpgemm_roofline_pct(run)
