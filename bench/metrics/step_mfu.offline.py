"""Whole steps' share of the chip's peaks (offline cells)."""
from readers import step_mfu_pct


def read(run):
    return step_mfu_pct(run)
