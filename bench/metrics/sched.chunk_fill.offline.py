"""Real tokens over padded rows of the chunk steps (offline cells)."""
from readers import chunk_fill_pct


def read(run):
    return chunk_fill_pct(run)
