"""Mean wall time of the chunk steps (offline cells)."""
from readers import step_ms


def read(run):
    return step_ms(run, "chunk")
