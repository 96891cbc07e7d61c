"""The control of the correctness check, at a size a test run holds: the
reference computed in float8 where the program keeps bfloat16 has to read
far above the served program, under the backlog and under a backlog with
shared prefixes."""
import pytest

import tiny
from control import readings


@pytest.mark.parametrize("mix", [tiny.OFFLINE, tiny.AGENT],
                         ids=["offline", "agent"])
def test_control_reads_far_above_the_program(mix):
    cell = tiny.cell(mix)
    r = readings(cell, 2**31 + 99, 1.0)
    assert r["tokens"] >= cell.check["min_tokens"]
    assert r["program_widest_gap"] <= cell.check["gap_limit"]
    assert r["control_widest_gap"] > cell.check["gap_limit"]
    assert r["control_widest_gap"] >= 3 * r["program_widest_gap"]
