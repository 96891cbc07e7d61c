import json
from collections import Counter

import numpy as np

import tiny
from harness import BENCH
from traffic import generate


def _mix(name, **over):
    m = (dict(tiny.CHAT) if name == "chat" else
         json.loads((BENCH / "traffic" / f"{name}.json").read_text()))
    m.update(over)
    return m


def test_every_seed_offers_the_same_work_in_another_order():
    mix = _mix("chat", rate_per_s=5.0, tail_s=4.0, lead_in_s=10.0)
    a = generate(mix, 1, 10.0, 1000)
    b = generate(mix, 2**31 + 12345, 10.0, 1000)
    for x, y in ((a, b),):
        wx = [r for r in x if r.in_window]
        wy = [r for r in y if r.in_window]
        assert len(wx) == len(wy) == 50
        assert Counter(len(r.prompt) for r in wx) == Counter(len(r.prompt) for r in wy)
        assert Counter(r.max_new_tokens for r in wx) == Counter(r.max_new_tokens for r in wy)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_same_seed_same_inputs_and_window_layout():
    mix = _mix("chat", rate_per_s=5.0, tail_s=4.0, lead_in_s=10.0)
    a = generate(mix, 7, 10.0, 1000)
    b = generate(mix, 7, 10.0, 1000)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due_s == y.due_s
               for x, y in zip(a, b))
    due = [r.due_s for r in a]
    assert due == sorted(due)
    win = [r.due_s for r in a if r.in_window]
    assert min(win) >= mix["lead_in_s"] and max(win) < mix["lead_in_s"] + 10.0
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    assert all(lo <= len(r.prompt) <= hi for r in a)


def test_backlog_is_all_due_at_once():
    mix = _mix("prefill_offline", backlog=30)
    a = generate(mix, 3, 10.0, 1000)
    assert len(a) == 30 and all(r.due_s == 0.0 and r.in_window for r in a)
    assert all(2 <= r.max_new_tokens <= 64 for r in a)
    assert all(512 <= len(r.prompt) <= 4096 for r in a)


def test_backlog_keeps_its_order_for_every_seed():
    mix = _mix("prefill_offline", backlog=64)
    a = generate(mix, 5, 10.0, 1000)
    b = generate(mix, 2**31 + 7, 10.0, 1000)
    assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
    assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
