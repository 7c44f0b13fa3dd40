"""The generator gives every seed the same work: in another order, or in
one fixed order, as the mix says."""
import itertools
import json
from pathlib import Path

import numpy as np
import pytest

from bench import loadgen

MIXES = Path(__file__).resolve().parents[1] / "traffic"


def mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def window(name, seed, seconds=40.0):
    m = mix(name)
    n = (round(m["rate_per_s"] * (m["ramp_s"] + seconds))
         if m["loop"] == "open" else 4 * m["clients"])
    return list(itertools.islice(
        loadgen.generate(m, 151_936, seed, seconds), n))


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_same_work_for_every_seed(name):
    a, b = window(name, 2 ** 31 + 1), window(name, 2 ** 31 + 2)
    for key in (lambda r: len(r.tokens), lambda r: r.max_new,
                lambda r: (len(r.tokens), r.max_new, r.prefix_id)):
        assert sorted(map(key, a)) == sorted(map(key, b))
    shape = [(len(r.tokens), r.max_new, r.prefix_id, r.due_s) for r in a]
    if mix(name)["order"] == "fixed":
        assert shape == [(len(r.tokens), r.max_new, r.prefix_id, r.due_s)
                         for r in b]
    else:
        assert [len(r.tokens) for r in a] != [len(r.tokens) for r in b]
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_unknown_order_is_refused():
    with pytest.raises(ValueError, match="order"):
        next(loadgen.generate(dict(mix("chat"), order="random"), 100, 1,
                              40.0))


def test_open_loop_schedule():
    m = mix("chat")
    reqs = window("chat", 2 ** 31 + 3)
    due = np.array([r.due_s for r in reqs])
    assert due[0] == -m["ramp_s"] and np.all(np.diff(due) > 0)
    in_window = (due >= 0) & (due < 40.0)
    assert in_window.sum() == round(m["rate_per_s"] * 40.0)
    assert all(r.prefix_id and len(r.tokens) >= 512 for r in reqs)
    lo, hi = m["output"]["min"], m["output"]["max"]
    assert all(lo <= r.max_new <= hi for r in reqs)
