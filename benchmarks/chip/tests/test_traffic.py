"""The generator: the same seed gives the same inputs; every seed gets the
same sizes and gaps in another order."""
import json
from pathlib import Path

import numpy as np

from chipbench import traffic

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "decode-heavy.json").read_text())
BIG = 2**31 + 12345


def _stream(seed):
    return traffic.serve_stream(MIX, seed, 30.0, 92416, horizon=60.0)


def test_same_seed_same_stream():
    a, b = _stream(BIG), _stream(BIG)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_share_sizes_and_gaps():
    a, b = _stream(BIG), _stream(BIG + 2**32)
    win = lambda s: [r for r in s if r.due < 30.0]  # noqa: E731
    assert len(win(a)) == len(win(b)) == round(MIX["rate_per_s"] * 30)
    assert sorted(len(r.prompt) for r in win(a)) == \
        sorted(len(r.prompt) for r in win(b))
    assert sorted(r.max_new for r in win(a)) == \
        sorted(r.max_new for r in win(b))
    assert [r.max_new for r in win(a)] != [r.max_new for r in win(b)]


def test_order_strata_moves_sizes_only_among_their_neighbours():
    mix = {**MIX, "order_strata": 4}
    a, b = (traffic.serve_stream(mix, s, 30.0, 92416, horizon=30.0)
            for s in (BIG, BIG + 2**32))
    for key in (lambda r: len(r.prompt), lambda r: r.max_new):
        ka, kb = [key(r) for r in a], [key(r) for r in b]
        assert sorted(ka) == sorted(kb) and ka != kb
        order = sorted(ka)
        # each slot's value keeps its rank's stratum of 4 under every seed
        rank = lambda v: order.index(v) // 4  # noqa: E731
        assert [rank(v) for v in ka] == [rank(v) for v in kb]
    gaps = [np.diff([r.due for r in s]) for s in (a, b)]
    assert not np.allclose(*gaps)


def test_lengths_follow_the_mix():
    s = _stream(7)
    p = np.array([len(r.prompt) for r in s])
    o = np.array([r.max_new for r in s])
    lo, hi = MIX["prompt_len"]["min"], MIX["prompt_len"]["max"]
    assert p.min() >= lo and p.max() <= hi
    assert abs(np.median(p) - MIX["prompt_len"]["median"]) < 0.1 * \
        MIX["prompt_len"]["median"]
    assert o.min() >= MIX["output_len"]["min"]
    assert o.max() <= MIX["output_len"]["max"]
    due = np.array([r.due for r in s])
    assert np.all(np.diff(due) > 0) and due[0] == 0.0
    assert all(r.prompt.dtype == np.int32 and r.prompt.max() < 92416
               for r in s)


def test_train_rows_deterministic_and_distinct():
    mix = {"batch": 3, "seq": 64}
    a = traffic.train_rows(mix, BIG, 5, 1000)
    assert a.shape == (3, 65)
    assert np.array_equal(a, traffic.train_rows(mix, BIG, 5, 1000))
    assert not np.array_equal(a, traffic.train_rows(mix, BIG, 6, 1000))
    assert len({r.tobytes() for r in a}) == 3
