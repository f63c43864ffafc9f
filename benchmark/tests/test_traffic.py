"""The traffic generator: the same seed gives the same traffic, another seed
the same sizes in the same order with other token ids."""
import numpy as np

import tiny
import traffic


def _first(mix, seed, n):
    src = traffic.serve_requests(mix, seed, 512)
    return [next(src) for _ in range(n)]


def test_closed_and_open_reproduce_from_the_seed():
    for cell in ("tiny_serve_closed", "tiny_serve_open"):
        mix = tiny.load(cell)["mix"]
        a, b = _first(mix, 2**31 + 5, 40), _first(mix, 2**31 + 5, 40)
        assert all((p == q).all() and m == n for (p, m), (q, n) in zip(a, b))
        c = _first(mix, 6, 40)
        # every seed sends the mix's own pool of sizes in the mix's own
        # order; the seed draws the ids
        assert [(len(p), n) for p, n in a] == [(len(p), n) for p, n in c]
        assert any((p != q).any() for (p, _), (q, _) in zip(a, c))
        assert sorted((len(p), n) for p, n in a[:16]) == \
            sorted(traffic.length_pool(mix))
        sizes = [(len(p), n) for p, n in a]
        assert sizes[:16] != sizes[16:32]     # each pass in another order


def test_open_schedule_rate_and_bursts():
    mix = tiny.load("tiny_serve_open")["mix"]
    due = traffic.open_schedule(mix, 11, 500.0)
    assert due == traffic.open_schedule(mix, 11, 500.0)
    assert due != traffic.open_schedule(mix, 12, 500.0)
    gaps = np.diff([0.0] + due)
    assert abs(len(due) / 500.0 - 4.0) < 0.4          # 4 requests a second
    assert gaps.std() / gaps.mean() > 1.5             # cv 2: bursts
    poisson = dict(mix, arrivals=dict(mix["arrivals"], process="poisson"))
    g = np.diff(traffic.open_schedule(poisson, 11, 500.0))
    assert 0.85 < g.std() / g.mean() < 1.15


def test_shared_prefix_and_train_batches():
    mix = tiny.load("tiny_serve_open")["mix"]
    heads = {tuple(p[:8]) for p, _ in _first(mix, 3, 40) if len(p) >= 8}
    assert len(heads) == 2
    t = tiny.load("tiny_train")["mix"]
    a = traffic.train_batch(t, 2**31 + 1, 0, 512)
    assert a.shape == (4, 64) and a.dtype == np.int32
    assert (a == traffic.train_batch(t, 2**31 + 1, 0, 512)).all()
    assert (a != traffic.train_batch(t, 2**31 + 1, 1, 512)).any()
    assert len({tuple(r) for r in a}) == 4            # rows all differ
