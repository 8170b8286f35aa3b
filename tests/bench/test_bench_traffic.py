"""The harness's own arithmetic: seeded traffic, Zipf and Poisson draws,
the p95 with failures, rates over the whole window, and the roofline's
byte count."""
import math

import numpy as np
import pytest

import benchkit  # noqa: F401  (puts bench/ on sys.path)
import drivers
from harness import percentile
from roofline import sweep_bytes

SERVE = {"rate_per_s": 20.0, "zipf_s": 1.0,
         "seq_lens": [512, 1024, 2048, 4096, 8192, 16384, 32768]}


def test_request_plan_is_seeded_and_same_multiset_for_every_seed():
    a_due, a_keys = drivers.request_plan(2**33 + 5, SERVE, 32, 20.0)
    b_due, b_keys = drivers.request_plan(2**33 + 5, SERVE, 32, 20.0)
    c_due, c_keys = drivers.request_plan(17, SERVE, 32, 20.0)
    assert np.array_equal(a_due, b_due) and np.array_equal(a_keys, b_keys)
    assert len(a_keys) == len(c_keys) == 400
    assert not np.array_equal(a_keys, c_keys)
    # every seed sends the same popularity profile, dealt to other keys
    assert sorted(np.bincount(a_keys, minlength=224)) == sorted(
        np.bincount(c_keys, minlength=224))


@pytest.mark.parametrize("s", [0.8, 1.0, 1.2])
def test_zipf_counts_follow_rank_power(s):
    n, keys = 100_000, 224
    counts = drivers.zipf_counts(keys, n, s)
    assert counts.sum() == n
    want = n * np.arange(1, keys + 1) ** -s / np.sum(
        np.arange(1, keys + 1) ** -s)
    assert np.all(np.abs(counts - want) < 1.0)
    assert np.all(np.diff(counts) <= 0)


def test_poisson_arrivals_match_rate():
    rng = np.random.default_rng(3)
    n, seconds = 20_000, 1000.0
    t = drivers.arrivals(rng, n, seconds)
    assert np.all(np.diff(t) >= 0) and t[0] >= 0 and t[-1] < seconds
    gaps = np.diff(t)
    assert gaps.mean() == pytest.approx(seconds / n, rel=0.03)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.05)
    # counts per unit time are Poisson: variance equals the mean
    per_s = np.bincount(t.astype(int), minlength=int(seconds))
    assert per_s.var() == pytest.approx(per_s.mean(), rel=0.1)


def test_p95_counts_failures_as_infinite():
    ok = [0.1] * 95
    assert percentile(ok + [math.inf] * 5, 0.95) == 0.1
    assert percentile(ok + [math.inf] * 6, 0.95) == math.inf
    assert percentile([3.0, 1.0, 2.0], 0.95) == 3.0


def test_roofline_bytes_hand_count():
    # 3 nodes, 2 edges, 5 hardware points, 4 groupings, one graph:
    # features 3*13*8, edges 2*3*8, masks 2*3+3+2, cuts 4*2,
    # hardware 5*11*8 + 4*8, rows 5*4*4*8.
    want = 312 + 48 + 11 + 8 + 440 + 32 + 640
    assert sweep_bytes(3, 2, 5, 4) == want
    assert sweep_bytes(3, 2, 5, 4, n_graphs=2) == 2 * (
        312 + 48 + 11 + 8) + 472 + 2 * 640
