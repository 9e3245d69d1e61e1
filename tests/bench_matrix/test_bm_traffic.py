"""The traffic generator: the same seed gives the same inputs, the clip
points hold, and every seed offers the same multiset of lengths."""

import numpy as np
import pytest

from bench_matrix import spec, traffic_gen

SERVE = [n for n in spec.names("traffic")
         if spec.load("traffic", n)["kind"] == "serve_requests"]
TRAIN = [n for n in spec.names("traffic")
         if spec.load("traffic", n)["kind"] == "train_batches"]


def _take(traffic, seed, n):
    stream = traffic_gen.RequestStream(traffic, 32768, seed)
    return [stream.next() for _ in range(n)]


@pytest.mark.parametrize("name", SERVE)
def test_request_stream_is_deterministic_and_clipped(name):
    t = spec.load("traffic", name)
    n = 2 * t["strata"]
    a, b, c = _take(t, 5, n), _take(t, 5, n), _take(t, 6, n)
    assert all((x[0] == y[0]).all() and x[1] == y[1] for x, y in zip(a, b))
    assert any(len(x[0]) != len(y[0]) for x, y in zip(a, c))
    p, o = t["prompt_tokens"], t["output_tokens"]
    for prompt, out in a + c:
        assert p["min"] <= len(prompt) <= p["max"] and prompt.dtype == np.int32
        assert o["min"] <= out <= o["max"]
        assert len(prompt) + out <= t["engine"]["max_seq_len"]
    # one cycle holds the same lengths whatever the seed: a fixed amount of work
    cyc = lambda reqs: sorted(len(r[0]) for r in reqs[:t["strata"]])
    assert cyc(a) == cyc(c)
    med = np.median([len(r[0]) for r in a])
    assert 0.8 * p["median"] <= med <= 1.25 * p["median"]


def test_quantile_clips_and_orders():
    d = {"dist": "lognormal", "median": 512, "sigma": 0.8, "min": 64, "max": 2048}
    qs = [traffic_gen.quantile(d, u) for u in (0.001, 0.25, 0.5, 0.75, 0.999)]
    assert qs == sorted(qs) and qs[0] == 64 and qs[2] == 512 and qs[-1] == 2048
    assert traffic_gen.quantile({"dist": "fixed", "value": 7}, 0.3) == 7
    with pytest.raises(ValueError):
        traffic_gen.quantile({"dist": "zipf"}, 0.5)


def test_shared_prefix_is_shared():
    t = dict(spec.load("traffic", SERVE[0]), shared_prefix_tokens=32, prefix_groups=1)
    reqs = _take(t, 1, 4)
    assert all((r[0][:32] == reqs[0][0][:32]).all() for r in reqs)
    assert not (reqs[0][0][32:40] == reqs[1][0][32:40]).all()


def test_open_arrivals_are_seeded_sorted_and_at_the_rate():
    arr = {"mode": "open", "rate_per_s": 50.0,
           "burst": {"every_s": 10.0, "length_s": 1.0, "factor": 4.0}}
    a = traffic_gen.open_arrivals(arr, 3, 100.0)
    assert (a == traffic_gen.open_arrivals(arr, 3, 100.0)).all()
    assert (np.diff(a) >= 0).all() and 0 <= a[0] and a[-1] < 100.0
    assert abs(len(a) - 5000) < 5 * 5000 ** 0.5
    in_burst = ((a % 10.0) < 1.0).mean()
    assert 0.25 < in_burst < 0.37  # 4 / (4 + 9) of the arrivals in 1/10 of the time
    assert len(traffic_gen.open_arrivals(arr, 4, 100.0)) != len(a)


@pytest.mark.parametrize("name", TRAIN)
def test_train_batches_are_deterministic(name):
    t = spec.load("traffic", name)
    a = traffic_gen.train_batches(t, 32768, 9)
    assert a.shape == (t["n_batches"], t["global_batch"], t["seq"])
    assert a.dtype == np.int32 and 0 <= a.min() and a.max() < 32768
    assert (a == traffic_gen.train_batches(t, 32768, 9)).all()
    assert (a != traffic_gen.train_batches(t, 32768, 10)).any()
