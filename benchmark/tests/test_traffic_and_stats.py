"""The generator is a pure function of seed and parameters; the percentile
and windowing arithmetic."""
import collections
import json
import os

import numpy as np
import pytest

import stats
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAT = os.path.join(BENCH, "traffic", "chat_open_loop.json")


def _sizes(reqs):
    return sorted(r["prompt"].size for r in reqs), \
        sorted(r["max_new"] for r in reqs)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 4294967301])
def test_open_loop_is_a_pure_function_of_seed(seed):
    mix = traffic.load(CHAT)
    a = traffic.open_loop(mix, seed, 40.0, 50272)
    b = traffic.open_loop(mix, seed, 40.0, 50272)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               and x["max_new"] == y["max_new"] for x, y in zip(a, b))
    assert len(a) == round(mix["rate_per_s"] * 40.0)
    assert a[0]["due_s"] == 0.0 and all(0 <= r["due_s"] < 40.0 for r in a)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= r["prompt"].size <= hi for r in a)
    assert all(1 <= int(r["prompt"].min()) and int(r["prompt"].max()) < 50272
               for r in a)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = traffic.load(CHAT)
    a = traffic.open_loop(mix, 1, 40.0, 50272)
    b = traffic.open_loop(mix, 2, 40.0, 50272)
    assert _sizes(a) == _sizes(b)
    assert [r["max_new"] for r in a] != [r["max_new"] for r in b]
    gaps = lambda rs: sorted(np.diff([r["due_s"] for r in rs] + [40.0]))
    assert np.allclose(gaps(a), gaps(b))


DECODE_MIXES = [("chat_open_loop", 50272), ("short_long_open_loop", 25024)]


def _pairs(reqs):
    return collections.Counter((r["prompt"].size, r["max_new"]) for r in reqs)


def _gaps(reqs, seconds):
    return np.sort(np.diff([r["due_s"] for r in reqs] + [seconds]))


@pytest.mark.parametrize("rehearse,seconds", [(False, 50.0), (True, 2.0)])
@pytest.mark.parametrize("mix_name,vocab", DECODE_MIXES)
def test_every_seed_offers_the_same_requests(mix_name, vocab, rehearse,
                                             seconds):
    """The multiset of (prompt length, max_new) PAIRS and of gaps is the
    mix's; a seed orders whole requests and draws the token ids."""
    mix = traffic.load(os.path.join(BENCH, "traffic", mix_name + ".json"),
                       rehearse)
    seeds = [0, 1, 3000039100, 2 ** 31 + 12345, 4294967301]
    runs = [traffic.open_loop(mix, s, seconds, vocab) for s in seeds]
    n = max(1, round(mix["rate_per_s"] * seconds))
    assert all(len(r) == n for r in runs)
    assert all(_pairs(r) == _pairs(runs[0]) for r in runs[1:])
    assert all(np.allclose(_gaps(r, seconds), _gaps(runs[0], seconds))
               for r in runs[1:])
    # the marginals are the stated quantiles, each once
    assert sorted(r["prompt"].size for r in runs[0]) == sorted(
        traffic.length_quantiles(mix["prompt_len"], n))
    assert sorted(r["max_new"] for r in runs[0]) == sorted(
        traffic.length_quantiles(mix["output_len"], n))
    if n > 3:
        order = lambda rs: [(r["prompt"].size, r["max_new"]) for r in rs]
        assert order(runs[1]) != order(runs[2])
        assert not np.array_equal(runs[1][0]["prompt"][:8],
                                  runs[2][0]["prompt"][:8]) \
            or runs[1][0]["prompt"].size != runs[2][0]["prompt"].size
    # still a pure function of (mix, seed, seconds)
    again = traffic.open_loop(mix, seeds[2], seconds, vocab)
    assert [r["due_s"] for r in again] == [r["due_s"] for r in runs[2]]
    assert all(np.array_equal(a["prompt"], b["prompt"])
               and a["max_new"] == b["max_new"]
               for a, b in zip(again, runs[2]))


@pytest.mark.parametrize("mix_name,vocab", DECODE_MIXES)
def test_two_seeds_offer_one_cycle_from_two_starting_points(mix_name, vocab):
    """The order of the requests and of the gaps is the mix's (``MIX_STREAM``
    of seed 0): a seed rotates both together, so every request keeps its
    neighbours and the gap behind it; the ids come from the seed's own
    stream as before."""
    mix = traffic.load(os.path.join(BENCH, "traffic", mix_name + ".json"))
    a = traffic.open_loop(mix, 77, 50.0, vocab)
    b = traffic.open_loop(mix, 3000039100, 50.0, vocab)
    n = len(a)

    def cycle(reqs):
        due = [r["due_s"] for r in reqs] + [50.0]
        return [(r["prompt"].size, r["max_new"], round(due[i + 1] - due[i], 9))
                for i, r in enumerate(reqs)]

    ca, cb = cycle(a), cycle(b)
    shifts = [k for k in range(n) if ca[k:] + ca[:k] == cb]
    assert shifts and shifts != [0], "not a rotation of one cycle"
    ids = traffic._rng(77, 1)
    first = ids.integers(1, vocab, a[0]["prompt"].size, dtype=np.int32)
    assert np.array_equal(first, a[0]["prompt"])


def test_lengths_follow_the_stated_distribution():
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.7, "min": 32,
            "max": 1024}
    vals = traffic.length_quantiles(spec, 1001)
    assert abs(float(np.median(vals)) - 256) <= 1
    assert vals.min() >= 32 and vals.max() <= 1024
    assert traffic.max_length(spec) == 1024
    assert set(traffic.length_quantiles({"dist": "fixed", "value": 9}, 5)) \
        == {9}


def test_shared_prefix_and_burstier_arrivals():
    mix = dict(traffic.load(CHAT), arrival_cv=2,
               shared_prefix={"group_size": 4, "tokens": 24})
    reqs = traffic.open_loop(mix, 3, 40.0, 1000)
    for g in range(0, len(reqs) - 3, 4):
        head = reqs[g]["prompt"][:24]
        assert all(np.array_equal(r["prompt"][:24], head)
                   for r in reqs[g:g + 4])
    gaps = np.diff([r["due_s"] for r in reqs])
    assert gaps.std() / gaps.mean() > 1.3


def test_train_feed_rows_all_differ():
    mix = traffic.load(os.path.join(BENCH, "traffic", "train_b128_feed.json"),
                       rehearse=True)
    a = traffic.train_feed(mix, 5, 4, 8, 10)
    b = traffic.train_feed(mix, 5, 4, 8, 10)
    assert len(a) == mix["distinct_batches"]
    assert a[0][0].shape == (mix["batch_per_chip"] * 4, 3, 8, 8)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    rows = np.concatenate([x for x, _y in a]).reshape(-1, 3 * 8 * 8)
    assert len({r.tobytes() for r in rows}) == rows.shape[0]


def test_percentiles_and_windows():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 90) == 90
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([5.0], 99) == 5.0
    assert stats.samples_beyond(100, 90) == 10
    assert stats.supported_tail(100) == 90.0
    assert stats.supported_tail(99) == 75.0
    assert stats.supported_tail(200) == 95.0
    assert stats.supported_tail(12) == 50.0
    assert stats.in_window(1.0, 1.0, 2.0) and not stats.in_window(3.0, 1.0, 2.0)
    assert abs(stats.iqr_share([10, 11, 12, 13, 14, 15]) - 3.5 / 12.5) < 1e-9
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_flops_and_peaks():
    import flops

    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "resnet50_v1_fp32.json")))
    per_image = flops.resnet_v1_train_flops_per_image(cfg["model"])
    # 3.858 G multiply-adds forward (stride on the first 1x1, MXNet v1)
    assert abs(per_image / 6 - 3.858e9) < 2e6
    assert flops.paged_decode_kv_bytes(100, 24, 32, 64, 4) == 2 * 100 * 24 * 2048 * 4
    assert flops.share_of_peak(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError):
        flops.share_of_peak(5.0, 4.0, "x")
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert all("source" in v for v in peaks.values())
