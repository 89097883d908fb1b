"""The generators give the same schedule for the same seed and cell file, a
fixed amount of work whatever the seed, and report their distribution."""
import numpy as np
import pytest

from benchmark import spec
from benchmark.generators import open_loop_serving as serving
from benchmark.generators import train_job

CHAT = spec.load_traffic("chat-steady")
LONG = spec.load_traffic("longprompt-steady")


@pytest.mark.parametrize("traffic", [CHAT, LONG])
@pytest.mark.parametrize("arrivals", ["poisson", "jittered"])
def test_same_seed_same_schedule(traffic, arrivals):
    t = {**traffic, "arrivals": arrivals}
    a = serving.generate(t, 7, 20.0, 92544)
    b = serving.generate(t, 7, 20.0, 92544)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.max_new,
                                                           b.max_new)
    assert all(np.array_equal(p, q) for p, q in zip(a.prompts, b.prompts))
    c = serving.generate(t, 8, 20.0, 92544)
    assert not np.array_equal(a.due, c.due)


@pytest.mark.parametrize("traffic", [CHAT, LONG])
def test_work_is_fixed_across_seeds_and_inside_the_clip(traffic):
    scheds = [serving.generate(traffic, s, 30.0, 92544) for s in (1, 2, 3)]
    n = round(traffic["rate_per_s"] * 30.0)
    for s in scheds:
        assert len(s.prompts) == n == s.due.size == s.max_new.size
        assert np.all(np.diff(s.due) >= 0) and 0 <= s.due[0] \
            and s.due[-1] < 30.0
        plen = np.array([p.size for p in s.prompts])
        assert plen.min() >= traffic["prompt_tokens"]["min"]
        assert plen.max() <= traffic["prompt_tokens"]["max"]
        assert s.max_new.min() >= traffic["output_tokens"]["min"]
        assert s.max_new.max() <= traffic["output_tokens"]["max"]
        assert all(p.min() >= 1 and p.max() < 92544 for p in s.prompts)
    # the same multiset of lengths in every run: the offered load is equal
    d0 = scheds[0].describe()
    for s in scheds[1:]:
        d = s.describe()
        assert d["prompt_tokens"] == d0["prompt_tokens"]
        assert d["output_tokens"] == d0["output_tokens"]
    # and the distribution is the file's: the median is its median
    assert d0["prompt_tokens"]["p50"] == pytest.approx(
        traffic["prompt_tokens"]["median"], rel=0.03)
    assert d0["output_tokens"]["p50"] == pytest.approx(
        traffic["output_tokens"]["median"], rel=0.03)


def test_buckets_and_warm_prompts_cover_the_mix():
    assert serving.buckets_reached(CHAT) == [32, 64, 128, 256, 512, 1024]
    assert serving.buckets_reached(LONG) == [1024, 2048, 4096, 8192]
    warm = serving.warm_prompts(LONG, 3, 92544)
    assert [p.size for p in warm] == [1024, 2048, 4096, 8192]


def test_latency_metrics_time_from_due_and_count_failures():
    recs = [
        {"due": 1.0, "state": "done", "tokens": 11, "asked": 11,
         "t_first": 1.5, "t_done": 2.5},
        {"due": 2.0, "state": "done", "tokens": 5, "asked": 5,
         "t_first": 2.1, "t_done": 2.5},
        {"due": 3.0, "state": "running", "tokens": 2, "asked": 9,
         "t_first": 3.2, "t_done": None},
    ]
    out = serving.latency_metrics(recs, 10.0)
    assert out["attempted"] == 3 and out["failed"] == 1
    assert out["ttft_p50_s"] == pytest.approx(0.3)      # from DUE: 0.5, 0.1
    assert out["ttft_p90_s"] == pytest.approx(0.46)
    assert out["tpot_p50_s"] == pytest.approx(0.1)
    assert out["serve_tokens_per_s"] == pytest.approx(1.6)
    assert serving.attainment(recs, 0.2, 0.2) == pytest.approx(1 / 3)


def test_train_job_is_seeded():
    t = spec.load_traffic("train-4k")
    x, y = train_job.generate(t, 5, 2, 64, 32768)
    x2, y2 = train_job.generate(t, 5, 2, 64, 32768)
    assert x.shape == (16, 64) and y.shape == (16, 64, 1)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    assert np.array_equal(x[:, 1:], y[:, :-1, 0])       # next-token labels
    assert not np.array_equal(x, train_job.generate(t, 6, 2, 64, 32768)[0])
