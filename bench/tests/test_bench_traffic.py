"""The traffic generator: same plan for the same seed, the same sizes for
every seed, and the stated length distributions."""

import math
import statistics

import bench_paths  # noqa: F401
import numpy as np
import pytest
from harness import traffic

CHAT = {"arrivals": "poisson",
        "prompt_len": {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 768},
        "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.7, "min": 16, "max": 256}}
BATCH = {"arrivals": "closed",
         "prompt_len": {"dist": "lognormal", "median": 48, "sigma": 0.6, "min": 16, "max": 128},
         "output_len": {"dist": "uniform", "min": 256, "max": 512}}
CHAT_ENGINE = {"n_slots": 8, "max_seq": 1024, "prefill_chunk": 64}
BATCH_ENGINE = {"n_slots": 32, "max_seq": 640, "prefill_chunk": 32}
BIG_SEED = 2**31 + 977


def _plan(t, load, seed, engine, seconds=40.0):
    return traffic.plan(t, load, seed=seed, seconds=seconds, vocab=151936, engine=engine)


@pytest.mark.parametrize("t,load,engine", [(CHAT, {"rate_per_s": 2.5}, CHAT_ENGINE),
                                           (BATCH, {"clients": 64}, BATCH_ENGINE)])
def test_same_seed_same_plan(t, load, engine):
    a, b = _plan(t, load, BIG_SEED, engine), _plan(t, load, BIG_SEED, engine)
    assert a.specs == b.specs and a.due_s == b.due_s
    c = _plan(t, load, BIG_SEED + 1, engine)
    assert c.specs != a.specs


@pytest.mark.parametrize("t,load,engine", [(CHAT, {"rate_per_s": 2.5}, CHAT_ENGINE),
                                           (BATCH, {"clients": 64}, BATCH_ENGINE)])
def test_every_seed_gets_the_same_sizes(t, load, engine):
    def lens(p):
        return (sorted(len(s["prompt"]) for s in p.specs),
                sorted(s["max_tokens"] for s in p.specs))

    a, b = _plan(t, load, 1, engine), _plan(t, load, BIG_SEED, engine)
    assert lens(a) == lens(b)
    if a.due_s is not None:
        assert len(a.due_s) == len(b.due_s) == 100
        assert a.due_s[0] == 0.0 and max(a.due_s) < 40.0
    assert all(1 <= t < 151936 for s in a.specs for t in s["prompt"])


def test_open_loop_rate_and_poisson_shape():
    p = _plan(CHAT, {"rate_per_s": 2.5}, 7, CHAT_ENGINE)
    gaps = np.diff(p.due_s)
    # exponential gaps: mean 1/rate, coefficient of variation about 1
    assert gaps.mean() == pytest.approx(1 / 2.5, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.15)


def test_lengths_match_the_stated_distributions():
    p = _plan(CHAT, {"rate_per_s": 25.0}, 3, CHAT_ENGINE)       # 1000 requests
    prompts = [len(s["prompt"]) for s in p.specs]
    outs = [s["max_tokens"] for s in p.specs]
    assert min(prompts) >= 32 and max(prompts) <= 768
    assert min(outs) >= 16 and max(outs) <= 256
    assert statistics.median(prompts) == pytest.approx(192, abs=1)
    assert statistics.median(outs) == pytest.approx(64, abs=1)
    # lognormal: the log-lengths' quartiles lie at median * exp(+-0.674 sigma)
    q1, _, q3 = statistics.quantiles(prompts, n=4)
    assert q1 == pytest.approx(192 * math.exp(-0.6745 * 0.8), rel=0.02)
    assert q3 == pytest.approx(192 * math.exp(0.6745 * 0.8), rel=0.02)
    b = _plan(BATCH, {"clients": 64}, 3, BATCH_ENGINE)
    outs = [s["max_tokens"] for s in b.specs]
    # 64 strata of 257 values each: the ends lie within one stratum of 256, 512
    assert 256 <= min(outs) <= 256 + 257 / 64 and 512 - 257 / 64 <= max(outs) <= 512
    assert statistics.mean(outs) == pytest.approx(384, abs=2)


def test_closed_loop_blocks_hold_the_same_sizes():
    p = _plan(BATCH, {"clients": 64}, 5, BATCH_ENGINE)
    assert p.clients == 64 and p.due_s is None
    blocks = [sorted(s["max_tokens"] for s in p.specs[i:i + 64])
              for i in range(0, len(p.specs), 64)]
    assert all(b == blocks[0] for b in blocks)


def test_a_mix_the_engine_would_truncate_is_refused():
    with pytest.raises(ValueError, match="do not fit"):
        _plan(CHAT, {"rate_per_s": 1.0}, 1, {"n_slots": 8, "max_seq": 512, "prefill_chunk": 64})
