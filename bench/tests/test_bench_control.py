"""The comparison that decides `correct`, and its control, at a size a test
run holds: the reference's own greedy tokens read a gap of 0, an altered
token reads its gap, cached rows read their error, and the control (the
reference at Precision.HIGH) is read on the same sequences."""

import json

import bench_paths  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_paths import BENCH, FIXTURES
from harness import check, family

from reference import qwen3

HIGHEST = jax.lax.Precision.HIGHEST


@pytest.fixture(scope="module", params=["tiny-lut", "tiny-dense"])
def ref(request):
    cfg = json.loads((FIXTURES / "configs" / f"{request.param}.json").read_text())
    model = family.load(cfg, BENCH)
    # the kernel path's m-shared scale layout
    shapes = {s.name: (1, 1, s.d_out) for s in model.layers()[1].sites}
    return check.Reference(model, 2**31 + 11, shapes, pad_len=64)


def _greedy(ref, prompt, n):
    """The reference's own greedy continuation of `prompt`."""
    seq = list(prompt)
    for _ in range(n):
        hf = ref.forward([seq], (HIGHEST,))[HIGHEST][0]
        lg = qwen3.logits(ref.g, hf[: len(seq)], HIGHEST)
        seq.append(int(jnp.argmax(lg[-1])))
    return seq[len(prompt):]


def _reference_kv(ref, seqs):
    """The reference's own cache rows of `seqs`, as a program would hold
    them: {"k", "v"}: (layers, seqs, pad_len, kv, dh)."""
    rows = {"k": {}, "v": {}}

    def on_layer(i, j, kv):
        for name, r in kv[HIGHEST].items():
            rows[name][i, j] = r
    ref.forward(seqs, (HIGHEST,), on_layer)
    n_layers = len(ref.lut)
    return {name: jnp.stack([jnp.stack([r[i, j] for j in range(len(seqs))])
                             for i in range(n_layers)]) for name, r in rows.items()}


def test_reference_tokens_read_zero_and_an_altered_token_does_not(ref):
    rng = np.random.default_rng(0)
    prompt = rng.integers(1, 256, 9).tolist()
    toks = _greedy(ref, prompt, 5)
    req = {"idx": 0, "prompt": prompt, "tokens": toks}
    assert check.check_served(ref, [req], [], None)[0]["gap"] == 0.0
    bad = dict(req, tokens=toks[:2] + [(toks[2] + 1) % 256] + toks[3:])
    assert check.check_served(ref, [bad], [], None)[0]["gap"] > 1e-3


def test_cached_rows_read_their_error(ref):
    rng = np.random.default_rng(2)
    seqs = [rng.integers(1, 256, 30).tolist(), rng.integers(1, 256, 12).tolist()]
    kv = _reference_kv(ref, seqs)
    nums, stats = check.check_served(ref, [], seqs, kv)
    lut = any(ref.lut)
    assert set(nums) == ({"kv_dense", "kv1_share", "kv_jump"} if lut else {"kv_dense"})
    assert all(x == 0.0 for x in nums.values())
    assert stats.rows.sum() == 2 * len(ref.lut) * (30 + 12) * ref.cfg["num_key_value_heads"]
    # one row off by 1% in each of layers 0 and 1; past the valid length
    # nothing counts
    bad = dict(kv, k=kv["k"].at[0, 1, 5, 0].multiply(1.01).at[0, 1, 40].set(7.0)
               .at[1, 0, 3, 0].multiply(1.01))
    nums, _ = check.check_served(ref, [], seqs, bad)
    assert nums["kv_dense"] == pytest.approx(0.01, rel=1e-3)
    if lut:
        assert nums["kv1_share"] == pytest.approx(1 / stats.rows[1])
        assert nums["kv_jump"] == pytest.approx(1 / stats.rows[1])


def test_kv_jump_is_the_largest_rise_of_the_share_off():
    stats = check.KVStats([False, True, True, True])
    stats.rows[:] = 1000
    stats.over[:] = [0, 10, 40, 400]
    stats.max[:] = [2e-7, 0.3, 0.3, 0.3]
    nums = stats.numbers()
    assert nums == {"kv_dense": 2e-7, "kv1_share": pytest.approx(0.01),
                    "kv_jump": pytest.approx(0.36)}
    # a share that falls reads no rise
    stats.over[:] = [0, 500, 20, 30]
    assert stats.numbers()["kv_jump"] == pytest.approx(0.5)
    dense = check.KVStats([False] * 3)
    dense.rows[:] = 10
    dense.max[:] = [1e-7, 3e-6, 2e-7]
    assert dense.numbers() == {"kv_dense": 3e-6}


def test_control_reads_every_position(ref):
    rng = np.random.default_rng(1)
    reqs = [{"idx": i, "prompt": rng.integers(1, 256, 20).tolist(),
             "tokens": rng.integers(1, 256, 8).tolist()} for i in range(3)]
    seqs = [rng.integers(1, 256, 16).tolist()]
    same, _ = check.control(ref, reqs, seqs, HIGHEST)
    assert set(same) >= {"gap", "kv_dense"} and all(x == 0.0 for x in same.values())
    # on the CPU every precision computes in float32: the control reads a
    # number at each, which the chip's lower precision moves
    lower, _ = check.control(ref, reqs, seqs)
    assert np.isfinite(lower["gap"]) and lower["kv_dense"] >= 0.0


def test_sample_holds_the_longest_and_some_hundreds_of_tokens():
    fin = [{"idx": i, "prompt": [1] * (10 + i), "tokens": [2] * 40} for i in range(30)]
    s = check.sample(fin, 3)
    assert s[0]["idx"] == 29
    assert sum(len(r["tokens"]) for r in s) >= check.MIN_SERVED
    assert check.sample(fin, 3) == s and check.sample([], 3) == []
