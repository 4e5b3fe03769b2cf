"""Serving engine: continuous batching correctness, chunked prefill,
admission batching, done-condition off-by-one, cache bounds, sampling."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import build_model, get_arch, reduce_arch
from repro.core.amm import Mode
from repro.serving.engine import ServingEngine
from repro.serving.sampling import SamplingParams, sample_tokens


def _greedy_reference(bundle, params, prompt, n_tokens):
    """Single-request greedy decode, no engine."""
    caches = bundle.init_caches(1, 64, dtype=jnp.float32)
    toks = jnp.asarray([prompt], jnp.int32)
    logits, caches = bundle.forward_step(
        params, {"tokens": toks, "cache_len": jnp.zeros((1,), jnp.int32)},
        caches, compute_dtype=jnp.float32,
    )
    out = [int(jnp.argmax(logits[0, len(prompt) - 1]))]
    pos = len(prompt)
    while len(out) < n_tokens:
        logits, caches = bundle.forward_step(
            params, {"tokens": jnp.asarray([[out[-1]]], jnp.int32),
                     "cache_len": jnp.full((1,), pos, jnp.int32)},
            caches, compute_dtype=jnp.float32,
        )
        out.append(int(jnp.argmax(logits[0, 0])))
        pos += 1
    return out


def _small_bundle(key, n_layers=2):
    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=n_layers)
    bundle = build_model(arch, Mode.DENSE)
    return bundle, bundle.init(key)


def test_engine_matches_single_request(key):
    bundle, params = _small_bundle(key)
    prompts = [[3, 5, 7], [11, 13, 17, 19, 23], [2, 4]]
    refs = [_greedy_reference(bundle, params, p, 5) for p in prompts]

    eng = ServingEngine(bundle, params, n_slots=2, max_seq=64, prefill_chunk=4)
    for p in prompts:
        eng.submit(p, max_tokens=5)
    done = sorted(eng.run_until_done(), key=lambda r: r.rid)
    assert len(done) == 3
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)


def test_chunked_prefill_matches_reference(key):
    """Prompts LONGER than prefill_chunk go through the multi-chunk loop and
    must still be token-identical to the single-shot reference."""
    bundle, params = _small_bundle(key)
    prompts = [[3, 5, 7, 9, 11, 13, 17, 19, 23, 29, 31],    # 11 tokens, chunk 4
               list(range(2, 2 + 9))]                        # 9 tokens
    refs = [_greedy_reference(bundle, params, p, 4) for p in prompts]
    eng = ServingEngine(bundle, params, n_slots=2, max_seq=64, prefill_chunk=4)
    for p in prompts:
        eng.submit(p, max_tokens=4)
    done = sorted(eng.run_until_done(), key=lambda r: r.rid)
    for r, ref in zip(done, refs):
        assert r.out_tokens == ref, (r.rid, r.out_tokens, ref)
    # 11 -> 3 chunks, 9 -> 3 chunks, admitted together: chunks shared
    assert eng.stats()["prefill_forwards"] == 3


def test_batched_admission_single_prefill_forward(key):
    """k>1 requests admitted in one step share exactly ONE prefill forward."""
    bundle, params = _small_bundle(key)
    eng = ServingEngine(bundle, params, n_slots=3, max_seq=64, prefill_chunk=8)
    for p in ([1, 2, 3], [4, 5], [6, 7, 8]):      # all fit one chunk
        eng.submit(p, max_tokens=3)
    done = eng.run_until_done()
    assert len(done) == 3
    st = eng.stats()
    assert st["prefill_forwards"] == 1
    assert st["prefill_tokens"] == 8              # valid tokens, not padding


def test_max_tokens_one_returns_one_token(key):
    """The prefill-produced token counts toward max_tokens (off-by-one fix)."""
    bundle, params = _small_bundle(key, n_layers=1)
    ref = _greedy_reference(bundle, params, [1, 2, 3], 1)
    eng = ServingEngine(bundle, params, n_slots=1, max_seq=64, prefill_chunk=4)
    eng.submit([1, 2, 3], max_tokens=1)
    done = eng.run_until_done()
    assert len(done) == 1
    assert done[0].out_tokens == ref              # exactly 1 token
    assert eng.stats()["decode_forwards"] == 0    # never entered decode


def test_eos_on_prefill_token(key):
    """EOS hit by the very first (prefill-sampled) token retires immediately."""
    bundle, params = _small_bundle(key, n_layers=1)
    ref = _greedy_reference(bundle, params, [1, 2, 3], 1)
    eng = ServingEngine(bundle, params, n_slots=1, max_seq=64, prefill_chunk=4)
    eng.submit([1, 2, 3], max_tokens=8, eos_id=ref[0])
    done = eng.run_until_done()
    assert done[0].out_tokens == ref
    assert eng.stats()["decode_forwards"] == 0


def test_engine_eos_stops(key):
    bundle, params = _small_bundle(key, n_layers=1)
    eng = ServingEngine(bundle, params, n_slots=1, max_seq=64, prefill_chunk=4)
    ref = _greedy_reference(bundle, params, [1, 2, 3], 8)
    eos = ref[2]                       # will be hit on the 3rd generated token
    eng.submit([1, 2, 3], max_tokens=8, eos_id=eos)
    done = eng.run_until_done()
    assert done[0].out_tokens[-1] == eos
    assert len(done[0].out_tokens) <= 8


def test_overlong_prompt_rejected(key):
    bundle, params = _small_bundle(key, n_layers=1)
    eng = ServingEngine(bundle, params, n_slots=1, max_seq=8, prefill_chunk=4,
                        autotune_lut=False)
    with pytest.raises(ValueError):
        eng.submit(list(range(9)))                # 9 > max_seq=8
    with pytest.raises(ValueError):
        eng.submit([1], max_tokens=0)
    # an exactly-fitting prompt (pads to 8 == max_seq) is accepted
    eng.submit(list(range(1, 8)), max_tokens=1)
    done = eng.run_until_done()
    assert len(done) == 1 and len(done[0].out_tokens) == 1


def test_chunk_padded_prompt_rejected_and_max_tokens_capped(key):
    bundle, params = _small_bundle(key, n_layers=1)
    eng = ServingEngine(bundle, params, n_slots=1, max_seq=6, prefill_chunk=4,
                        autotune_lut=False)
    # 5 tokens pad to 8 > max_seq=6: the padded writes would be dropped at
    # the cache boundary, so submit must refuse
    with pytest.raises(ValueError):
        eng.submit(list(range(1, 6)))
    # 4 tokens fit exactly; max_tokens is capped to remaining cache
    # (max_seq - len + 1 = 3) instead of silently overflowing
    eng.submit([1, 2, 3, 4], max_tokens=100)
    done = eng.run_until_done()
    assert len(done) == 1
    assert len(done[0].out_tokens) == 3


def test_seeded_sampling_deterministic(key):
    """Same seed => identical tokens across runs and slot placements."""
    bundle, params = _small_bundle(key, n_layers=1)
    sp = SamplingParams(temperature=0.9, top_k=40, top_p=0.95, seed=123)

    def run(n_slots, extra_first):
        eng = ServingEngine(bundle, params, n_slots=n_slots, max_seq=64,
                            prefill_chunk=4, autotune_lut=False)
        if extra_first:        # perturb slot placement / batch composition
            eng.submit([9, 8, 7], max_tokens=6,
                       sampling=SamplingParams(temperature=0.9, seed=7))
        eng.submit([1, 2, 3, 4, 5], max_tokens=6, sampling=sp)
        done = sorted(eng.run_until_done(), key=lambda r: r.rid)
        return done[-1].out_tokens

    a = run(1, False)
    b = run(2, True)
    assert a == b
    assert len(a) == 6


def test_sampler_filters_reduce_to_greedy(key):
    """top_k=1 and tiny top_p must pick the argmax at any temperature."""
    logits = jax.random.normal(key, (3, 33))
    ref = np.asarray(jnp.argmax(logits, axis=-1))
    B = logits.shape[0]
    f32 = lambda v: jnp.full((B,), v, jnp.float32)
    i32 = lambda v: jnp.full((B,), v, jnp.int32)
    topk1 = sample_tokens(logits, f32(5.0), i32(1), f32(1.0), i32(0), i32(0))
    topp0 = sample_tokens(logits, f32(5.0), i32(0), f32(1e-6), i32(3), i32(1))
    greedy = sample_tokens(logits, f32(0.0), i32(0), f32(1.0), i32(9), i32(2))
    np.testing.assert_array_equal(np.asarray(topk1), ref)
    np.testing.assert_array_equal(np.asarray(topp0), ref)
    np.testing.assert_array_equal(np.asarray(greedy), ref)


def test_invalid_sampling_params():
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)


def test_stats_counters(key):
    bundle, params = _small_bundle(key, n_layers=1)
    eng = ServingEngine(bundle, params, n_slots=2, max_seq=64, prefill_chunk=4,
                        autotune_lut=False)
    eng.submit([1, 2, 3], max_tokens=4)
    eng.submit([4, 5, 6], max_tokens=4)
    eng.run_until_done()
    st = eng.stats()
    assert st["prefill_tokens"] == 6
    assert st["decode_tokens"] == 6               # 3 post-prefill tokens x 2
    assert st["decode_occupancy"] == 1.0          # both slots every decode step
    assert st["prefill_s"] > 0 and st["decode_s"] > 0
    # 1 prefill + 3 decode forwards over 2 shapes: 2 misses, 2 hits
    assert st["shape_cache_hits"] == st["prefill_forwards"] + st["decode_forwards"] - 2


@pytest.mark.parametrize("use_kernel", [False, True], ids=["xla", "kernel"])
def test_prefill_logits_match_train_logits(key, use_kernel):
    """The engine's served prefill forward agrees with the uncached
    training forward on the same LUT_INFER params, for the XLA one-hot
    path and the (interpret-mode) kernel path; engine state is untouched."""
    arch = reduce_arch(get_arch("qwen3_1p7b"), n_layers=2,
                       lut_use_kernel=use_kernel)
    bundle = build_model(arch, Mode.LUT_INFER)
    params = bundle.init(key)
    ref_bundle = build_model(reduce_arch(get_arch("qwen3_1p7b"), n_layers=2),
                             Mode.LUT_INFER)
    prompt = [5, 17, 3, 99, 42, 7]
    toks = jnp.asarray([prompt], jnp.int32)
    ref, _ = ref_bundle.train_logits(params, {"tokens": toks, "labels": toks},
                                     compute_dtype=jnp.float32)
    eng = ServingEngine(bundle, params, n_slots=2, max_seq=32, prefill_chunk=8)
    got = eng.prefill_logits(prompt)
    assert got.shape == (len(prompt), arch.vocab)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref[0]), atol=1e-4)
    assert not eng.has_work() and eng.stats()["prefill_forwards"] == 0
    with pytest.raises(ValueError):
        eng.prefill_logits(list(range(1, 10)))          # longer than one chunk


# --- the cache write: a forward writes only the rows it adds, in place --------

def _model(mode):
    """Three layers: the LUT plan's dense first layer is a segment of its
    own, so both a one-layer and a multi-layer segment are written."""
    bundle = build_model(reduce_arch(get_arch("qwen3_1p7b"), n_layers=3), mode)
    return bundle, bundle.init(jax.random.PRNGKey(1))


def _slot_rows(eng, slot):
    """Host copies of every cached row of one slot, per cache leaf."""
    return [np.array(leaf[:, slot]) for leaf in jax.tree.leaves(eng.caches)]


def _same(a, b):
    return all(np.array_equal(x, y) for x, y in zip(a, b))


@pytest.mark.parametrize("mode", [Mode.DENSE, Mode.LUT_INFER])
def test_a_forward_leaves_the_rows_of_slots_it_does_not_advance(mode):
    eng = ServingEngine(*_model(mode), n_slots=3, max_seq=32, prefill_chunk=4,
                        autotune_lut=False)
    # two one-token requests retire in the first step and leave their rows
    # behind; the third decodes on
    for prompt, n in (([3, 5, 7], 1), ([2, 4, 6, 8], 1), ([9, 11], 8)):
        eng.submit(prompt, max_tokens=n)
    eng.step()
    eng.submit(list(range(1, 8)), max_tokens=4)        # two chunks
    eng._admit()
    prefilling, idle, decoding = 0, 1, 2
    assert eng.slots[idle] is None and not eng.slots[prefilling].prefill_done
    assert eng.slots[decoding].prefill_done
    rows = [_slot_rows(eng, i) for i in range(3)]
    assert any(np.any(r != 0) for r in rows[idle])     # what it held stays

    eng._prefill_step()                  # advances the prefilling slot only
    after = [_slot_rows(eng, i) for i in range(3)]
    assert _same(after[idle], rows[idle]) and _same(after[decoding], rows[decoding])
    assert not _same(after[prefilling], rows[prefilling])

    rows = after
    eng._decode_step()                   # advances the decoding slot only
    after = [_slot_rows(eng, i) for i in range(3)]
    assert _same(after[idle], rows[idle]) and _same(after[prefilling], rows[prefilling])
    assert not _same(after[decoding], rows[decoding])


@pytest.mark.parametrize("tokens_per_row", [1, 4])
@pytest.mark.parametrize("arch_id", ["qwen3_1p7b", "mamba2_370m", "zamba2_1p2b", "whisper_tiny"])
def test_every_cache_family_writes_only_the_masked_slots(arch_id, tokens_per_row):
    """forward_step with a slot mask: attention KV, SSM state and cross-KV
    leaves of the slot it leaves out come back bit-identical; the kept
    slot's are written."""
    arch = reduce_arch(get_arch(arch_id))
    bundle = build_model(arch, Mode.DENSE)
    params = bundle.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(0)
    caches = jax.tree.map(lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
                          bundle.init_caches(2, 16, dtype=jnp.float32))
    before = [np.array(a) for a in jax.tree.leaves(caches)]
    batch = {"tokens": jnp.full((2, tokens_per_row), 3, jnp.int32),
             "cache_len": jnp.asarray([4, 4], jnp.int32),
             "slot_mask": jnp.asarray([True, False])}
    if arch.family == "audio":
        batch["frames"] = jnp.ones((2, arch.enc_frames, arch.d_model))
    _, caches = bundle.forward_step(params, batch, caches, compute_dtype=jnp.float32)
    after = [np.array(a) for a in jax.tree.leaves(caches)]
    assert all(np.array_equal(a[:, 1], b[:, 1]) for a, b in zip(after, before))
    assert all(not np.array_equal(a[:, 0], b[:, 0]) for a, b in zip(after, before))


@pytest.mark.parametrize("past", [0, 2], ids=["ends_at_max_seq", "runs_past_it"])
def test_a_chunk_at_the_end_of_the_cache_writes_only_its_own_rows(past):
    """A chunk whose rows run past max_seq drops those rows; the ones that
    fit land at their own positions, never shifted back onto valid rows."""
    eng = ServingEngine(*_model(Mode.DENSE), n_slots=2, max_seq=16, prefill_chunk=4,
                        autotune_lut=False)
    rng = np.random.default_rng(0)
    eng.caches = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype), eng.caches)
    before = [np.array(a) for a in jax.tree.leaves(eng.caches)]
    start = eng.max_seq - eng.prefill_chunk + past
    toks = jnp.asarray([[5, 6, 7, 8], [1, 2, 3, 4]], jnp.int32)
    _, eng.caches = eng._step_fn(eng.params, toks, jnp.asarray([start, 3], jnp.int32),
                                 eng.caches, jnp.asarray([True, False]))
    for b, a in zip(before, (np.array(x) for x in jax.tree.leaves(eng.caches))):
        assert np.array_equal(a[:, 1], b[:, 1])                    # slot left out
        assert np.array_equal(a[:, 0, :start], b[:, 0, :start])    # rows before it
        assert np.all(a[:, 0, start:] != b[:, 0, start:])          # its own rows


@pytest.mark.parametrize("mode", [Mode.DENSE, Mode.LUT_INFER])
def test_caches_after_many_steps_equal_the_select_merge_path(mode):
    """The engine's caches and tokens after a run equal those of a step that
    writes every row and then keeps the masked slots' rows by a select over
    the whole cache."""
    bundle, params = model = _model(mode)

    def merged_step(params, tokens, cache_len, caches, mask):
        logits, new = bundle.forward_step(
            params, {"tokens": tokens, "cache_len": cache_len}, caches,
            compute_dtype=jnp.float32)
        return logits, jax.tree.map(
            lambda o, n: jnp.where(mask.reshape((1, -1) + (1,) * (o.ndim - 2)), n, o),
            caches, new)

    def run(step=None):
        eng = ServingEngine(*model, n_slots=3, max_seq=32, prefill_chunk=4,
                            autotune_lut=False)
        if step is not None:
            eng._step_fn = jax.jit(step)
        for i, prompt in enumerate(([3, 5, 7, 9, 11, 13], [2, 4], [6, 8, 10, 12, 14],
                                    [1], [7, 7, 7, 7, 7, 7, 7, 7, 7])):
            eng.submit(prompt, max_tokens=3 + 2 * i)
        done = eng.run_until_done()
        return ({r.rid: r.out_tokens for r in done},
                [np.array(a) for a in jax.tree.leaves(eng.caches)])

    tokens, caches = run()
    ref_tokens, ref_caches = run(merged_step)
    assert tokens == ref_tokens
    assert _same(caches, ref_caches)


def test_a_forward_that_loses_its_donated_caches_leaves_the_engine_usable():
    """A step that hands back the caches it consumed leaves the engine no
    rows to serve from: the next forward fails, the live requests retire as
    "error", and the engine goes on from empty caches."""
    eng = ServingEngine(*_model(Mode.DENSE), n_slots=2, max_seq=32, prefill_chunk=4,
                        autotune_lut=False)
    step = eng._step_fn

    def stale(params, tokens, cache_len, caches, mask):
        logits, _ = step(params, tokens, cache_len, caches, mask)
        return logits, caches                   # deleted: the step consumed them
    eng._step_fn = stale
    eng.submit([1, 2, 3], max_tokens=4)
    with pytest.raises(RuntimeError, match="deleted"):
        eng.step()
    assert [r.status for r in eng.finished] == ["error"] and not eng.has_work()
    assert not any(np.any(np.array(leaf)) for leaf in jax.tree.leaves(eng.caches))

    eng._step_fn = step
    eng.submit([1, 2, 3], max_tokens=2)
    assert eng.run_until_done()[-1].ok
