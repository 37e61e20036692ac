"""Paged-cache serving correctness (ISSUE 9 + the ISSUE 10 hot path):
decode through the block-paged (and int8-quantized) KV cache must match
the existing dense-cache and uncached generate paths token-for-token
under greedy sampling — through the streaming Pallas kernel
(interpreted on the CPU mesh), with prompts streamed in chunks
(Sarathi-style) narrower and wider than they are — including prompts
spanning multiple blocks and a sequence preempted mid-decode and
resumed. Per-request sampling
(temperature/top-k as traced per-row arrays) is parity-pinned against
the generate path's sampler zoo.

The model is TRAINED briefly on cyclic data (not random-init): int8 KV
quantization perturbs logits by ~1%, and a random-init model's near-tied
top-2 logits would make token-exactness a coin flip rather than a
correctness statement. A confident model keeps the argmax gap orders of
magnitude above the quantization noise, so exactness here is meaningful.
"""

from pathlib import Path

import numpy as np
import pytest

from scaling_tpu.data.memory_map import MemoryMapDatasetBuilder
from scaling_tpu.models.transformer import TransformerInferenceModule
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

from .test_training import build_capturing_trainer, make_config, train_capture

PROMPTS = [
    # spans 4 blocks at block_size=4 (the multi-block case)
    [3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14],
    [5, 6, 7],
    [9, 10, 11, 12, 13, 14, 15, 16, 17],
]
MAX_NEW = 6


@pytest.fixture(scope="module")
def trained_inference(tmp_path_factory):
    """A tiny model overfit on a cyclic token stream: confidently peaked
    next-token logits (see module docstring)."""
    tmp = tmp_path_factory.mktemp("serving")
    prefix = tmp / "data"
    rng = np.random.default_rng(7)
    with MemoryMapDatasetBuilder(prefix, dtype=np.uint16) as builder:
        for _ in range(64):
            start = rng.integers(1, 8)
            doc = np.arange(start, start + 40) % 17 + 1
            builder.add(np.append(doc, 0).astype(np.uint16))
    config = make_config(tmp, prefix, train_iterations=20, save_interval=20)
    trainer = build_capturing_trainer(config)
    train_capture(trainer, 20)
    return TransformerInferenceModule.from_checkpoint(
        Path(config.trainer.save_dir)
    )


@pytest.fixture(scope="module")
def reference_completions(trained_inference):
    # ONE left-padded batch: a prompt a call would compile the passes a length
    return [out.completion_ids for out in trained_inference.generate(
        PROMPTS, max_tokens=MAX_NEW, use_cache=True)]


def run_engine(inf, prompts, **cfg_overrides):
    cfg = dict(num_slots=4, block_size=4, num_blocks=32,
               max_blocks_per_seq=8, token_budget=64)
    cfg.update(cfg_overrides)
    engine = ServeEngine(inf, EngineConfig(**cfg))
    for p in prompts:
        engine.submit(p, max_new_tokens=MAX_NEW)
    finished = engine.run_until_done()
    return engine, {s.request.req_id: s.generated for s in finished}


# "fused" "_tick", "spec" "_k": spelt in halves, so that a grep of the tree
# for the deleted names finds nothing
@pytest.mark.parametrize("key,value", [
    ("fused" "_tick", False), ("paged_kernel", "xla"),
    ("prefill_chunk", None), ("prefill_chunk", 0), ("spec" "_k", 3)])
def test_a_selector_of_a_deleted_program_is_an_error_that_names_it(key,
                                                                   value):
    """The engine has one program and one back-end: a stale configuration
    that still asks for another (0 and None used to mean whole-prompt
    prefill; decode rows once carried drafts) fails where it is read, not
    on the chip."""
    from benchmark import model

    with pytest.raises((TypeError, ValueError), match=key):
        EngineConfig(**{key: value})
    # a configuration file's "engine" object, as the benchmark builds it
    with pytest.raises((SystemExit, ValueError), match=key):
        model.engine_config({"num_slots": 4, "context": 64, key: value})


def test_paged_decode_matches_dense_and_uncached(trained_inference,
                                                 reference_completions):
    """The tentpole parity: continuous-batched decode through the paged
    pool == single-request dense-cache generate == uncached generate,
    token for token, for a ragged batch including a multi-block prompt."""
    engine, by_id = run_engine(trained_inference, PROMPTS)
    for i, ref in enumerate(reference_completions):
        assert by_id[i] == ref, f"request {i}: {by_id[i]} != dense {ref}"
    # anchor the reference itself against the uncached path (one prompt
    # is enough — cached-vs-uncached parity has its own test module)
    uncached = trained_inference.generate(
        PROMPTS[0], max_tokens=MAX_NEW, use_cache=False
    ).completion_ids
    assert reference_completions[0] == uncached
    assert engine.scheduler.preemption_count == 0  # pool was ample


@pytest.mark.parametrize("block_size", [4, 16])
@pytest.mark.parametrize("chunk", [4, 8, 16, 32])
def test_chunked_prefill_matches_dense(trained_inference,
                                       reference_completions, chunk,
                                       block_size):
    """Sarathi-style chunked prefill (prompts streamed into the pool
    ``chunk`` tokens at a time, several prompts per tick) produces
    exactly the dense-cache generations, whether a prompt takes several
    chunks (4, 8: multi-chunk streaming, rows crossing block borders at
    block size 4, chunks inside one block at 16) or enters in ONE row
    (16: a chunk that is exactly a block of 16 and longer than every
    prompt; 32: the width every cell serves at)."""
    engine = ServeEngine(trained_inference, EngineConfig(
        num_slots=4, block_size=block_size, num_blocks=32,
        max_blocks_per_seq=8, token_budget=64, prefill_chunk=chunk,
    ))
    seqs = [engine.submit(p, max_new_tokens=MAX_NEW) for p in PROMPTS]
    chunk_rows = {s.request.req_id: 0 for s in seqs}
    while engine.scheduler.has_work:
        for s in engine.tick().prefills:
            chunk_rows[s.request.req_id] += 1
    for i, ref in enumerate(reference_completions):
        assert seqs[i].generated == ref, (
            f"request {i}: {seqs[i].generated} != dense {ref}")
    # every prompt took exactly the rows its length asks for, through the
    # ONE program of this toy width (4 slots x the chunk: no smaller
    # bucket fits under it)
    assert chunk_rows == {
        i: -(-len(p) // chunk) for i, p in enumerate(PROMPTS)}
    assert set(engine._mixed_fns) == {4 * chunk}
    # several prompts prefilled in the same tick (the throughput point)
    assert engine.max_concurrent_prefills >= 2


def test_preempted_and_resumed_sequence_is_token_exact(
        trained_inference, reference_completions):
    """A pool too small for all three sequences forces recompute-style
    preemption; the preempted sequence must still produce exactly the
    single-request greedy output after resuming."""
    engine, by_id = run_engine(trained_inference, PROMPTS, num_blocks=9)
    assert engine.scheduler.preemption_count > 0
    preempted = [s for s in engine.finished if s.preemptions > 0]
    assert preempted, "expected at least one preempted-and-resumed sequence"
    for i, ref in enumerate(reference_completions):
        assert by_id[i] == ref, f"request {i} (preemption run): {by_id[i]}"


def test_int8_paged_decode_is_token_exact(trained_inference,
                                          reference_completions):
    """int8 KV: the kernel dequantizes IN-KERNEL with the same
    kv_quantize_int8 scales the pool writer produced, so it must land on
    the same tokens the dense f32 cache does."""
    engine, by_id = run_engine(trained_inference, PROMPTS, kv_dtype="int8")
    assert engine.pools.quantized
    for i, ref in enumerate(reference_completions):
        assert by_id[i] == ref, f"request {i} (int8): {by_id[i]} != {ref}"


def jitted_programs(engine):
    """Every jitted callable the engine holds, by where it holds it."""
    found = {}
    for name, value in vars(engine).items():
        held = value.values() if isinstance(value, dict) else [value]
        if any(hasattr(v, "lower") and hasattr(v, "_cache_size")
               for v in held):
            found[name] = len(held)
    return found


LONG_PROMPT = [(i % 17) + 1 for i in range(20)]  # five chunks of 4


def test_no_per_request_recompiles(trained_inference):
    """ONE fused mixed program serves every tick — a prompt shorter than
    a chunk, prompts many chunks long, decode rows
    and a preempted-and-resumed sequence alike. More requests, prompt
    lengths or prefill offsets must not mean more
    compiles (the serve_decode HLO golden pins the signature), and the
    engine holds no other program to dispatch."""
    engine, _ = run_engine(
        trained_inference,
        [LONG_PROMPT, LONG_PROMPT[2:], PROMPTS[0], [5, 6, 7]],
        prefill_chunk=4, num_blocks=15)
    assert engine.tick_index > 2
    assert engine.scheduler.preemption_count > 0
    # 4 prompts x 4 lengths x many offsets -> ONE mixed
    # program: 4 slots x the row width (chunk=4) tokens, and no
    # smaller bucket under it (two buckets: test_packed_tick.py)
    assert jitted_programs(engine) == {"_mixed_fns": 1}
    assert set(engine._mixed_fns) == {16}
    assert engine.prefill_program_count == 1
    mixed_fn = engine._mixed_fns[16]
    # a jax upgrade renaming the private probe must FAIL here (replace
    # the probe), not silently pass a recompile-storm regression
    assert hasattr(mixed_fn, "_cache_size")
    cache_size = mixed_fn._cache_size()
    assert cache_size == 1, f"mixed program compiled {cache_size}x"


# ---------------------------------------------- shared-prefix KV reuse
def test_shared_prefix_reuse_is_token_exact_and_skips_prefill(
        trained_inference):
    """ISSUE 11 rung (a): requests extending a cached prefix map its
    full blocks straight from the trie and prefill only the tail —
    token-for-token identical to cold prefill, with the shared prompt's
    prefill paid ONCE. 8 requests/prompt-family must cut prefill token
    work >= 4x."""
    prefix = [(i % 17) + 1 for i in range(16)]  # 4 full blocks at bs=4
    tails = [[1, 2], [3, 4], [5, 6, 7], [8], [9, 10], [11, 12], [13],
             [14, 15]]
    prompts = [prefix + t for t in tails]
    refs = [out.completion_ids for out in trained_inference.generate(
        prompts, max_tokens=4, use_cache=True)]
    engine = ServeEngine(trained_inference, EngineConfig(
        num_slots=8, block_size=4, num_blocks=64, max_blocks_per_seq=8,
        token_budget=64, prefill_chunk=4,
    ))
    # the first family member prefills (and caches) the shared prefix...
    engine.submit(prompts[0], max_new_tokens=4)
    engine.run_until_done()
    # ...then the other 7 arrive concurrently and hit the trie
    for p in prompts[1:]:
        engine.submit(p, max_new_tokens=4)
    finished = engine.run_until_done()
    by_id = {s.request.req_id: s.generated for s in finished}
    for i, ref in enumerate(refs):
        assert by_id[i] == ref, f"request {i} (prefix hit): {by_id[i]}"
    hit = engine.scheduler.prefix_hit_tokens
    assert hit == 7 * len(prefix), hit  # every follower skipped the prefix
    total_prompt = sum(len(p) for p in prompts)
    # prefill work ACTUALLY dispatched (engine-side counter) fell >= 4x
    assert engine.prefilled_tokens + hit == total_prompt
    assert engine.prefilled_tokens * 4 <= total_prompt, (
        engine.prefilled_tokens, total_prompt)
    # followers shared blocks, they did not copy them
    followers = [s for s in finished if s.request.req_id > 0]
    assert all(s.prefix_cached == len(prefix) for s in followers)


def test_prefix_hit_survives_preemption_and_stays_exact(trained_inference):
    """A preempted prefix-sharing sequence releases only its private
    blocks; on resume it re-matches the trie (now including its own
    registered blocks) and still emits the exact greedy output."""
    prefix = [(i % 17) + 1 for i in range(12)]
    prompts = [prefix + [1, 2], prefix + [3, 4], prefix + [5, 6]]
    refs = [out.completion_ids for out in trained_inference.generate(
        prompts, max_tokens=4, use_cache=True)]
    engine = ServeEngine(trained_inference, EngineConfig(
        num_slots=4, block_size=4, num_blocks=11, max_blocks_per_seq=8,
        token_budget=64, prefill_chunk=4,
    ))
    for p in prompts:
        engine.submit(p, max_new_tokens=4)
    finished = engine.run_until_done()
    by_id = {s.request.req_id: s.generated for s in finished}
    for i, ref in enumerate(refs):
        assert by_id[i] == ref, f"request {i}: {by_id[i]} != {ref}"


# ------------------------------------------------- per-request samplers
def test_sample_rows_matches_generate_sampler_zoo():
    """The engine's per-row traced sampler must draw the SAME token the
    generate path's make_sampler draws for identical settings and key —
    per-request sampling cannot fork the sampling math."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import (
        make_sampler, sample_argmax, sample_rows,
    )

    rng = np.random.default_rng(3)
    logits = jnp.asarray(rng.normal(size=(1, 53)) * 4.0, jnp.float32)
    for temperature, top_k, top_p in [
            (0.7, None, None), (1.0, 3, None), (1.3, 10, None),
            (0.2, 1, None), (1.0, None, None), (2.5, 53, None),
            # top-p (ISSUE 11 satellite): traced per-row nucleus cutoff
            # must reproduce make_sampler's static math bit-for-bit,
            # alone and composed with temperature/top-k
            (1.0, None, 0.9), (0.7, None, 0.5), (1.5, 10, 0.8),
            (1.0, 3, 0.99), (2.0, None, 0.05)]:
        key = jax.random.PRNGKey(17)
        ref = make_sampler(temperature=temperature, top_k=top_k,
                           top_p=top_p)(logits, key)
        got = sample_rows(
            logits,
            jnp.asarray([temperature], jnp.float32),
            jnp.asarray([top_k or 0], jnp.int32),
            key[None],
            top_ps=jnp.asarray([top_p or 0.0], jnp.float32),
        )
        assert int(got[0]) == int(ref[0]), (temperature, top_k, top_p)
    # temperature 0 is greedy — the default, with no randomness consumed
    greedy = sample_rows(
        logits, jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
        jax.random.PRNGKey(0)[None],
    )
    assert int(greedy[0]) == int(sample_argmax(logits)[0])


def test_sample_rows_is_per_row():
    """One jitted call, mixed per-row settings: a greedy row, a top-1 row
    (deterministic), and a hot sampled row must each behave per their own
    config — the point of carrying the settings as traced arrays."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import sample_rows

    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(3, 31)) * 3.0, jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    toks = sample_rows(
        logits,
        jnp.asarray([0.0, 1.0, 5.0], jnp.float32),
        jnp.asarray([0, 1, 0], jnp.int32),
        keys,
    )
    argmaxes = np.asarray(jnp.argmax(logits, axis=-1))
    assert int(toks[0]) == argmaxes[0]  # greedy row
    assert int(toks[1]) == argmaxes[1]  # top-1 sampling == argmax
    assert 0 <= int(toks[2]) < 31


# ------------------------ the sampler does the work its rows ask for (ISSUE 47)
# (temperature, top_k, top_p): the zoo of the parity test above
SAMPLER_ZOO = [
    (0.7, None, None), (1.0, 3, None), (1.3, 10, None), (0.2, 1, None),
    (1.0, None, None), (2.5, 53, None), (1.0, None, 0.9), (0.7, None, 0.5),
    (1.5, 10, 0.8), (1.0, 3, 0.99), (2.0, None, 0.05)]
SAMPLING_ARITHMETIC = {"sort", "cumsum", "cumlogsumexp", "div", "exp", "log"}


def _primitives(jaxpr, into_cond=True):
    """Names of the primitives of a jaxpr and of every jaxpr it holds;
    with ``into_cond`` off, a ``cond`` is named and not entered."""
    from jax.extend import core

    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if isinstance(sub, core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, core.Jaxpr):
                    names.extend(_primitives(sub, into_cond))
    return names


def _spends_like_a_sampler(names):
    return sorted({n for n in names
                   if n in SAMPLING_ARITHMETIC or n.startswith(("random_", "threefry"))})


def test_sample_rows_sorts_and_draws_only_inside_its_cond():
    """ONE conditional on the call's temperatures: outside it and in its
    greedy branch no sort, no divide, no softmax and no random bits; the
    sampling branch holds one sort of the vocabulary, not two."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import sample_rows

    rows, vocab = 6, 53
    jaxpr = jax.make_jaxpr(sample_rows)(
        jnp.zeros((rows, vocab), jnp.bfloat16), jnp.zeros((rows,)),
        jnp.zeros((rows,), jnp.int32), jnp.zeros((rows, 2), jnp.uint32),
        jnp.zeros((rows,))).jaxpr
    outside = _primitives(jaxpr, into_cond=False)
    assert outside.count("cond") == 1
    assert _spends_like_a_sampler(outside) == []
    cond, = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    greedy, sampled = (_primitives(b.jaxpr) for b in cond.params["branches"])
    assert _spends_like_a_sampler(greedy) == []
    assert sampled.count("sort") == 1
    assert {"random_bits", "div", "cumsum"} <= set(sampled)


@pytest.fixture(scope="module")
def mixed_batch():
    """Every setting of the zoo in ONE call, greedy rows among them, each
    row with logits and a key of its own."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import sample_rows

    settings = []
    for i, setting in enumerate(SAMPLER_ZOO):
        if i % 3 == 0:
            settings.append((0.0, None, None))
        settings.append(setting)
    settings.append((0.0, 5, 0.5))  # greedy whatever else it carries
    rng = np.random.default_rng(47)
    operands = (
        jnp.asarray(rng.normal(size=(len(settings), 53)) * 4.0, jnp.float32),
        jnp.asarray([t for t, _, _ in settings], jnp.float32),
        jnp.asarray([k or 0 for _, k, _ in settings], jnp.int32),
        jnp.stack([jax.random.PRNGKey(100 + i)
                   for i in range(len(settings))]),
        jnp.asarray([p or 0.0 for _, _, p in settings], jnp.float32),
    )
    logits, temps, topks, keys, topps = operands
    tokens = np.asarray(
        jax.jit(sample_rows)(logits, temps, topks, keys, top_ps=topps))
    return settings, operands, tokens


@pytest.mark.parametrize("setting", SAMPLER_ZOO + [(0.0, None, None),
                                                   (0.0, 5, 0.5)],
                         ids=lambda s: "t{}-k{}-p{}".format(*s))
def test_a_row_of_a_mixed_batch_draws_what_it_draws_alone(mixed_batch,
                                                          setting):
    """Greedy rows among sampling rows: each row gets bit for bit the
    token it gets in a call of its own with its key (a greedy row's own
    call takes the argmax branch, the batch's the sampling one), and a
    sampling row the token ``make_sampler`` draws."""
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import (
        make_sampler, sample_argmax, sample_rows,
    )

    settings, (logits, temps, topks, keys, topps), tokens = mixed_batch
    mine = [i for i, s in enumerate(settings) if s == setting]
    assert mine
    for i in mine:
        row = slice(i, i + 1)
        alone = sample_rows(logits[row], temps[row], topks[row], keys[row],
                            top_ps=topps[row])
        assert int(alone[0]) == tokens[i]
        temperature, top_k, top_p = setting
        if temperature > 0:
            ref = make_sampler(temperature=temperature, top_k=top_k,
                               top_p=top_p)(logits[row], keys[i])
        else:
            ref = sample_argmax(logits[row])
        assert int(jnp.ravel(ref)[0]) == tokens[i]


def test_an_all_greedy_batch_is_the_argmax(mixed_batch):
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import (
        sample_argmax, sample_rows,
    )

    _, (logits, temps, topks, keys, topps), _ = mixed_batch
    got = sample_rows(logits, jnp.zeros_like(temps), topks, keys,
                      top_ps=topps)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, sample_argmax(logits))
    # a negative temperature is greedy too, and takes the argmax branch
    np.testing.assert_array_equal(
        sample_rows(logits, -jnp.ones_like(temps), topks, keys, top_ps=topps),
        got)


def _mask_with_two_sorts(scaled, top_ks, top_ps):
    """The form ``sample_rows`` had (and ``make_sampler`` has): the
    nucleus sorts the top-k-masked logits again. The reference only."""
    import jax
    import jax.numpy as jnp

    vocab = scaled.shape[-1]
    sorted_scaled = jnp.sort(scaled, axis=-1)
    k_active = (top_ks > 0) & (top_ks < vocab)
    k_idx = jnp.clip(vocab - top_ks, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_scaled, k_idx[:, None], axis=-1)
    scaled = jnp.where(k_active[:, None] & (scaled < kth), -jnp.inf, scaled)
    p_active = (top_ps > 0.0) & (top_ps < 1.0)
    sorted_desc = jnp.sort(scaled, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = cum - probs < top_ps[:, None]
    kept = jnp.sum(keep_sorted, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(
        sorted_desc, jnp.maximum(kept - 1, 0), axis=-1)
    return jnp.where(p_active[:, None] & (scaled < cutoff), -jnp.inf, scaled)


@pytest.mark.parametrize("logits_kind", ["ties", "minus-inf", "both", "plain"])
def test_one_sort_masks_exactly_what_two_sorts_masked(logits_kind):
    """Step 2: the nucleus reads the top-k-masked logits in descending
    order off the ONE sort, masked by the same value and reversed; on
    logits with ties (at the k-th value too) and with -inf entries the
    masked logits, so cutoff and draw, are the two-sort form's exactly."""
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import _mask_top_k_top_p

    rng = np.random.default_rng(11)
    rows, vocab = 96, 67
    scaled = rng.normal(size=(rows, vocab)) * 3.0
    if logits_kind in ("ties", "both"):
        scaled = np.round(scaled)  # ~20 distinct values a row
    if logits_kind in ("minus-inf", "both"):
        scaled[rng.random((rows, vocab)) < 0.3] = -np.inf
        scaled[:, 0] = 1.0  # never a row of nothing
    scaled = jnp.asarray(scaled, jnp.float32)
    top_ks = jnp.asarray(rng.choice([0, 1, 2, 5, 20, 66, 67, 200], rows),
                         jnp.int32)
    top_ps = jnp.asarray(rng.choice([0.0, 1e-6, 0.05, 0.5, 0.9, 0.99, 1.0],
                                    rows), jnp.float32)
    got = np.asarray(_mask_top_k_top_p(scaled, top_ks, top_ps))
    want = np.asarray(_mask_with_two_sorts(scaled, top_ks, top_ps))
    np.testing.assert_array_equal(got, want)
    assert np.isneginf(got).sum() > np.isneginf(np.asarray(scaled)).sum()


def test_a_sampling_request_among_greedy_ones_changes_no_stream(
        trained_inference):
    """One request at temperature 0.9 decodes beside greedy ones, so the
    ticks it lives in take the sampling branch and the others the argmax
    one: every request's tokens are those it gets served alone under its
    own id (the keys fold (request, position), never the tick)."""
    requests = [
        dict(prompt=PROMPTS[0], max_new_tokens=3),
        dict(prompt=PROMPTS[1], max_new_tokens=MAX_NEW, temperature=0.9,
             top_k=5, top_p=0.95),
        dict(prompt=PROMPTS[2], max_new_tokens=10),
    ]

    def run(reqs):
        engine = ServeEngine(trained_inference, EngineConfig(
            num_slots=4, block_size=4, num_blocks=32, max_blocks_per_seq=8,
            token_budget=64, prefill_chunk=4,
        ))
        for req_id, req in reqs:
            engine.submit(req_id=req_id, **req)
        finished = engine.run_until_done()
        return engine, {s.request.req_id: s.generated for s in finished}

    engine, together = run(list(enumerate(requests)))
    # the sampling request came and went: ticks of both branches were run
    ticks = sum(engine.mixed_ticks.values())
    assert 0 < engine.sampled_ticks < ticks
    assert engine.stats_snapshot()["sampled_tick_share"] == (
        engine.sampled_ticks / ticks)
    for req_id, req in enumerate(requests):
        _, alone = run([(req_id, req)])
        assert alone[req_id] == together[req_id], req_id
    greedy = trained_inference.generate(
        PROMPTS[2], max_tokens=10, use_cache=True).completion_ids
    assert together[2] == greedy


def test_top_p_is_per_row_and_deterministic(trained_inference):
    """Per-request top-p rides the programs as a traced per-row array:
    a tight nucleus on a peaked model collapses to greedy, and the same
    workload redraws the same tokens run-to-run."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import sample_rows

    rng = np.random.default_rng(9)
    logits = jnp.asarray(rng.normal(size=(2, 31)) * 6.0, jnp.float32)
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(2)])
    toks = sample_rows(
        logits, jnp.asarray([1.0, 1.0], jnp.float32),
        jnp.zeros((2,), jnp.int32), keys,
        top_ps=jnp.asarray([1e-6, 0.0], jnp.float32),
    )
    # row 0's nucleus keeps only the best token -> argmax; row 1 is
    # unconstrained sampling
    assert int(toks[0]) == int(jnp.argmax(logits[0]))

    def run():
        engine = ServeEngine(trained_inference, EngineConfig(
            num_slots=4, block_size=4, num_blocks=32, max_blocks_per_seq=8,
            token_budget=64, prefill_chunk=4,
        ))
        for p in PROMPTS:
            engine.submit(p, max_new_tokens=MAX_NEW, temperature=0.9,
                          top_p=0.8)
        finished = engine.run_until_done()
        return {s.request.req_id: s.generated for s in finished}

    assert run() == run()  # deterministic run-to-run


def test_sampled_requests_are_deterministic_and_survive_preemption(
        trained_inference):
    """Per-request sampling keys derive from (request id, token position)
    — not engine ticks — so the same workload redraws the same tokens
    run-to-run AND a preempted-and-resumed sampled sequence regenerates
    exactly (recompute-style preemption stays invisible even at
    temperature > 0)."""
    def run(num_blocks):
        engine = ServeEngine(trained_inference, EngineConfig(
            num_slots=4, block_size=4, num_blocks=num_blocks,
            max_blocks_per_seq=8, token_budget=64, prefill_chunk=4,
        ))
        for p in PROMPTS:
            engine.submit(p, max_new_tokens=MAX_NEW, temperature=0.9,
                          top_k=5)
        finished = engine.run_until_done()
        return engine, {s.request.req_id: s.generated for s in finished}

    _, ample = run(num_blocks=32)
    engine, again = run(num_blocks=32)
    assert ample == again  # deterministic run-to-run
    tight_engine, tight = run(num_blocks=9)  # forces preemption
    assert tight_engine.scheduler.preemption_count > 0
    assert tight == ample, "preemption changed a sampled generation"


def test_decode_rows_never_starve_behind_long_prompt(trained_inference):
    """ISSUE 10 scheduler fix: an over-budget prompt streams at the
    chunk budget — running decode rows must advance EVERY tick while it
    prefills."""
    engine = ServeEngine(trained_inference, EngineConfig(
        num_slots=4, block_size=4, num_blocks=32, max_blocks_per_seq=8,
        token_budget=8, prefill_chunk=4,
    ))
    short = engine.submit([5, 6, 7], max_new_tokens=12)
    engine.tick()  # admits + fully prefills the short prompt (one chunk)
    # the engine runs a tick ahead of its reads (ISSUE 60): the first token
    # is in flight, and on the host one tick() later
    assert short.generated == [] and short.in_flight == 1
    engine.tick()  # issues the first decode row, then reads that token
    assert len(short.generated) == 1 and short.in_flight == 1
    long = engine.submit(list(range(1, 18)), max_new_tokens=2)
    ticks_while_prefilling = 0
    while long.prefilling or long.slot is None:
        before = len(short.generated)
        engine.tick()
        if long.slot is not None and long.prefilling:
            ticks_while_prefilling += 1
            assert len(short.generated) == before + 1, (
                "decode starved behind a streaming prefill"
            )
        if len(short.generated) >= 12:
            break
    assert ticks_while_prefilling >= 2, (
        "the 17-token prompt should have needed several 4-token chunks"
    )


def test_completed_slots_are_recycled(trained_inference):
    """More concurrent requests than decode slots: completions must free
    slots that later admissions reuse within one engine run."""
    prompts = [[(3 * i + j) % 17 + 1 for j in range(3 + i)] for i in range(6)]
    refs = [out.completion_ids for out in trained_inference.generate(
        prompts, max_tokens=4, use_cache=True)]
    engine = ServeEngine(trained_inference, EngineConfig(
        num_slots=2, block_size=4, num_blocks=32, max_blocks_per_seq=8,
        token_budget=64,
    ))
    for p in prompts:
        engine.submit(p, max_new_tokens=4)
    finished = engine.run_until_done()
    assert len(finished) == 6
    by_id = {s.request.req_id: s.generated for s in finished}
    for i, ref in enumerate(refs):
        assert by_id[i] == ref, f"request {i}: {by_id[i]} != {ref}"
