"""A stack whose KV heads are 256 lanes wide (Qwen3-Next's attention layers')
through ``ServeEngine`` at a toy size: ``init_pools`` makes its pools HEAD-MAJOR
(``paged_attention.head_major_kv``; ISSUE 74), the ONE writer and the kernel
address them so, and the block copies of the prefix cache (a hit's shared
blocks, a copy-on-write fork) move whole head-major blocks: the greedy tokens
are ``generate``'s, whose dense caches know nothing of blocks."""

import jax
import numpy as np
import pytest

from scaling_tpu.models.transformer import TransformerConfig
from scaling_tpu.models.transformer.inference import TransformerInferenceModule
from scaling_tpu.models.transformer.model import init_model
from scaling_tpu.serve.engine import EngineConfig, ServeEngine

VOCAB, BLOCK = 96, 8
CONFIG = {
    "topology": {"model_parallel_size": 1, "pipe_parallel_size": 1,
                 "data_parallel_size": 1, "micro_batch_size": 1,
                 "gradient_accumulation_steps": 1},
    "transformer_architecture": {
        # the homogeneous layer's heads are hidden / heads wide: 256
        "vocab_size": VOCAB, "hidden_size": 512, "num_layers": 2,
        "num_attention_heads": 2, "attention_num_kv_heads": 2,
        "attention_qkv_in_one": False,
        "attention_bias": False, "mlp_type": "swiglu", "mlp_factor": 2.0,
        "mlp_bias": False, "norm_type": "rms", "sequence_length": 128,
        "precision": "float32", "weight_tying": False},
    "data": {}, "logger": {"log_dir": None}}


@pytest.fixture(scope="module")
def wide():
    config = TransformerConfig.from_dict(CONFIG)
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(3))
    # seeded weights away from their initial ones, so that greedy tokens vary
    # with what the cache holds
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(4), len(leaves))
    params = jax.tree.unflatten(treedef, [
        x + 0.3 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return TransformerInferenceModule(config, module, params)


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, VOCAB, size=n).tolist() for n in lengths]


def engine_of(inf, **config):
    return ServeEngine(inf, EngineConfig(**{
        "num_slots": 4, "block_size": BLOCK, "num_blocks": 33,
        "max_blocks_per_seq": 8, "token_budget": 64, "prefill_chunk": 8,
        **config}))


def served(engine, requests, max_new):
    for p in requests:
        engine.submit(p, max_new_tokens=max_new)
    return {s.request.req_id: s.generated for s in engine.run_until_done()}


def test_the_pools_are_made_head_major_and_serve_what_generate_gives(wide):
    requests = prompts((9, 21, 14, 30))
    want = [out.completion_ids for out in wide.generate(requests, max_tokens=8)]
    engine = engine_of(wide)
    assert [p.shape for p in engine.pools.pool_k] == [(33, 2, BLOCK, 256)] * 2
    # a head-major tile is reckoned at its own bytes: the whole table
    assert engine._kv_tile == 8 * BLOCK
    got = served(engine, requests, 8)
    assert [got[i] for i in range(len(requests))] == want
    assert len({tuple(w) for w in want}) > 1  # the weights say something


def test_a_prefix_hit_and_a_fork_copy_whole_head_major_blocks(wide):
    prefix = prompts((2 * BLOCK,), seed=3)[0]
    family = [prefix + tail for tail in prompts((3, 2, 5), seed=4)]
    want = [wide.generate(p, max_tokens=6).completion_ids for p in family]
    engine = engine_of(wide)
    first = served(engine, family[:1], 6)
    rest = served(engine, family[1:], 6)
    assert engine.scheduler.prefix_hit_tokens == 2 * len(prefix)
    assert [first[0], rest[1], rest[2]] == want
    # a fork: the block a row shares is copied before the row writes into it
    before = np.asarray(engine.pools.pool_k[0])
    engine._apply_cow([(1, 30)])
    after = np.asarray(engine.pools.pool_k[0])
    assert before[1].any() and (after[30] == before[1]).all()
    assert (np.delete(after, 30, 0) == np.delete(before, 30, 0)).all()
