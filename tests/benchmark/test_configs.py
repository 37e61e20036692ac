"""Each configuration file counts exactly the parameters its source
publishes, at the published depth, and names every key it changed."""

import json
from pathlib import Path

import pytest

from benchmark import cells, model

CONFIG_DIR = cells.ROOT / "configs"
CONFIGS = sorted(p.stem for p in CONFIG_DIR.glob("*.json"))
BENCH = json.loads((cells.REPO / "BENCHMARK.json").read_text())


def published_view(arch: dict) -> dict:
    """The architecture as run, under the source's (Hugging Face) key names."""
    hidden, heads = arch["hidden_size"], arch["num_attention_heads"]
    return {
        "hidden_size": hidden,
        "num_hidden_layers": arch["num_layers"],
        "num_attention_heads": heads,
        "num_key_value_heads": arch.get("attention_num_kv_heads") or heads,
        "head_dim": hidden // heads,
        "intermediate_size": int(hidden * arch["mlp_factor"]),
        "hidden_act": "silu" if arch["mlp_type"] == "swiglu" else arch.get(
            "activation_function", "gelu"),
        "rms_norm_eps": arch["layernorm"]["layernorm_epsilon"],
        "rope_theta": float(arch["rotary_embedding_base"]),
        "max_position_embeddings": arch["sequence_length"],
        "vocab_size": arch["vocab_size"],
        "tie_word_embeddings": arch["weight_tying"],
        "torch_dtype": arch["precision"],
    }


@pytest.mark.parametrize("name", CONFIGS)
def test_published_parameter_count(name):
    """``jax.eval_shape`` of the program's own parameter tree at the
    published depth: no allocation."""
    from scaling_tpu.models.transformer.model import init_model

    config = cells.load_json(CONFIG_DIR / f"{name}.json")
    published = config["published"]
    cfg = model.transformer_config(
        {**config, "topology": {**config["topology"], "model_parallel_size": 1,
                                "data_parallel_size": 1, "sequence_parallel": False}},
        {}, num_layers=published["num_hidden_layers"])
    shapes = model.param_shapes(init_model(cfg, None))
    assert model.count_params(shapes) == published["parameter_count"]


@pytest.mark.parametrize("name", CONFIGS)
def test_every_changed_key_is_named(name):
    config = cells.load_json(CONFIG_DIR / f"{name}.json")
    view = published_view(config["transformer_architecture"])
    published, reduced = config["published"], config["reduced"]
    for key, value in published.items():
        if key in ("parameter_count", "sliding_window"):
            continue
        if key in reduced:
            assert reduced[key]["published"] == value
            assert reduced[key]["run"] == view[key] != value
        else:
            assert view[key] == value, f"{key} differs and is not in reduced"
    assert set(reduced) <= set(published)
    # a width is never cut
    for key in reduced:
        assert not key.endswith(("_dim", "_rank", "_size")) or key == "vocab_size"
        assert key in ("num_hidden_layers", "max_position_embeddings")
    # what the source does not say is listed as assumed
    arch = config["transformer_architecture"]
    if arch["norm_type"] == "layernorm":
        assert {"attention_bias", "mlp_bias", "norm_type"} <= set(config["assumed"])
    assert published.get("sliding_window") is None


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_benchmark_json_agrees_with_the_file(entry):
    config = cells.load_json(cells.REPO / entry["file"])
    assert Path(entry["file"]).stem == entry["name"] == config["name"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    cell_chips = {w["chips"] for w in BENCH["workloads"] if w["config"] == entry["name"]}
    assert cell_chips == {config["chips"]}
