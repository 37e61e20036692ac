"""What a pipeline-parallel training step COSTS: the fill-drain garbage the
interleaved schedule shrinks, in compiled FLOPs; the bubble the analyzer
reports from a run's spans; the tuner's prediction against it; the edge
layers' sharding over pipe; remat chunking; memory in the microbatch count and
the carry budget. Loss parity and checkpoint interchange:
``test_training_pipeline.py``, whose dataset and config builder these use."""

import json

import numpy as np
import pytest

from .test_training import build_capturing_trainer
from .test_training_pipeline import data_prefix, make_pp_config  # noqa: F401


def test_interleaved_flops_shrink_fill_drain_garbage():
    """The bubble shrink, measured on compiled HLO FLOPs at fixed global
    batch (remat off): fill-drain runs (gas + pp - 1)/gas of the body's
    useful FLOPs, interleaved (gas*v + pp - 1)/(gas*v) — strictly less
    garbage. Measured at seq=512 (a realistic tokens-per-micro-batch),
    where the schedule's only counted overhead — the per-tick
    dynamic-index chunk select whose backward is a param-sized
    scatter-add — is O(v/tokens) noise; at the 48-token toy dataset
    shape it would swamp the ~1% diluted garbage win."""
    from scaling_tpu.analysis.hlo_audit import lower_train_step, make_train_config

    flops = {}
    for label, vpp in (("naive", 1), ("vpp2", 2)):
        cfg = make_train_config(pp=2, gas=8, vpp=vpp, layers=4, hidden=64,
                                seq=512, vocab=128)
        lowered, _, _ = lower_train_step(cfg)
        analysis = lowered.compile().cost_analysis()
        analysis = analysis[0] if isinstance(analysis, list) else analysis
        flops[label] = float(analysis["flops"])
    assert flops["vpp2"] < flops["naive"], flops


def test_pipeline_obs_report_measures_interleaved_bubble(
    tmp_path, data_prefix, monkeypatch
):
    """The ISSUE 7 acceptance: simulated AND obs-span-measured bubble for
    interleaved (pp=2, v=2, gas=8) strictly below fill-drain's on the
    same shape. Two real runs on the virtual mesh write span telemetry;
    the analyzer's pipeline section must (a) appear with the right
    schedule label, (b) predict the smaller bubble, and (c) attribute a
    strictly smaller share of each run's own measured fwdbwd+sync spans
    to idle: 1/17 against 1/9 of a pass. What is pinned are the COUNTS
    the attribution is made from (ticks a pass, steps kept, the share);
    the idle seconds are that share of a host-clock reading, and two of
    those are not compared (0.003 < 0.003 under six loaded workers)."""
    from scaling_tpu.obs.report import load_run_dir, pipeline_section, render_report

    measured = {}
    for label, vpp in (("naive", 1), ("vpp2", 2)):
        run_dir = tmp_path / f"run_{label}"
        run_dir.mkdir(parents=True)
        monkeypatch.setenv("SCALING_TPU_EVENTS_PATH",
                           str(run_dir / "events.jsonl"))
        monkeypatch.setenv("SCALING_TPU_METRICS_PATH",
                           str(run_dir / "metrics.jsonl"))
        cfg = make_pp_config(tmp_path / label, data_prefix, pp=2, gas=8,
                             vpp=vpp, num_layers=4,
                             train_iterations=6, save_interval=100)
        t = build_capturing_trainer(cfg)
        t.run_training()
        monkeypatch.delenv("SCALING_TPU_EVENTS_PATH")
        monkeypatch.delenv("SCALING_TPU_METRICS_PATH")

        data = load_run_dir(run_dir)
        lines = pipeline_section(data)
        assert lines, "pipeline section missing for a pp>1 run"
        text = "\n".join(lines)
        assert ("interleaved(v=2)" in text) == (vpp == 2)
        assert "predicted bubble" in text
        # full report renders cleanly too
        assert "== pipeline ==" in render_report(data, run_dir)
        import re

        pred = float(re.search(r"predicted bubble: ([0-9.]+)%", text).group(1))
        m = re.search(r"fill/drain idle ([0-9.]+)s/step \(([0-9.]+)% of compute\)",
                      text)
        assert m, text
        ticks = re.search(r"\((\d+) work ticks / (\d+) total per pass\)", text)
        steps = re.search(r"amortized over (\d+) steps", text)
        measured[label] = {
            "pred": pred, "idle_s": float(m.group(1)), "share": float(m.group(2)),
            "ticks": (int(ticks.group(1)), int(ticks.group(2))),
            "steps": int(steps.group(1))}

    # gas 8 over pp 2: 8 of 9 ticks work; two virtual stages a rank: 16 of 17
    assert measured["naive"]["ticks"] == (8, 9), measured
    assert measured["vpp2"]["ticks"] == (16, 17), measured
    # simulated bubble strictly below fill-drain's...
    assert (measured["vpp2"]["pred"], measured["naive"]["pred"]) == (5.9, 11.1)
    # ...and that share of the span-measured compute is what is attributed
    # to idle, over the six steps less the one that compiled
    for run in measured.values():
        assert run["share"] == run["pred"] and run["steps"] == 5, measured
        assert run["idle_s"] >= 0


def test_tuner_prediction_closes_calibration_loop(
    tmp_path, data_prefix, monkeypatch
):
    """ISSUE 8 acceptance: a real CPU-mesh run launched with the tuner's
    exported prediction (``SCALING_TPU_TUNER_PREDICTION``) lands a
    ``tuner-prediction`` event in its run dir; ``obs report`` renders a
    tuner section with prediction vs span-measured step time and a
    FINITE calibration error, and the ``--assert-tuner-calibration``
    gate passes at a generous ceiling and fails at an absurd one — the
    cost model's error is a tracked, gateable number."""
    import re

    from scaling_tpu.obs.cli import main as obs_main
    from scaling_tpu.obs.report import load_run_dir, tuner_section

    run_dir = tmp_path / "run"
    run_dir.mkdir()
    monkeypatch.setenv("SCALING_TPU_EVENTS_PATH",
                       str(run_dir / "events.jsonl"))
    monkeypatch.setenv(
        "SCALING_TPU_TUNER_PREDICTION",
        json.dumps({"label": "pp2·dp1·mp1·z1", "predicted_step_s": 0.05,
                    "world_size": 2, "source": "test"}),
    )
    cfg = make_pp_config(tmp_path / "t", data_prefix, pp=2, gas=4,
                         train_iterations=4, save_interval=100)
    t = build_capturing_trainer(cfg)
    t.run_training()
    monkeypatch.delenv("SCALING_TPU_EVENTS_PATH")

    data = load_run_dir(run_dir)
    lines, stats = tuner_section(data)
    text = "\n".join(lines)
    assert "layout pp2·dp1·mp1·z1: predicted 0.050s/step" in text, text
    assert "span-measured compute" in text
    err = stats["tuner_calibration_error"]
    assert np.isfinite(err), stats
    m = re.search(r"calibration error: ([+-][0-9.]+)%", text)
    assert m and float(m.group(1)) == pytest.approx(err * 100, abs=0.05)
    # the gate: generous ceiling passes, absurd ceiling fails (exit 1)
    assert obs_main([
        "report", str(run_dir), "--assert-tuner-calibration",
        str(abs(err) * 2 + 1.0),
    ]) == 0
    assert obs_main([
        "report", str(run_dir), "--assert-tuner-calibration", "1e-9",
    ]) == 1


def test_edge_layers_sharded_over_pipe(tmp_path, data_prefix, devices):
    """Embedding/lm-head params must not be replicated per pipe stage: their
    vocab dim shards over (pipe, model), so each device holds 1/(pp*mp) of
    the table (VERDICT r1: several GB per stage at 7B/128k-vocab scale)."""
    cfg = make_pp_config(tmp_path, data_prefix, pp=2, mp=2, gas=4,
                         train_iterations=1, save_interval=100)
    trainer = build_capturing_trainer(cfg)
    vocab = cfg.transformer_architecture.vocab_size
    hidden = cfg.transformer_architecture.hidden_size
    seen = 0
    for key, p, meta in trainer.module.named_parameters(trainer.params):
        if p.shape and p.shape[0] == vocab and p.ndim == 2 and p.shape[1] == hidden:
            shard_rows = {s.data.shape[0] for s in p.addressable_shards}
            assert shard_rows == {vocab // 4}, (key, shard_rows)
            seen += 1
    assert seen >= 1, "no vocab-dim parameters found"


def test_remat_chunking_minimizes_padding():
    """Every padded tick runs the full stage vmap for discarded outputs, so
    the chunking must pick the minimal-padding split near sqrt(T) — e.g.
    T=10 must use 2x5 (zero waste), not ceil(sqrt)=4 -> 3x4 (two wasted
    ticks, 20% of the step)."""
    from scaling_tpu.parallel.pipeline import _remat_chunking

    for T in range(4, 200):
        chunk, n_chunks = _remat_chunking(T)
        padding = chunk * n_chunks - T
        assert padding >= 0 and n_chunks * chunk >= T
        # never worse than the naive ceil(sqrt) chunking
        naive_chunk = int(np.ceil(np.sqrt(T)))
        naive_pad = int(np.ceil(T / naive_chunk)) * naive_chunk - T
        assert padding <= naive_pad, (T, chunk, n_chunks, naive_pad)
        # memory bound stays O(sqrt(T))
        assert chunk <= np.sqrt(T) + 3 and n_chunks <= np.sqrt(T) + 3
    assert _remat_chunking(10) == (5, 2)  # naive pads 2 ticks here
    assert _remat_chunking(9) == (3, 3)


def _compile_train_step(tmp_path, data_prefix, pp, gas, remat=False):
    """Build a trainer and compile (not run) its train step."""
    cfg = make_pp_config(tmp_path, data_prefix, pp=pp, gas=gas,
                         train_iterations=1, save_interval=100)
    if remat:
        d = cfg.model_dump(mode="json")
        d["topology"]["activation_checkpointing_type"] = "every_layer"
        cfg = type(cfg).from_dict(d)
    trainer = build_capturing_trainer(cfg)
    micro_batches = trainer._next_micro_batches()
    key = trainer.context.rng.key("dropout", 0)
    return trainer._train_step.lower(
        trainer.params, trainer.opt_state, micro_batches, key
    ).compile()


def test_pipeline_step_flops_quantify_fill_drain(tmp_path, data_prefix):
    """The spatial pipeline's compute economics, measured via compiled HLO
    FLOPs at fixed global batch (remat off, so no recompute multiplier
    muddies the accounting): pp=2 spends (n_micro + pp - 1)/n_micro of the
    pp=1 body FLOPs — the fill/drain garbage ticks. Those garbage FLOPs
    run on the pipe-axis devices that 1F1B would leave idle in its bubble,
    so they cost no extra wall-clock on a real pipe mesh."""
    flops = {}
    gas = 9
    for pp in (1, 2):
        compiled = _compile_train_step(tmp_path / f"flops_pp{pp}", data_prefix,
                                       pp=pp, gas=gas)
        analysis = compiled.cost_analysis()
        analysis = analysis[0] if isinstance(analysis, list) else analysis
        # cost_analysis reports the PER-PARTITION program; scale by the
        # mesh size to compare totals
        flops[pp] = float(analysis["flops"]) * pp
    ratio = flops[2] / flops[1]
    # body ratio bound: (n_micro + pp - 1) / n_micro = 10/9 at gas=9; non-
    # body FLOPs (embedding/head/optimizer) only dilute it, collective
    # permutes add a little back
    assert 0.95 <= ratio <= 10 / 9 + 0.08, (flops, ratio)


def test_pipeline_memory_sublinear_in_microbatch_count(
    tmp_path, data_prefix, monkeypatch
):
    """The 1F1B-comparable-memory claim, measured (VERDICT r1 asked for
    numbers, not assertions): with activation checkpointing on, the pp=2
    train step's compiled temp memory must grow sublinearly in the
    micro-batch count — the sqrt(T)-chunked tick remat stores chunk-edge
    carries only (pipeline.py), where a plain scan would hold every tick's
    carry (linear, ~1.7x per doubling when measured)."""
    monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", "0")
    temp_bytes = {}
    for gas in (8, 16):
        compiled = _compile_train_step(tmp_path / f"gas{gas}", data_prefix,
                                       pp=2, gas=gas, remat=True)
        temp_bytes[gas] = compiled.memory_analysis().temp_size_in_bytes
    assert temp_bytes[16] < 1.6 * temp_bytes[8], temp_bytes


def test_pipeline_carry_budget_gates_chunked_remat(tmp_path, data_prefix,
                                                   monkeypatch):
    """Chunked tick-remat costs one extra full body forward (~+25% step
    time at b=2f), so it must engage ONLY when the plain scan's saved
    carries would strain HBM (PERF.md 'Spatial pipeline vs a 1F1B
    executor'). Measured on compiled buffer assignment: under a roomy
    budget the step must hold MORE temp memory (every tick's carry saved)
    than the chunked build of the identical config — the observable
    signature that the extra-forward trade was skipped."""
    from scaling_tpu.parallel.pipeline import _tick_carries_exceed_budget

    import jax
    import jax.numpy as jnp

    state = {"activations": jnp.zeros((2, 2, 64, 32), jnp.float32)}
    monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", "1024")
    assert not _tick_carries_exceed_budget(state, n_ticks=9, n_state_shards=2)
    monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", "0")
    assert _tick_carries_exceed_budget(state, n_ticks=9, n_state_shards=2)
    # BASELINE #4's flagship numbers through the same gate: (pp=2, dp=8,
    # mbs=1, s=2048, h=4096, bf16) = 16 MiB/tick/device x 9 ticks =
    # 144 MiB — comfortably under the 1 GiB default, so the plain scan
    # (1F1B wall-clock parity) must win; dividing by pp alone would read
    # 8x that and wrongly engage the extra-forward trade
    monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", "1024")
    b4 = {"activations": jax.ShapeDtypeStruct((2, 8, 2048, 4096), jnp.bfloat16)}
    assert not _tick_carries_exceed_budget(b4, n_ticks=9, n_state_shards=16)
    assert _tick_carries_exceed_budget(b4, n_ticks=9, n_state_shards=2)

    # the observable build signature: the chunked path nests a tick scan
    # inside the chunk scan, so its compiled program carries strictly more
    # while-loops than the plain build of the identical config. (The old
    # signature — plain temp memory > chunked — died with the
    # roll-then-overwrite shift fix: the concatenate form had been
    # double-materializing the state into the saved carries, which was
    # most of what that comparison measured.)
    whiles = {}
    for label, budget in (("plain", "100000"), ("chunked", "0")):
        monkeypatch.setenv("SCALING_TPU_PIPE_CARRY_BUDGET_MB", budget)
        compiled = _compile_train_step(tmp_path / label, data_prefix,
                                       pp=2, gas=48, remat=True)
        whiles[label] = compiled.as_text().count(" while(")
    assert whiles["chunked"] > whiles["plain"], whiles
