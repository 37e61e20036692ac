"""The program's account of its own set-up (``obs/compile_events.py``):
JAX's trace / lower / compile / cache-load events as rows of the recorder and
counters of the registry, by program name; the spans at the engine's and the
step's build phases; the recompile counter an operator reads. On the CPU, with
toy programs: one compile a shape."""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from scaling_tpu import obs
from scaling_tpu.obs import compile_events
from scaling_tpu.obs.recorder import clock

REPO_ROOT = Path(__file__).resolve().parents[2]
PHASES = ("compile.trace", "compile.lower", "compile.backend")


lowered_total = compile_events.programs_lowered


def compile_rows(since_s: float, fun_name=None):
    """The ``compile.*`` rows that start at or after ``since_s``, in closing
    order; those of one program where named."""
    return [r for r in obs.recorded_spans(since_ns=round(since_s * 1e9))
            if r.name.startswith("compile.")
            and (fun_name is None or r.fields.get("fun_name") == fun_name)]


def names(rows):
    return [r.name for r in rows]


def fresh_program(name: str, inner=None):
    """A jitted function no test has met: its own function object under its
    own name, so its first call traces, lowers and compiles."""

    def program(x):
        y = jnp.sin(x) * 2.0
        return (inner(y) if inner is not None else y) + 1.0

    program.__name__ = name
    return jax.jit(program)


def test_a_first_call_writes_one_row_a_phase_by_name_and_counts_the_lowering():
    fn = fresh_program("account_first_call")
    x = jnp.ones((4,), jnp.float32)   # made before the clock is read
    t0, lowered = clock(), lowered_total()
    fn(x)
    t1 = clock()
    mine = compile_rows(t0, "jit(account_first_call)")
    assert names(mine) == list(PHASES) or names(mine) == [
        "compile.trace", "compile.lower", "compile.cache_load", "compile.backend"]
    # on the recorder's clock, and closed before the call returned
    for r in mine:
        assert t0 * 1e9 <= r.start_ns and r.start_ns + r.duration_ns <= t1 * 1e9 + 1
        assert r.duration_ns >= 0
    # in order: the trace ends before the lowering, that before the compile
    by = {r.name: r for r in mine}
    assert (by["compile.trace"].start_ns + by["compile.trace"].duration_ns
            <= by["compile.lower"].start_ns + by["compile.lower"].duration_ns
            <= by["compile.backend"].start_ns + by["compile.backend"].duration_ns)
    # eager operations lower programs of their own: the counter moves by the
    # compile.lower rows written, whoever's
    rows = compile_rows(t0)
    assert lowered_total() - lowered == names(rows).count("compile.lower") >= 1

    # a second call adds nothing
    t2, lowered = clock(), lowered_total()
    fn(x)
    assert compile_rows(t2) == [] and lowered_total() == lowered

    # a new shape adds the three again: "which step recompiled" has a name
    x8 = jnp.ones((8,), jnp.float32)
    t3 = clock()
    fn(x8)
    again = compile_rows(t3, "jit(account_first_call)")
    assert [n for n in names(again) if n != "compile.cache_load"] == list(PHASES)


def test_a_jitted_inner_function_leaves_one_trace_row_the_outers():
    """JAX fires a trace event for every jitted function traced inside the
    outer trace (``sin``, ``multiply``, ``inner``, ``add``); only the one the
    lower event names, the outermost, becomes a row."""
    inner = fresh_program("account_inner")
    outer = fresh_program("account_outer", inner=inner)
    x = jnp.ones((4,), jnp.float32)
    t0 = clock()
    outer(x)
    traces = [r for r in compile_rows(t0) if r.name == "compile.trace"]
    assert [r.fields["fun_name"] for r in traces] == ["jit(account_outer)"]
    lowered = [r.fields["fun_name"] for r in compile_rows(t0)
               if r.name == "compile.lower"]
    assert lowered == ["jit(account_outer)"]   # inner is inlined, not a program


def test_the_seconds_counter_moves_by_the_rows_written():
    def seconds():
        counters = obs.get_registry().snapshot()["counters"]
        return {p: counters.get(f"jax_compile_seconds_total{{phase={p}}}", 0.0)
                for p in ("trace", "lower", "backend", "cache_load")}

    fn = fresh_program("account_seconds")
    x = jnp.ones((4,), jnp.float32)
    t0, before = clock(), seconds()
    fn(x)
    rows, after = compile_rows(t0), seconds()
    for phase in ("trace", "lower", "cache_load"):
        assert after[phase] - before[phase] == pytest.approx(sum(
            r.duration_ns for r in rows if r.name == f"compile.{phase}") / 1e9,
            abs=1e-6)
    # backend's seconds are net of the retrieval it contains
    assert after["backend"] - before["backend"] == pytest.approx(sum(
        r.duration_ns / 1e9 - r.fields.get("retrieval_s", 0.0)
        for r in rows if r.name == "compile.backend"), abs=1e-6)


def test_install_twice_registers_one_listener_and_writes_a_row_each_time():
    from jax._src import monitoring

    def mine():
        return (monitoring.get_event_duration_listeners().count(
                    compile_events._on_duration),
                monitoring.get_event_listeners().count(compile_events._on_event))

    assert mine() == (1, 1)   # tests/conftest.py passed enable_compile_cache()
    before = len(obs.recorded_spans(name="process.start"))
    compile_events.install()
    compile_events.install()
    assert mine() == (1, 1)
    starts = obs.recorded_spans(name="process.start")
    assert len(starts) >= min(before + 2, 2)
    # each from the process's own start to its call
    assert starts[-1].start_ns == starts[-2].start_ns == round(
        obs.process_start_s() * 1e9)
    assert starts[-1].duration_ns >= starts[-2].duration_ns > 0


def test_process_start_is_stable_and_before_every_row():
    first = obs.process_start_s()
    assert obs.process_start_s() == first
    assert first <= clock()
    compile_events.install()
    rows = obs.recorded_spans()
    start = [r for r in rows if r.name == "process.start"][-1]
    assert start.start_ns <= min(r.start_ns for r in rows)
    # a tier-1 worker is seconds to minutes old, not days
    assert 0 < start.duration_ns / 1e9 < 86400


@pytest.mark.skipif(
    os.environ.get("SCALING_TPU_TEST_CACHE", "").lower() == "off",
    reason="the persistent compile cache is off")
def test_a_second_lowering_of_the_same_program_hits_the_persistent_cache():
    """Two function objects with one body under one name lower to the same
    module: the second finds the first's executable in the persistent cache
    (tests/conftest.py enables it), whatever the first found."""
    def hits():
        counters = obs.get_registry().snapshot()["counters"]
        return (counters.get("jax_compile_cache_hits_total", 0.0),
                counters.get("jax_compile_cache_misses_total", 0.0))

    x = jnp.ones((4,), jnp.float32)
    fresh_program("account_cached_twice")(x)
    t0, (hit, miss) = clock(), hits()
    fresh_program("account_cached_twice")(x)
    mine = compile_rows(t0, "jit(account_cached_twice)")
    assert names(mine) == ["compile.trace", "compile.lower",
                           "compile.cache_load", "compile.backend"]
    load, backend = mine[2], mine[3]
    assert backend.fields["cache_hit"] is True
    assert backend.fields["retrieval_s"] == pytest.approx(load.duration_ns / 1e9)
    # the backend row CONTAINS the retrieval
    assert backend.start_ns <= load.start_ns
    assert (load.start_ns + load.duration_ns
            <= backend.start_ns + backend.duration_ns)
    assert hits() == (hit + 1, miss)


# ----------------------------------------------------------- the engine
@pytest.fixture(scope="module")
def toy_inference():
    from scaling_tpu.serve.bench import build_toy_inference

    return build_toy_inference(hidden=32, layers=2, vocab=64, heads=4)


def make_engine(inf):
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    # 4 slots x chunk 32: one token width, one program
    return ServeEngine(inf, EngineConfig(
        num_slots=4, block_size=16, prefill_chunk=32, num_blocks=4 * 8 + 1,
        max_blocks_per_seq=8, enable_prefix_cache=False))


def test_the_engines_set_up_is_two_spans_and_a_recompile_counter(toy_inference):
    t0 = clock()
    engine = make_engine(toy_inference)
    since = round(t0 * 1e9)
    init, = obs.recorded_spans(since_ns=since, name="serve.init")
    assert init.fields == {
        "num_slots": 4, "kv_lines": engine.pools.kv_lines,
        "pool_bytes": engine.pools.device_bytes()}
    assert engine.stats_snapshot()["programs_lowered_since_ready"] is None

    # warm-up silences the tick's spans, not the lowering's
    engine.warmup_mode = True
    engine.submit([1], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    lower, = obs.recorded_spans(since_ns=since, name="serve.lower")
    assert lower.fields == {"widths": [128]} and lower.parent is None
    assert obs.recorded_spans(since_ns=since, name="serve.tick") == []
    # the program's compile rows fall inside the span
    mixed = compile_rows(t0, "jit(mixed_128)")
    assert {"compile.trace", "compile.lower", "compile.backend"} <= set(names(mixed))
    assert all(lower.start_ns <= r.start_ns
               and r.start_ns + r.duration_ns <= lower.start_ns + lower.duration_ns
               for r in mixed)
    ready = obs.get_registry().snapshot()["gauges"]["serve_ready_seconds"]
    assert ready == pytest.approx(
        (lower.start_ns + lower.duration_ns) / 1e9 - obs.process_start_s(), abs=0.05)

    # twenty ticks of prefill chunks and decode rows: nothing is lowered
    for prompt, new in (([3] * 40, 6), ([5] * 7, 9), ([7] * 33, 4)):
        engine.submit(prompt, new)
    for _ in range(20):
        if engine.scheduler.has_work:
            engine.tick()
    engine.settle()
    stats = engine.stats_snapshot()
    assert stats["tick"] >= 12 and stats["prefill_compiles"] == 1
    assert stats["programs_lowered_since_ready"] == 0

    # a program lowered behind the engine's back is the alarm
    x = jnp.ones((4,), jnp.float32)
    before = engine.programs_lowered_since_ready
    fresh_program("account_behind_the_engine")(x)
    assert engine.stats_snapshot()["programs_lowered_since_ready"] == before + 1 == 1


def test_the_report_prints_both_numbers(tmp_path):
    import json

    from scaling_tpu.obs.report import load_run_dir, serving_section

    run = tmp_path / "run"
    run.mkdir()
    summary = {"event": "serve-summary", "ts": 20.0, "requests": 1, "wall_s": 2.0,
               "output_tokens": 4, "tokens_per_s": 2, "ticks": 12, "preemptions": 0,
               "prefill_compiles": 2, "programs_lowered_since_ready": 3}
    (run / "events.jsonl").write_text(json.dumps(summary) + "\n")
    text = "\n".join(serving_section(load_run_dir(run))[0])
    assert "prefill_compiles=2 programs_lowered_since_ready=3" in text
    # a run dir from before the counter prints the line it always did
    del summary["programs_lowered_since_ready"]
    (run / "events.jsonl").write_text(json.dumps(summary) + "\n")
    text = "\n".join(serving_section(load_run_dir(run))[0])
    assert "prefill_compiles=2" in text and "programs_lowered" not in text


# ------------------------------------------------------------- the step
def test_building_the_train_step_is_a_span(monkeypatch):
    from scaling_tpu.parallel.parallel_module import ParallelModule

    seen = []
    monkeypatch.setattr(
        ParallelModule, "_assemble_train_step",
        lambda self, optimizer, loss_function, donate: seen.append(
            (optimizer, loss_function, donate)) or "the step")
    t0 = clock()
    module = object.__new__(ParallelModule)
    assert module.build_train_step("opt", "loss") == "the step"
    assert seen == [("opt", "loss", True)]
    row, = obs.recorded_spans(since_ns=round(t0 * 1e9), name="train.build_step")
    assert row.parent is None and row.duration_ns >= 0


def test_the_trainers_first_step_sets_its_gauge(tmp_path):
    """The MLP example through ``BaseTrainer.run_training``: the step is
    assembled under ``train.build_step``, its first call is the ``compile.*``
    rows named ``jit(step)``, and the loop stamps the process's start to the
    first step's return, once."""
    from tests.core.test_training.test_training import (
        build_trainer, make_config, run_steps,
    )

    t0 = clock()
    trainer = build_trainer(make_config(tmp_path, train_iterations=3), 128)
    built, = obs.recorded_spans(since_ns=round(t0 * 1e9), name="train.build_step")
    assert len(run_steps(trainer, 3)) == 3
    t1 = clock()
    step_rows = compile_rows(t0, "jit(step)")
    assert {"compile.trace", "compile.lower", "compile.backend"} <= set(names(step_rows))
    assert all(r.start_ns >= built.start_ns + built.duration_ns for r in step_rows)
    first = obs.get_registry().snapshot()["gauges"]["train_first_step_seconds"]
    lowered = max(r.start_ns + r.duration_ns for r in step_rows) / 1e9
    assert lowered - obs.process_start_s() <= first <= t1 - obs.process_start_s()


# ------------------------------------------------------------ the import
def test_obs_and_the_listener_import_no_jax():
    code = (f"import sys; sys.path.insert(0, {str(REPO_ROOT)!r}); "
            "import scaling_tpu.obs, scaling_tpu.obs.compile_events as ce; "
            "assert 'jax' not in sys.modules, 'obs imported jax'; "
            "assert scaling_tpu.obs.process_start_s() <= scaling_tpu.obs.recorder.clock(); "
            "print(ce.programs_lowered())")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "0"


def test_only_the_listener_module_listens_to_jax():
    hits = sorted(
        str(path.relative_to(REPO_ROOT))
        for path in (REPO_ROOT / "scaling_tpu").rglob("*.py")
        if "jax.monitoring" in path.read_text()
        or "_src.monitoring" in path.read_text()
        or "import monitoring" in path.read_text())
    assert hits == ["scaling_tpu/obs/compile_events.py"]
