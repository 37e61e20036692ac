"""Profiler window/observation behavior (reference: core/profiler tests)."""

import json

from scaling_tpu.profiler import Profiler, ProfilerConfig, SynchronizedTimer


def test_window_gating(tmp_path):
    out = tmp_path / "profile.json"
    p = Profiler(ProfilerConfig(profile_steps=2, profile_start_at_step=3,
                                profiler_output=out))
    for step in range(6):
        p.begin_step(step)
        p.record(step, {"step_time": 0.1 * (step + 1)})
        p.end_step(step)
    obs = json.loads(out.read_text())
    assert [o["step"] for o in obs] == [3, 4]


def test_disabled_writes_nothing(tmp_path):
    out = tmp_path / "profile.json"
    p = Profiler(ProfilerConfig(profile_steps=0, profiler_output=out))
    p.record(5, {"step_time": 1.0})
    p.flush()
    assert not out.exists()


def test_synchronized_timer():
    import jax.numpy as jnp

    t = SynchronizedTimer("op")
    t.start()
    x = jnp.ones((64, 64)) @ jnp.ones((64, 64))
    d = t.stop(wait_for=x)
    assert d > 0 and t.durations == [d]


def test_capture_xla_trace_produces_parseable_xplane(tmp_path):
    """capture_xla_trace writes a real xplane dump next to the
    observations, and ``jax.profiler.ProfileData`` reads it as
    ``scaling_tpu/obs/capture.py`` and the benchmark's readers do: a jax
    upgrade that shifts the xplane schema has to fail HERE, on the CPU,
    not on chip time."""
    import jax
    import jax.numpy as jnp

    out = tmp_path / "profile.json"
    p = Profiler(ProfilerConfig(profile_steps=1, profile_start_at_step=0,
                                profiler_output=out, capture_xla_trace=True))

    @jax.jit
    def work(x):
        return (x @ x).sum()

    x = jnp.ones((128, 128))
    jax.block_until_ready(work(x))
    p.begin_step(0)
    jax.block_until_ready(work(x))
    p.record(0, {"step_time": 0.01})
    p.end_step(0)

    assert json.loads(out.read_text())[0]["step"] == 0
    trace_dir = out.parent / "xla_trace"
    files = list(trace_dir.glob("**/*.xplane.pb"))
    assert files, "capture_xla_trace produced no xplane file"

    from jax.profiler import ProfileData

    planes = ProfileData.from_file(str(files[-1])).planes
    timed = [
        (plane.name, line.name)
        for plane in planes
        for line in plane.lines
        if any(event.duration_ns > 0 for event in line.events)
    ]
    assert timed, [plane.name for plane in planes]
