"""A tick's host state reaches the device as ONE int32 operand (ISSUE 37).

(a) What the host packs through ``TickLayout`` is what the program's
opening slices give back: all nine arrays bit for bit, the float32
sampler rows included, at both token widths. (b) An engine tick hands
the device exactly one host array, and the ``serve.mixed.dispatch``
span and ``stats_snapshot()`` say so: dense and routed, one device and
the mp = 2 serving mesh. (c) The lowered program takes five arguments:
the parameters, the donated pool state, one ``s32[N]``, the key and the
samples of the program before it, which never left the device.
"""

import jax
import numpy as np
import pytest

from scaling_tpu import obs
from scaling_tpu.serve.engine import TickFields, TickLayout
from tests.core.test_serve.test_packed_tick import (  # noqa: F401
    FULL,
    MAX_BLOCKS,
    SLOTS,
    SMALL,
    make_engine,
    models,
)


# ------------------------------------------------ (a) pack -> the slices
def a_tick(layout, width, seed):
    """Nine arrays as a tick holds them, the float rows with values whose
    bits are no integer's (0.7, 1e-3, 0.95) beside the greedy 0.0."""
    rng = np.random.default_rng(seed)
    n, m = layout.num_slots, layout.max_blocks_per_seq

    def ints(*shape, high=2**31 - 1):
        return rng.integers(0, high, shape).astype(np.int32)

    return TickFields(
        tables=ints(n, m), ctx_lens=ints(n), new_lens=ints(n, high=33),
        topks=ints(n, high=50), reqids=ints(n), gen0=ints(n) - 2**30,
        temps=rng.choice(np.float32([0.0, 0.7, 1e-3, 1.3]), n),
        topps=rng.choice(np.float32([0.0, 0.95, 0.1]), n),
        tokens=ints(width, high=32768))


@pytest.mark.parametrize("width", [128, 512], ids=["small", "full"])
@pytest.mark.parametrize("slots,max_blocks", [(16, 256), (8, 8), (3, 1)],
                         ids=["benchmark", "toy", "odd"])
def test_the_program_s_slices_give_back_what_the_host_packed(
        slots, max_blocks, width):
    layout = TickLayout(slots, max_blocks)
    want = a_tick(layout, width, seed=width + slots)
    packed, fields = layout.host(width)
    assert packed.shape == (layout.size(width),) and packed.dtype == np.int32
    assert layout.size(width) == slots * (max_blocks + 7) + width
    for view, value in zip(fields, want):
        assert view.base is not None  # a view: the write lands in `packed`
        view[...] = value
    # every element of the vector belongs to exactly one field
    assert sum(f.size for f in fields) == packed.size
    got = jax.jit(layout.split)(jax.device_put(packed))
    for name, g, w in zip(TickFields._fields, got, want):
        g = np.asarray(g)
        assert (g.shape, g.dtype) == (w.shape, w.dtype), name
        # bit for bit: float rows compared as the integers they travel as
        assert g.view(np.int32).tolist() == w.view(np.int32).tolist(), name
    assert np.asarray(got.temps).tolist() == want.temps.tolist()


def test_every_offset_but_the_length_is_the_same_at_both_widths():
    layout = TickLayout(SLOTS, MAX_BLOCKS)
    small, at_small = layout.host(SMALL)
    full, at_full = layout.host(FULL)
    for a, b in zip(at_small[:-1], at_full[:-1]):
        assert (a.__array_interface__["data"][0] - small.ctypes.data
                == b.__array_interface__["data"][0] - full.ctypes.data)
    assert at_small.tokens.shape == (SMALL,)
    assert at_full.tokens.shape == (FULL,)
    # so a vector written at the full width is the small one's, cut short
    assert full[:layout.size(SMALL)].shape == small.shape


# ------------------------------------ (b) one transfer a tick, and counted
@pytest.mark.parametrize("model", ["dense", "routed", "mp2"])
def test_a_tick_hands_the_device_one_host_array(models, model, monkeypatch,
                                                tmp_path):
    engine = make_engine(models[model])
    engine.warmup_mode = True  # both programs lowered, nothing counted
    engine.submit([5], 2)
    engine.run_until_done()
    engine.warmup_mode = False
    assert engine.tick_operands == 0

    # every way a host array can reach the device in a tick: an explicit
    # device_put (the serving mesh's replicated operand), or a host array
    # among the jitted call's arguments (which the call moves itself)
    moved = []  # per program call, the host arrays it cost
    puts = []
    device_put = jax.device_put

    def counting(x, *args, **kwargs):
        puts.append(x)
        return device_put(x, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting)
    for width, fn in list(engine._mixed_fns.items()):
        def spy(*args, _fn=fn):
            handed = [leaf for leaf in jax.tree_util.tree_leaves(args)
                      if not isinstance(leaf, jax.Array)]
            moved.append(puts + handed)
            puts.clear()
            return _fn(*args)
        engine._mixed_fns[width] = spy
    rng = np.random.default_rng(11)
    # prompts of several chunks, sampled and greedy rows, a crowded tick
    for i, n in enumerate([40, 3, 70, 33, 32, 32, 9]):
        engine.submit(list(rng.integers(1, 60, n)), 3,
                      temperature=0.7 if i % 2 else 0.0, top_p=0.9)
    obs.start_capture(tmp_path / "trace")
    try:
        engine.run_until_done()
    finally:
        capture = obs.stop_capture()
    assert not puts  # nothing was put on the device outside a program call
    dispatches = [f for name, _, _, f in capture.spans
                  if name == "serve.mixed.dispatch"]
    ticks = sum(engine.mixed_ticks.values())
    assert ticks == len(dispatches) == len(moved) > 4
    assert set(engine.mixed_ticks) == {SMALL, FULL}  # both programs ran
    layout = engine._layout
    for arrays, fields in zip(moved, dispatches):
        (packed,) = arrays  # ONE host array a tick
        assert isinstance(packed, np.ndarray) and packed.dtype == np.int32
        assert packed.size in (layout.size(SMALL), layout.size(FULL))
        assert fields["operands"] == 1 and fields["bytes"] == packed.nbytes
    assert engine.tick_operands == ticks
    assert engine.stats_snapshot()["tick_operands"] == 1.0


def test_no_tick_counted_reads_as_none(models):
    assert make_engine(models["dense"]).stats_snapshot()[
        "tick_operands"] is None


# ------------------------------------------- (c) the program's signature
@pytest.mark.parametrize("width", [SMALL, FULL], ids=["small", "full"])
@pytest.mark.parametrize("model", ["dense", "routed", "mp2"])
def test_the_lowered_program_takes_five_arguments(models, model, width):
    """Flattened, ``main`` takes the parameters' leaves, the pool state's,
    ONE ``s32[N]``, the key and the samples of the program before it
    (``prev``, already on the device: a decode row's last token while the
    host has not read it): nothing else of a tick is an argument."""
    engine = make_engine(models[model])
    packed, _ = engine._layout.host(width)
    args = (engine.inf.params, engine._pool_state(), engine._dev(packed),
            engine._base_key, engine._prev)
    text = engine._build_mixed_fn(width).lower(*args).as_text()
    signature = text.split("@main(", 1)[1].split(") -> ", 1)[0]
    types = [a.split("tensor<", 1)[1].split(">", 1)[0]
             for a in signature.split("%arg")[1:]]
    leaves = [len(jax.tree_util.tree_leaves(a)) for a in args]
    assert leaves[2:] == [1, 1, 1] and len(types) == sum(leaves)
    assert types[-3] == f"{engine._layout.size(width)}xi32"
    assert types[-2] == "2xui32"
    assert types[-1] == f"{engine.config.num_slots}x1xi32"
