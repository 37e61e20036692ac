"""The latent mixer at a SECOND set of sizes, its head-wise gate and its
latents' rescale (``nn/latent_attention.py``), and that mixer under a window
over a ring of latent lines a slot (``nn/window_latent_attention.py``,
``nn/latent_ring_attention.py``): both forms equal the benchmark's plain
reference layer with the rescale and the gate on and off; the ring walk
(kernel interpreted, and the gather form) equals the expanded form under the
mask at contexts 1, 512, 513, 514 of a window of 513 and past the ring's wrap,
in a reused slot and for a row recomputed from nothing; a window of 512 fails
it; the kernel at its smallest grid of two tiles and two rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from scaling_tpu.nn import latent_paged_attention as lpa
from scaling_tpu.nn.attention import PagedKVCacheView
from scaling_tpu.nn.base_layer import ForwardContext
from scaling_tpu.nn.latent_attention import LatentSelfAttention
from scaling_tpu.nn.latent_ring_attention import latent_ring_attention
from scaling_tpu.nn.norm import LayerNormConfig
from scaling_tpu.nn.rotary import RotaryConfig
from scaling_tpu.nn.window_attention import RingRows, ring_lines
from scaling_tpu.nn.window_latent_attention import (
    LatentRingView, WindowLatentSelfAttention,
)
from scaling_tpu.nn.window_ring_attention import NOBODY

from .one_program import jitted

H, EPS, WINDOW, CHUNK = 64, 1e-5, 513, 64
# (heads, q_lora, kv_lora, nope, rope, v, rotary base): the two geometries of
# one stack, the second with the wider latent and key
SIZES = {"full": (8, 32, 16, 16, 8, 16, 80000000.0),
         "window": (4, 32, 32, 24, 8, 16, 50000.0)}
RING = ring_lines(WINDOW, CHUNK)


def mixer(kind, rescale=True, gate=True, window=WINDOW):
    n, q_lora, kv_lora, nope, rope, v, base = SIZES[kind]
    own = {"window_size": window} if kind == "window" else {}
    return (WindowLatentSelfAttention if own else LatentSelfAttention)(
        **own, hidden_size=H, num_attention_heads=n, q_lora_rank=q_lora,
        kv_lora_rank=kv_lora, qk_nope_head_dim=nope, qk_rope_head_dim=rope,
        v_head_dim=v, dtype=jnp.float32, output_gate=gate, lora_rescale=rescale,
        layernorm_config=LayerNormConfig(layernorm_epsilon=EPS),
        rotary_config=RotaryConfig(dimensions=rope, base=int(base),
                                   max_seq_length=2048))


def seeded(m, seed=0):
    """Weights away from the init: norms off one, a gate that differs by head
    and by token."""
    params = m.init(jax.random.PRNGKey(seed))
    for name in ("q_a_norm", "kv_a_norm"):
        w = params[name]["weight"]
        params[name]["weight"] = w + 0.2 * jax.random.normal(
            jax.random.PRNGKey(seed + 1), w.shape)
    if "gate" in params:
        params["gate"]["weight"] = 4 * params["gate"]["weight"]
    return params


@pytest.fixture(scope="module")
def reference():
    return cells.load_module(cells.ROOT, "reference", "layered_latent_moe_decoder",
                             cells.REFERENCE_CONTRACT)


def by_reference(ref, kind, params, x, rescale, window=WINDOW):
    """The reference's layer (no norm before it, no residual) on one sequence;
    a mixer without a gate is the reference's at a gate of one half, doubled."""
    n = SIZES[kind][0]
    gate = params["gate"]["weight"] if "gate" in params else jnp.zeros((H, n))
    p = {"q_a": params["q_a_proj"]["weight"], "q_a_norm": params["q_a_norm"],
         "q_b": params["q_b_proj"]["weight"], "kv_a": params["kv_a_proj"]["weight"],
         "kv_a_norm": params["kv_a_norm"], "kv_b": params["kv_b_proj"]["weight"],
         "o": params["dense"]["weight"], "head_gate": gate}
    spec = {"sizes": (SIZES["full"], SIZES["window"]), "hidden": H,
            "rescale": rescale, "window": window, "index_topk": None, "eps": EPS}
    with jax.default_matmul_precision("highest"):
        y, _ = ref.attention_parts(x[0], p, kind, spec)
    return np.asarray(y) * (1.0 if "gate" in params else 2.0)


def empty_pages(kind, blocks=32, block=4):
    kv_lora, rope = SIZES[kind][2], SIZES[kind][4]
    return PagedKVCacheView(
        pool_k=jnp.zeros((blocks + 1, block, kv_lora)),
        pool_v=jnp.zeros((blocks + 1, block, lpa.rope_line_width(rope))),
        block_table=1 + jnp.arange(blocks, dtype=jnp.int32)[None],
        context_len=jnp.zeros((1,), jnp.int32))


def empty_ring(lines=RING, slots=1):
    kv_lora, rope = SIZES["window"][2], SIZES["window"][4]
    return LatentRingView(
        line=jnp.zeros((slots, lines, kv_lora + lpa.rope_line_width(rope))),
        context_len=jnp.zeros((slots,), jnp.int32),
        new_len=jnp.zeros((slots,), jnp.int32))


def ring_call(m, paged_kernel):
    """``(params, x, position_ids, view) -> (y, view)`` of a windowed mixer
    over its ring: one program a call shape."""
    ctx = ForwardContext(serving=True, paged_kernel=paged_kernel)
    return jax.jit(lambda params, x, position_ids, view: m(
        params, x, ctx, position_ids=position_ids, state=view))


def served(call, params, x, sizes, view, start=0):
    """``x`` (1, s, H) through ``call`` in calls of ``sizes`` tokens from
    position ``start`` on: ``(y (s, H), the view after)``."""
    got, done = [], 0
    for n in sizes:
        at = start + done
        y, view = call(
            params, x[:, done:done + n], (at + jnp.arange(n))[None],
            view._replace(context_len=jnp.asarray([at], jnp.int32),
                          new_len=jnp.asarray([n], jnp.int32)))
        got.append(np.asarray(y[0]))
        done += n
    assert done == x.shape[1]
    return np.concatenate(got), view


@pytest.mark.parametrize("rescale", [True, False])
@pytest.mark.parametrize("gate", [True, False])
@pytest.mark.parametrize("kind", ["full", "window"])
def test_both_forms_at_both_geometries_are_the_references_layer(
        reference, kind, gate, rescale):
    """The ONE mixer class at two sets of sizes, the second under a window of
    9: expanded without a cache, absorbed over pages (full) or over a ring
    (window), == the reference's layer; with the rescale and the gate on and
    off (the leaves say which)."""
    m = mixer(kind, rescale, gate, window=9)
    params = seeded(m)
    assert ("gate" in params) == gate and len(params) == 7 + gate
    assert (m.q_scale, m.kv_scale) == (
        ((H / SIZES[kind][1]) ** 0.5, (H / SIZES[kind][2]) ** 0.5)
        if rescale else (1.0, 1.0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, H))
    want = by_reference(reference, kind, params, x, rescale, window=9)
    assert want.std() > 0.05
    pos = jnp.arange(24)[None]
    if kind == "window":
        np.testing.assert_allclose(m(params, x, ForwardContext(), position_ids=pos)[0],
                                   want, atol=2e-5)
        got, _ = served(ring_call(m, "pallas"), params, x, [8, 8, 1, 1, 6],
                        empty_ring(ring_lines(9, 8)))
    else:
        call = jitted(m, ForwardContext(paged_kernel="pallas"))
        np.testing.assert_allclose(call(params, x, pos)[0], want, atol=2e-5)
        got, view, done = [], empty_pages(kind), 0
        for n in (12, 8, 1, 1, 1, 1):
            y, view = call(params, x[:, done:done + n], (done + jnp.arange(n))[None],
                           view._replace(context_len=jnp.asarray([done], jnp.int32),
                                         new_len=jnp.asarray([n], jnp.int32)))
            got.append(np.asarray(y[0]))
            done += n
        got = np.concatenate(got)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the options are seen: the other setting moves the layer's output
    other = by_reference(reference, kind, params, x, not rescale, window=9)
    assert np.abs(other - want).max() > 0.01


# one token at context 0, then chunks up to 512 tokens, single tokens at
# contexts 512, 513 and 514 (the first queries that see exactly the window,
# and one line fewer than what is behind them), chunks past the ring's 1,024
# lines (the chunk from 963 on straddles its end), then single tokens
SCHEDULE = [1, 63] + [64] * 7 + [1] * 3 + [64] * 8 + [1] * 3
LENGTH = sum(SCHEDULE)


@pytest.fixture(scope="module")
def windowed():
    """The windowed mixer, its weights, a sequence that wraps the ring and the
    expanded form under the mask over the whole of it."""
    m = mixer("window")
    params = seeded(m)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, LENGTH, H))
    want = np.asarray(jax.jit(lambda p, x: m(
        p, x, ForwardContext(),
        position_ids=jnp.arange(LENGTH, dtype=jnp.int32)[None]))(params, x)[0])
    return m, params, x, want


@pytest.mark.parametrize("paged_kernel", ["pallas", "xla"])
def test_the_ring_walk_is_the_expanded_form_under_the_mask(windowed, paged_kernel):
    """Contexts 1, 512, 513, 514 and past one ring's wrap, decode rows and
    chunk rows, through the kernel (interpreted) and through the gather
    form."""
    m, params, x, want = windowed
    assert RING == 1024 and LENGTH == 1030 > RING and SCHEDULE[9:12] == [1, 1, 1]
    got, view = served(ring_call(m, paged_kernel), params, x, SCHEDULE, empty_ring())
    np.testing.assert_allclose(got, want, atol=3e-5)
    # position p lies at line p % ring: the last six positions wrapped
    line = np.asarray(view.line[0])
    assert np.abs(line[:6]).max() > 0 and not line[:, 32 + 8:].any()


def test_a_window_of_512_fails_it(windowed):
    """The comparison sees an off-by-one window: the same walk with 512 lines
    a query differs from position 512 on, and only from there."""
    m, params, x, want = windowed
    short = mixer("window", window=WINDOW - 1)
    got, _ = served(ring_call(short, "pallas"), params, x[:, :640],
                    SCHEDULE[:12] + [61, 64], empty_ring())
    np.testing.assert_allclose(got[:512], want[:512], atol=3e-5)
    assert np.abs(got[512:] - want[512:640]).max() > 100 * 3e-5


@pytest.mark.parametrize("again", ["another row", "the same row recomputed"])
def test_a_reused_slot_and_a_recomputed_row_see_their_own_lines_alone(
        windowed, again):
    """A row of 1,030 tokens leaves every line of its slot's ring written. The
    next row of the slot (another sequence; or the same one, evicted and
    prefilled again) starts at position 0 over that ring, with no reset by the
    host: a line's position follows from the row's last position, and what is
    not this row's is masked."""
    m, params, x, want = windowed
    call = ring_call(m, "pallas")
    _, dirty = served(call, params, x, SCHEDULE, empty_ring())
    if again == "another row":
        x = jax.random.normal(jax.random.PRNGKey(7), (1, 80, H))
        want = np.asarray(m(params, x, ForwardContext(),
                            position_ids=jnp.arange(80)[None])[0])
    got, _ = served(call, params, x[:, :80], [64] + [1] * 16, dirty)
    np.testing.assert_allclose(got, want[:80], atol=3e-5)


def test_the_kernel_at_its_smallest_grid_is_the_gather_form():
    """Two rows over rings of two tiles, interpreted: a decode-like row whose
    arc wraps the ring's end beside a row that is not live; then a chunk row of
    two query blocks whose arc covers both tiles."""
    m = mixer("window", window=5)
    n, lat, lanes, ring, tile = 4, 32, 32 + 128, 16, 8
    rng = np.random.default_rng(0)
    rings = jnp.zeros((2, ring, lanes)).at[..., :40].set(
        rng.normal(size=(2, ring, 40)))

    def both(q, slot, at, last, first, live):
        got = latent_ring_attention(
            q, rings, slot, at, last, first, live, window=5, lat=lat, tile=tile,
            sm_scale=float(m.scaling_factor), interpret=True)
        rows, positions = at.shape
        flat = RingRows(None, None, last, None, positions,
                        jnp.repeat(slot, positions), at.reshape(-1),
                        (at.reshape(-1) != NOBODY) & jnp.repeat(live, positions), None)
        want = m._attend_gathered_rings(
            q.reshape(rows * positions, n, lanes),
            LatentRingView(rings, None, None), flat)
        return np.asarray(got), np.asarray(want).reshape(got.shape)

    q = jnp.asarray(rng.normal(size=(2, 1, n, lanes)), jnp.float32).at[..., 40:].set(0)
    # row 0: position 17 (line 1), sees 13..17 = lines 13, 14, 15, 0, 1
    got, want = both(q, jnp.asarray([0, 1]), jnp.asarray([[17], [3]]),
                     jnp.asarray([17, 3]), jnp.asarray([13, -1]),
                     jnp.asarray([True, False]))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    assert np.abs(got[0]).max() > 0 and not got[1].any()
    q = jnp.asarray(rng.normal(size=(1, 20, n, lanes)), jnp.float32).at[..., 40:].set(0)
    at = jnp.where(jnp.arange(20) < 11, 20 + jnp.arange(20), NOBODY)[None]
    got, want = both(q, jnp.asarray([1]), at, jnp.asarray([30]), jnp.asarray([16]),
                     jnp.asarray([True]))
    np.testing.assert_allclose(got[0, :11], want[0, :11], atol=2e-5)
    assert not got[0, 11:].any()


def test_a_ring_too_short_for_the_rows_width_is_refused():
    m = mixer("window")
    with pytest.raises(ValueError, match="a ring of 512 lines under rows of up to 64"):
        served(ring_call(m, "xla"), seeded(m), jnp.zeros((1, 64, H)), [64],
               empty_ring(lines=512))
