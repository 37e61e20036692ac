"""``ops/row_scatter.py`` (ISSUE 72): rows of a table by index whose
gradient is a sorted one-hot matmul and not XLA's scatter. The kernel runs
under the interpreter here (``interpret=True``); that Mosaic takes it at the
cell's shapes is held in ``tests/core/test_chip_compile.py``, its time and
its answers on the chip by PERF.md's probe."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from scaling_tpu.obs import kernel_build_count
from scaling_tpu.ops import row_scatter
from scaling_tpu.ops.row_scatter import (
    _staircase, row_scatter_interpret, scatter_add_rows, take_rows,
)


def indices(kind, n, num_rows, rng):
    if kind == "log":  # the benchmark's tokens: half of them below sqrt(v)
        return np.exp(rng.uniform(size=n) * math.log(2 * num_rows)).astype(np.int32) - 3
    if kind == "one-row":
        return np.full(n, 7, np.int32)
    if kind == "all-outside":
        return rng.integers(num_rows, 2 * num_rows, n).astype(np.int32)
    return rng.integers(-5, num_rows + 30, n).astype(np.int32)  # uniform, some outside


def summed(ids, rows, num_rows):
    want = np.zeros((num_rows, rows.shape[1]), np.float32)
    for i, row in zip(ids, rows):
        if 0 <= i < num_rows:
            want[i] += row
    return want


@pytest.mark.parametrize("num_rows,h,n,tiles,kind", [
    (640, 256, 1000, (64, 128), "log"),
    (640, 256, 1000, (64, 128), "uniform"),
    (640, 128, 256, (64, 128), "all-outside"),
    (1000, 128, 513, (256, 128), "one-row"),   # a last block of 232 rows
    (300, 128, 128, (128, 128), "uniform"),    # one chunk over three blocks
    (24, 8, 32, row_scatter._TILES, "uniform"),  # a table smaller than a block
], ids=["log", "uniform", "all-outside", "one-row", "one-chunk", "small-table"])
def test_kernel_sums_the_rows_of_each_index(
        monkeypatch, num_rows, h, n, tiles, kind):
    """Float32 rows: the kernel's sum equals a loop's to float32's rounding
    (the order of a row's summands differs), an index outside the table is
    dropped, and XLA's scatter-add, which stands in off the chip, agrees."""
    monkeypatch.setattr(row_scatter, "_TILES", tiles)
    rng = np.random.default_rng(0)
    ids = indices(kind, n, num_rows, rng)
    rows = rng.normal(size=(n, h)).astype(np.float32)
    want = summed(ids, rows, num_rows)
    before = kernel_build_count("scatter_add_rows", interpret=True)
    got = scatter_add_rows(jnp.asarray(ids), jnp.asarray(rows), num_rows,
                           interpret=True)
    assert kernel_build_count("scatter_add_rows", interpret=True) == before + 1
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
    assert row_scatter_interpret("cpu") is None and row_scatter_interpret("tpu") is False
    stand_in = scatter_add_rows(jnp.asarray(ids), jnp.asarray(rows), num_rows)
    np.testing.assert_allclose(np.asarray(stand_in), want, rtol=1e-4, atol=1e-4)


def test_bf16_rows_are_summed_in_float32_and_rounded_once():
    """3,000 bf16 rows of ones onto one index: a bf16 accumulator stops at
    256 (256 + 1 rounds to 256); the kernel's float32 sum gives 3,000,
    rounded once to bf16 (2,992)."""
    ids = jnp.full((3000,), 5, jnp.int32)
    rows = jnp.ones((3000, 128), jnp.bfloat16)
    got = scatter_add_rows(ids, rows, 64, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = np.zeros((64, 128), np.float32)
    want[5] = float(jnp.asarray(3000.0, jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)), want)


@pytest.mark.parametrize("kind", ["log", "uniform", "one-row", "all-outside"])
def test_staircase_meets_every_pair_that_holds_a_row(kind):
    """The steps are ``blocks + chunks - 1`` whatever the indices; blocks
    never fall, every block is met, and every update's (block, chunk) pair
    is among the steps."""
    num_rows, tr, tk, n = 1000, 64, 128, 1024
    ids = np.sort(np.clip(indices(kind, n, num_rows, np.random.default_rng(1)),
                          -1, num_rows))
    ids = np.where(ids < 0, num_rows, ids)
    ids.sort()
    block, chunk = (np.asarray(x) for x in _staircase(
        jnp.asarray(ids, jnp.int32), num_rows, tr, tk))
    blocks, chunks = -(-num_rows // tr), n // tk
    assert len(block) == blocks + chunks - 1
    assert (np.diff(block) >= 0).all() and (np.diff(chunk) >= 0).all()
    assert set(block) == set(range(blocks)) and set(chunk) == set(range(chunks))
    steps = set(zip(block.tolist(), chunk.tolist()))
    for position, index in enumerate(ids):
        if index < num_rows:
            assert (index // tr, position // tk) in steps


def test_take_rows_reads_zeros_outside_and_differentiates_through_the_sum():
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.normal(size=(40, 16)), jnp.float32)
    ids = jnp.asarray(rng.integers(-3, 45, (2, 6)), jnp.int32)
    weigh = jnp.asarray(rng.normal(size=(2, 6, 16)), jnp.float32)
    inside = (ids >= 0) & (ids < 40)

    def plain(t):
        return jnp.where(inside[..., None], t[jnp.clip(ids, 0, 39)], 0)

    np.testing.assert_array_equal(np.asarray(take_rows(table, ids)),
                                  np.asarray(plain(table)))
    grad = jax.jit(jax.grad(lambda t: (take_rows(t, ids) * weigh).sum()))(table)
    want = jax.grad(lambda t: (plain(t) * weigh).sum())(table)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
