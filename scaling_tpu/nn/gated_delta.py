"""Gated delta-rule mixer (Yang et al. 2024, "Gated Delta Networks",
arXiv:2412.06464): ``jax.numpy``, a Pallas kernel that takes a row of one
token from the input projection to the output projection, and one that
advances a row of several from its state line, in place.

The linear-attention layer of the Qwen3-Next family. ``x`` (.., H) is the
normed residual stream; ``nk`` key heads of ``dk`` lanes, ``nv`` value heads
of ``dv`` lanes, value head ``j`` reads key head ``j // (nv / nk)``:

- ``[q | k | v | z] = x W_in`` (``nk dk | nk dk | nv dv | nv dv`` columns, no
  bias), ``[b | a] = x W_ba`` (``nv | nv``). The columns lie kind by kind; a
  released checkpoint orders them by key head, and the benchmark's view
  (``benchmark/views/gdn_moe_decoder.py``) is where the two orders meet;
- ``c = silu(causal depthwise conv_K([q | k | v]))``, no bias;
- ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``, a value a
  value head, float32;
- ``q <- q rsqrt(sum q^2 + 1e-6) dk^-0.5``, ``k <- k rsqrt(sum k^2 + 1e-6)``;
- a head's state ``S`` (``dk x dv``, float32, from zeros) is read, corrected
  by what it already holds for the key, and written:
  ``S~ = exp(g_t) S_{t-1}``, ``u_t = beta_t (v_t - S~^T k_t)``,
  ``S_t = S~ + k_t u_t^T``, ``o_t = S_t^T q_t``. The transition ``exp(g) (I -
  beta k k^T)`` is NOT diagonal: Mamba-2's closed form (``nn/mamba.py``
  ``ssd_chunk``) does not hold;
- ``y = w_n (o rsqrt(mean o^2 + eps)) silu(z)`` over each head's ``dv`` lanes
  (the norm first, then the gate; one plain weight of ``dv``), ``out = y
  W_out``.

``A_log`` and ``dt_bias`` are float32 leaves; the convolution, the recurrence
and the gated norm run in float32 whatever the model's dtype.

**One chunk, one read of the state** (``delta_chunk``): ``C`` positions
advance from a state ``S_0``. With ``G_i = g_1 + .. + g_i``:

    (I + L) U = diag(beta) (V - diag(exp G) K S_0),
    L_ij = beta_i exp(G_i - G_j) (k_i . k_j) for j < i, else 0;
    o_i = exp(G_i) S_0^T q_i + sum_{j <= i} exp(G_i - G_j) (q_i . k_j) u_j;
    S_C = exp(G_C) S_0 + sum_j exp(G_C - G_j) k_j u_j^T.

``I + L`` is unit lower triangular, ``C x C`` a head. Solving it by
substitution is ``C`` dependent steps; ``unit_lower_inverse`` joins blocks
pairwise from 1 x 1 up, two small products a level (``[[A, 0], [C, B]]^-1 =
[[A^-1, 0], [-B^-1 C A^-1, B^-1]]``): the same arithmetic as a substitution by
blocks, ``log2 C`` levels deep. ``exp(G_i - G_j)`` is formed from the
difference, masked before the exponential, never as a quotient of two
exponentials. A position with ``beta = 0`` and ``g = 0`` neither decays the
state nor writes to it: that is how what is no token (a chunk's padding, an
empty slot) leaves the state as it was, as ``dt = 0`` does in ``ssd_chunk``.

Two callers, the contract ``Mamba2Mixer`` keeps:

- uncached (``logits()``, the tests): each sequence is walked in chunks of
  ``CHUNK`` positions from a zero state, ``delta_chunk`` as plain
  ``jax.numpy`` (differentiated, and the kernels' reference);
- served (``state`` a :class:`DeltaStateView`): one line a (slot, layer),
  ``state (slots, nv, dk, dv)`` float32 and the conv tail ``conv (slots, K - 1,
  C / 128, 128)``, ``C = 2 nk dk + nv dv`` channels: a tap a plane of whole
  tiles with the channels on the lanes (``conv_line``), every row advanced
  from ITS line. Below the full width a row of ONE token takes the single step
  where it lies, from ``in_proj``'s output to ``out_proj``'s input in one
  Pallas kernel (``delta_step``; ``_split_rows`` calls it): a slot a grid
  step, the row's token of ``[q | k | v | z]`` and ``[b | a]`` found among the
  tick's places through the prefetched index of its first place. In VMEM and
  in float32 it takes the conv over the slot's taps and its ``silu``, the two
  L2 norms, ``beta``, ``g`` and the decay, ``q . k``; holds the slot's state,
  reads it once, takes both read-outs ``S^T k`` and ``S^T q`` from it and the
  output from ``o = exp(g) S^T q + (q . k) u``, and writes the new state once
  over the old one (the update depends on a read-out, so the compiler's own
  fusions pass over the state twice); then the gated RMS norm a head. It
  writes the new tail over the old one and the gated output in the model's
  dtype. The at most ``split_capacity`` rows of more advance by the chunk
  form through a second kernel (``delta_chunk_rows``): a grid over the
  prefetched list of those rows, the state operand the WHOLE leaf the step
  returned, aliased to the result; a row's line is brought into VMEM once,
  every product that reads it is taken there (float32, ``HIGHEST``) and the
  new state goes over the old line: no gathered copy of the states and no
  scatter exists. What never touches the state (the conv over the rows'
  places, ``T``, ``D (q . k)``, the decays) stays plain ``jax.numpy``. At the
  full width every row runs that chunk form, the list naming every slot. A
  row whose ``context_len`` is 0 starts from zeros in either form; an empty
  place is untouched.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from . import paged_attention as _paged
from .attention import PagedTokenMap
from .base_layer import BaseLayer, ForwardContext
from .mamba import causal_conv, split_capacity
from .param import ParamMeta
from ..topology.topology import MODEL_AXIS

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
# positions a chunk advances at once: the triangular system is CHUNK x CHUNK a
# head (the released kernels' chunk is 64)
CHUNK = 64
L2_EPS = 1e-6
KERNEL_NAME = "delta_step"
CHUNK_KERNEL_NAME = "delta_chunk_rows"
# key heads a step of the chunk rows' kernel's loop over them (unrolled inside)
KEY_HEADS_A_STEP = 4
# channels a row of a conv line's plane: the chip's lanes
LANES = 128


class DeltaStateView(NamedTuple):
    """One gated delta-rule layer's lines of the serving engine's pool of
    lines a slot (serve/kvcache.py), plus the tick's addressing:
    ``RecurrentStateView``'s counterpart for this mixer. Row ``r`` of the tick
    is slot ``r``'s line."""

    LINES = ("state", "conv")
    NAME = "delta"
    # rows of one token step, rows of more run the chunk form: the engine
    # picks a tick's width by ``split_capacity`` and counts both
    SPLITS = True

    state: jax.Array        # (slots, nv, dk, dv) float32
    conv: jax.Array         # (slots, K - 1, C / 128, 128) the last K - 1 conv
    #                         inputs of the C = 2 nk dk + nv dv channels, oldest
    #                         first, as ``conv_line`` lays them
    context_len: jax.Array  # (slots,) int32 tokens the state has seen
    new_len: jax.Array      # (slots,) int32 real tokens the row brings
    token_map: Optional[PagedTokenMap] = None  # token-major batches


def conv_line(tail):
    """A conv tail ``(.., K - 1, C)`` as its line lies in the pool, ``(.., K -
    1, C / LANES, LANES)``: a tap a plane of whole ``(8, 128)`` tiles with the
    channels on the lanes, so that the step's kernel takes a slot's taps as
    they lie. (As ``(slots, K - 1, C)`` or ``(slots, C, K - 1)`` the chip
    keeps a leaf tap-major, ``(K - 1, slots, C)``, to spare the padding of 3
    to 8: a slot's taps are then rows of three planes, and the kernel's block
    a copy of the whole leaf before and after. PERF.md, PR 76.) Where ``C`` is
    no multiple of ``LANES`` (the tests' toys) a plane is one row."""
    C = tail.shape[-1]
    lanes = LANES if C % LANES == 0 else C
    return tail.reshape(*tail.shape[:-1], C // lanes, lanes)


def _block_product(a, b):
    """``(n, i, j, N) x (n, j, k, N) -> (n, i, k, N)``: small matrices whose
    batch is the long minor axis, as a multiply and a sum."""
    return jnp.sum(a[:, :, :, None, :] * b[:, None, :, :, :], axis=2)


def _pad_positions(t, pad: int):
    """``pad`` more places on axis 1 (a row's positions), zeros."""
    return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))


def unit_lower_inverse(L):
    """``(I + L)^-1`` for ``L`` (.., C, C) STRICTLY lower triangular, float32,
    ``C`` a power of two. Blocks joined pairwise from 1 x 1 up (``[[A, 0], [C,
    B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``): ``log2 C`` levels of two
    small products, where substitution row by row is ``C`` dependent steps. The
    blocks lie ``(block, row, column, every head of every row of the tick)`` and
    a product is a multiply and a sum over that long minor axis: as ``(.., 16,
    16)`` each 16-wide row pads to a register's 128 lanes, and each row update
    of a substitution cost 24 us on the chip, 90 of them a tick (PERF.md, PR
    71)."""
    C = L.shape[-1]
    assert C & (C - 1) == 0, C
    lead = L.shape[:-2]
    Lt = jnp.moveaxis(L.reshape(-1, C, C), 0, -1)          # (C, C, N)
    N = Lt.shape[-1]
    T = jnp.ones((C, 1, 1, N), L.dtype)                    # C blocks of 1 x 1
    size = 1
    while size < C:
        n = C // (2 * size)
        at = jnp.arange(n)
        # the block under each pair's diagonal, (n, size, size, N)
        below = Lt.reshape(n, 2, size, n, 2, size, N)[at, 1, :, at, 0]
        T11, T22 = T[0::2], T[1::2]
        T21 = -_block_product(_block_product(T22, below), T11)
        T = jnp.concatenate([
            jnp.concatenate([T11, jnp.zeros_like(T11)], 2),
            jnp.concatenate([T21, T22], 2)], 1)
        size *= 2
    return jnp.moveaxis(T[0], -1, 0).reshape(*lead, C, C)


def _chunk_operands(q, k, g, beta):
    """What a chunk's advance needs that never touches the state, from
    operands already padded to the system's side ``w``: ``(T, A (r, nv, w, w),
    decay, tail (r, w, nv))``. ``T = (I + L)^-1``; ``A[i, j] = exp(G_i - G_j)
    (q_i . k_j)`` for ``j <= i``, else 0; ``decay_i = exp(G_i)``; ``tail_j =
    exp(G_C - G_j)``: what position ``j`` writes, decayed to the chunk's end."""
    r, w, nk, _ = q.shape
    nv = g.shape[2]
    per = nv // nk
    G = jnp.cumsum(g, axis=1)                               # (r, w, nv), <= 0
    # D[i, j] = exp(G_i - G_j) for j <= i; masked BEFORE the exponential
    # (above the diagonal the difference is positive and may overflow)
    diff = G[:, :, None, :] - G[:, None, :, :]              # (r, i, j, nv)
    causal = jnp.tril(jnp.ones((w, w), bool))[None, :, :, None]
    D = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    D = D.reshape(r, w, w, nk, per)
    kk = jnp.einsum("rihd,rjhd->rijh", k, k, precision=HIGHEST)
    qk = jnp.einsum("rihd,rjhd->rijh", q, k, precision=HIGHEST)
    strict = jnp.tril(jnp.ones((w, w), bool), -1)[None, :, :, None, None]
    b5 = beta.reshape(r, w, 1, nk, per)
    L = jnp.where(strict, b5 * D * kk[..., None], 0.0)
    T = unit_lower_inverse(jnp.moveaxis(L, (1, 2), (-2, -1)))  # (r, nk, per, i, j)
    A = jnp.moveaxis(D * qk[..., None], (1, 2), (-2, -1))
    return (T.reshape(r, nv, w, w), A.reshape(r, nv, w, w), jnp.exp(G),
            jnp.exp(G[:, -1:, :] - G))


def _padded(q, k, v, g, beta):
    """The operands with their positions padded to a power of two, the
    triangular system's side."""
    C = q.shape[1]
    pad = (1 << (C - 1).bit_length()) - C
    if pad:
        q, k, v, g, beta = (_pad_positions(t, pad) for t in (q, k, v, g, beta))
    return q, k, v, g, beta


def delta_chunk(q, k, v, g, beta, S0, fresh=None):
    """Advance every row by one chunk, float32.

    ``q`` and ``k`` (r, C, nk, dk), normalised; ``v`` (r, C, nv, dv); ``g``
    (<= 0) and ``beta`` (r, C, nv), both 0 where the position is no token;
    ``S0`` (r, nv, dk, dv). ``fresh`` (r,) bool: rows that start from zeros
    whatever ``S0`` holds; the choice is made on what is COMPUTED from ``S0``,
    so no zeroed copy of the states is ever written. Returns ``(o (r, C, nv,
    dv), S_C (r, nv, dk, dv))``."""
    r, C, nk, dk = q.shape
    nv, dv = v.shape[2:]
    per = nv // nk
    q, k, v, g, beta = _padded(q, k, v, g, beta)
    width = q.shape[1]
    T, A, decay, tail = _chunk_operands(q, k, g, beta)
    T, A = (t.reshape(r, nk, per, width, width) for t in (T, A))
    decay, tail = (t.reshape(r, width, nk, per) for t in (decay, tail))
    S0g = S0.reshape(r, nk, per, dk, dv)
    kS = jnp.einsum("rihd,rhpdv->rihpv", k, S0g, precision=HIGHEST)
    qS = jnp.einsum("rihd,rhpdv->rihpv", q, S0g, precision=HIGHEST)
    kS, qS = kS * decay[..., None], qS * decay[..., None]
    carried = S0g * decay[:, -1, :, :, None, None]
    if fresh is not None:
        zero = fresh[:, None, None, None, None]
        kS, qS = jnp.where(zero, 0.0, kS), jnp.where(zero, 0.0, qS)
        carried = jnp.where(zero, 0.0, carried)
    vg = v.reshape(r, width, nk, per, dv)
    rhs = (vg - kS) * beta.reshape(r, width, nk, per, 1)
    U = jnp.einsum("rhpij,rjhpv->rihpv", T, rhs, precision=HIGHEST)
    o = qS + jnp.einsum("rhpij,rjhpv->rihpv", A, U, precision=HIGHEST)
    S = carried + jnp.einsum("rjhd,rjhpv->rhpdv", k, U * tail[..., None],
                             precision=HIGHEST)
    return o.reshape(r, width, nv, dv)[:, :C], S.reshape(S0.shape)


def _column(row):
    """``(1, n) -> (n, 1)`` through the diagonal of its broadcast: a select and
    a reduction over the lanes, a few registers for the ``n`` of a layer's
    heads (no transpose of an unaligned shape is asked of Mosaic)."""
    n = row.shape[1]
    diagonal = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
                == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.sum(jnp.where(diagonal, jnp.broadcast_to(row, (n, n)), 0.0),
                   axis=1, keepdims=True)


def _delta_step_kernel(first_ref, ctx_ref, len_ref, proj_ref, ba_ref, tail_ref,
                       w_ref, a_log_ref, dt_ref, norm_ref, state_ref,
                       new_ref, new_tail_ref, y_ref,
                       rows_scr, e_scr, kq_scr, o_scr, *,
                       nk: int, dk: int, eps: float):
    """One slot's step from ``in_proj``'s output to ``out_proj``'s input.

    ``first_ref`` / ``ctx_ref`` / ``len_ref`` (slots,) int32 in SMEM: the
    row's first place of the tick, the tokens its state has seen and the
    tokens it brings. ``proj_ref`` (tb, 2 nk dk + 2 nv dv) and ``ba_ref`` (tb,
    2 nv): the block of ``tb`` places that holds the row's token (the index
    map chose it); ``tail_ref`` (1, K - 1, C / L, L) the slot's last conv
    inputs as ``conv_line`` lays them; ``w_ref`` (K, C / L, L) float32 the
    conv's weight, a tap a plane; ``a_log_ref`` / ``dt_ref`` (1, nv);
    ``norm_ref`` (1, dv); the state (1, nv, dk, dv), read once and written
    once over itself. A row that brings no single token steps with ``beta = g
    = 0``: state and tail are written as they were.

    The prologue is vector work over whole ``(heads, lanes)`` tiles and leaves
    what the loop over the heads reads in VMEM: ``rows_scr`` (3, nv, dv), a
    value head's lane rows ``a = beta v``, ``c = beta decay``, ``d = decay``
    (0: start from zeros); ``e_scr`` (nk, dv), ``q . k`` a key head;
    ``kq_scr`` (dk, 2 nk), a key head's k (then q) a LANE, so that a head's
    key is a column that broadcasts over the state's lanes. The loop leaves a
    head's read-out a row of ``o_scr`` (nv, dv) for the gated norm."""
    i = _paged.pl.program_id(0)
    nv, dv = o_scr.shape
    per = nv // nk
    kd = nk * dk
    taps, tiles, lanes = tail_ref.shape[1:]
    C = tiles * lanes
    steps = len_ref[i] == 1
    fresh = jnp.logical_and(steps, ctx_ref[i] == 0)
    # the row's token among the block's places, float32
    tb = proj_ref.shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (tb, 1), 0) == first_ref[i] % tb
    tok = jnp.sum(jnp.where(at, proj_ref[...].astype(F32), 0.0), axis=0,
                  keepdims=True)                                # (1, W)
    ba = jnp.sum(jnp.where(at, ba_ref[...].astype(F32), 0.0), axis=0,
                 keepdims=True)                                 # (1, 2 nv)
    # the depthwise conv over the K taps: the slot's tail, then the token
    tail = tail_ref[0]                                          # (K - 1, ..)
    old = jnp.where(fresh, 0.0, tail.astype(F32))
    new = tok[:, :C].reshape(tiles, lanes)
    conved = new * w_ref[taps]
    for j in range(taps):
        conved = conved + old[j] * w_ref[j]
    conved = (conved * jax.nn.sigmoid(conved)).reshape(1, C)    # silu
    new_tail_ref[0] = jnp.where(
        steps, jnp.concatenate([old[1:], new[None]], axis=0).astype(tail.dtype),
        tail)
    q = conved[:, :kd].reshape(nk, dk)
    k = conved[:, kd:2 * kd].reshape(nk, dk)
    v = conved[:, 2 * kd:].reshape(nv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    kq_scr[...] = jnp.concatenate([k, q], axis=0).T             # (dk, 2 nk)
    e_scr[...] = jnp.broadcast_to(jnp.sum(q * k, -1, keepdims=True), (nk, dv))
    # the gates, a value a value head: rows of nv lanes, then columns
    beta = jnp.where(steps, jax.nn.sigmoid(ba[:, :nv]), 0.0)
    g = jnp.where(steps, -jnp.exp(a_log_ref[...]) * jax.nn.softplus(
        ba[:, nv:] + dt_ref[...]), 0.0)
    decay = jnp.where(fresh, 0.0, jnp.exp(g))
    rows_scr[0] = _column(beta) * v
    rows_scr[1] = jnp.broadcast_to(_column(beta * decay), (nv, dv))
    rows_scr[2] = jnp.broadcast_to(_column(decay), (nv, dv))
    for kh in range(nk):
        k_col = kq_scr[:, kh:kh + 1]                           # (dk, 1)
        q_col = kq_scr[:, nk + kh:nk + kh + 1]
        e = e_scr[kh:kh + 1, :]                                # (1, dv)
        for h in range(kh * per, (kh + 1) * per):
            a, c, d = (rows_scr[j, h:h + 1, :] for j in range(3))
            # zeros chosen on the state itself: a reused slot may hold anything
            S = jnp.where(d != 0.0, state_ref[0, h], 0.0)      # (dk, dv)
            kS = jnp.sum(S * k_col, axis=0, keepdims=True)     # (1, dv)
            qS = jnp.sum(S * q_col, axis=0, keepdims=True)
            u = a - c * kS
            new_ref[0, h] = d * S + k_col * u
            # o = S_t^T q without reading S_t back
            o_scr[h:h + 1, :] = d * qS + e * u
    # each head's read-out RMS-normed, then the gate
    o = o_scr[...]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    z = tok[:, C:].reshape(nv, dv)
    y_ref[0] = (o * norm_ref[...].astype(F32) * (z * jax.nn.sigmoid(z))
                ).astype(y_ref.dtype)


def delta_step(proj, ba, first, ctx_len, new_len, state, tail, conv_weight,
               a_log, dt_bias, norm_weight, eps: float, interpret: bool):
    """The single step of every row that brings ONE token, from ``in_proj``'s
    output to ``out_proj``'s input, a Pallas kernel that holds a slot's state
    in VMEM: read once, used twice (the read-outs for the key and for the
    query), written once over itself. Around the step it does, in float32 and
    in VMEM, what the step's operands and its read-out need: the depthwise
    conv over the tail and its ``silu``, q and k normalised, ``beta``, ``g``
    and the decay, ``q . k``, then the gated RMS norm a head. As plain
    ``jax.numpy`` the chip's compiler makes two passes over the state (PERF.md,
    PR 71) and ~45 operations a layer of the rest, each over every slot's row
    (PR 76).

    ``proj`` (T, 2 nk dk + 2 nv dv) = ``[q | k | v | z]`` and ``ba`` (T, 2
    nv) = ``[b | a]``, the tick's places in the model's dtype; ``first``
    (slots,) int32 the place of each row's first token, ascending (scalar
    prefetch: the index map picks the block of places that holds it);
    ``ctx_len`` and ``new_len`` (slots,) int32; ``state`` (slots, nv, dk, dv)
    float32; ``tail`` (slots, K - 1, C / L, L), a ``conv_line``;
    ``conv_weight`` (C, K); ``a_log``, ``dt_bias`` (nv,); ``norm_weight``
    (dv,). A row steps where ``new_len ==
    1`` (from zeros where ``ctx_len == 0``); any other row keeps its state and
    its tail bit for bit and its output is not to be read. Returns ``(y
    (slots, nv dv) in ``proj``'s dtype, state, tail)``."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    count_kernel_build(KERNEL_NAME, interpret)
    r, nv, dk, dv = state.shape
    T, width = proj.shape
    taps, tiles, lanes = tail.shape[1:]
    C = tiles * lanes
    nk = (C - nv * dv) // (2 * dk)
    assert width == C + nv * dv and C == 2 * nk * dk + nv * dv, (
        proj.shape, tail.shape, state.shape)
    # places a block: a packed tile of the model's dtype, or the whole tick
    # (Mosaic takes no block of ONE row of a 2-D operand, and no dynamic row
    # of a packed dtype: the kernel selects the row among the block's)
    tb = min(T, 16)

    def place(i, first, *_):    # the block that holds the row's first place
        return first[i] // tb, 0

    def slot(rank):             # the slot's own block of an operand
        return lambda i, *_: (i,) + (0,) * (rank - 1)

    def whole(rank):            # the same block every step: brought in once
        return lambda i, *_: (0,) * rank

    block = 2 * nv * dk * dv * 4                                # state in and out
    beside = 2 * (tb * (width + 2 * nv) + 2 * taps * C) * proj.dtype.itemsize
    new_state, new_tail, y = pl.pallas_call(
        functools.partial(_delta_step_kernel, nk=nk, dk=dk, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(r,),
            in_specs=[pl.BlockSpec((tb, width), place),
                      pl.BlockSpec((tb, 2 * nv), place),
                      pl.BlockSpec((1, taps, tiles, lanes), slot(4)),
                      pl.BlockSpec((taps + 1, tiles, lanes), whole(3)),
                      pl.BlockSpec((1, nv), whole(2)),
                      pl.BlockSpec((1, nv), whole(2)),
                      pl.BlockSpec((1, dv), whole(2)),
                      pl.BlockSpec((1, nv, dk, dv), slot(4))],
            out_specs=[pl.BlockSpec((1, nv, dk, dv), slot(4)),
                       pl.BlockSpec((1, taps, tiles, lanes), slot(4)),
                       pl.BlockSpec((1, nv, dv), slot(3))],
            scratch_shapes=[pltpu.VMEM((3, nv, dv), F32),
                            pltpu.VMEM((nk, dv), F32),
                            pltpu.VMEM((dk, 2 * nk), F32),
                            pltpu.VMEM((nv, dv), F32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype),
                   jax.ShapeDtypeStruct((r, nv, dv), proj.dtype)],
        # operands counted with the three prefetched: state and tail in place
        input_output_aliases={10: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # both blocks of the state double-buffered, the small blocks
            # beside them, and room
            vmem_limit_bytes=max(2 * block + beside + (8 << 20), 16 << 20)),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(first, ctx_len, new_len, proj, ba, tail,
      conv_weight.T.astype(F32).reshape(taps + 1, tiles, lanes),
      a_log.reshape(1, nv), dt_bias.reshape(1, nv), norm_weight.reshape(1, dv),
      state)
    return y.reshape(r, nv * dv), new_state, new_tail


def _dot(a, b, contract=((1,), (0,))):
    """A float32 product at the precision the state's products are held to."""
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=HIGHEST,
                               preferred_element_type=F32)


def _delta_chunk_kernel(src_ref, line_ref, real_ref, fresh_ref, q_ref, k_ref,
                        v_ref, t_ref, a_ref, cols_ref, state_ref,
                        new_ref, o_ref):
    """One place of the list of chunk rows: its row's line of the state, read
    once, every product that reads it, and the new state over the old one.

    ``src_ref`` / ``line_ref`` / ``real_ref`` / ``fresh_ref`` (R,) int32 in
    SMEM: the place whose blocks this grid step sits on (itself, or the last
    filled place where no row fills it), that place's slot, whether a row
    fills the place and whether it starts from zeros. ``q_ref`` / ``k_ref``
    (1, w, nk dk), ``v_ref`` (1, w, nv dv), ``t_ref`` / ``a_ref`` (1, nv, w,
    w), ``cols_ref`` (1, nk, w, 3 per) = a key head's ``[decay | beta |
    tail]``, a value head a lane; the state (1, nv, dk, dv). A place no row
    fills sits on the blocks of the place before it and touches nothing:
    nothing is fetched for it and nothing written anew. (Were there no filled
    place at all the blocks would still be written back once: place 0 then
    copies its line through.)

    The key heads are a LOOP of ``KEY_HEADS_A_STEP`` heads a step: every
    index that depends on the head is a leading one or a whole tile of lanes,
    and a step's body is traced and lowered once. (Unrolled over all 32 value
    heads the kernel cost the cell's set-up 0.75 s a delta layer a program, 9
    s in all; one key head a step costs the kernel 12 us of its 235 a call,
    the scheduler having less to overlap: PERF.md, PR 78.)"""
    pl = _paged.pl
    i = pl.program_id(0)
    nv, dk, dv = state_ref.shape[1:]
    nk, w = cols_ref.shape[1:3]
    per = nv // nk

    @pl.when(real_ref[i] == 1)
    def _():
        fresh = fresh_ref[i] == 1
        last = jax.lax.broadcasted_iota(jnp.int32, (w, dv), 0) == w - 1

        def key_head(kh):
            lanes = pl.ds(pl.multiple_of(kh * dk, dk), dk)
            k = k_ref[0, :, lanes]                                  # (w, dk)
            kq = jnp.concatenate([k, q_ref[0, :, lanes]], axis=0)
            cols = cols_ref[0, kh]
            for p in range(per):
                h = kh * per + p
                lanes = pl.ds(pl.multiple_of(h * dv, dv), dv)
                decay, beta, tail = (
                    cols[:, j * per + p:j * per + p + 1] for j in range(3))
                # zeros chosen on the state itself: a reused slot may hold
                # anything
                S0 = jnp.where(fresh, 0.0, state_ref[0, h])         # (dk, dv)
                read = _dot(kq, S0)                                 # K S0, Q S0
                decay = jnp.broadcast_to(decay, (w, dv))
                kS, qS = read[:w] * decay, read[w:] * decay
                U = _dot(t_ref[0, h], beta * (v_ref[0, :, lanes] - kS))
                o_ref[0, :, lanes] = qS + _dot(a_ref[0, h], U)
                # exp(G_C), the last position's decay, as a row of lanes: a
                # select and a sum (Mosaic broadcasts no single value along
                # both axes, and a slice of the broadcast folds into one)
                carry = jnp.sum(jnp.where(last, decay, 0.0), axis=0, keepdims=True)
                new_ref[0, h] = carry * S0 + _dot(k, U * tail, ((0,), (0,)))

        together = math.gcd(nk, KEY_HEADS_A_STEP)

        def step(n, _):
            for j in range(together):
                key_head(n * together + j)
            return _

        jax.lax.fori_loop(0, nk // together, step, 0)

    @pl.when(jnp.logical_and(real_ref[i] == 0, i == 0))
    def _():
        new_ref[...] = state_ref[...]


def delta_chunk_rows(q, k, v, g, beta, state, fresh, at, interpret: bool):
    """``delta_chunk`` for rows whose states are lines of the pool's leaf: a
    Pallas kernel over the list of chunk rows that holds a row's state in VMEM
    once, takes every product that reads it there (the two read-outs ``K S0``
    and ``Q S0`` with their decay, ``U = T beta (V - K S0)``, ``o = Q S0 + A
    U``, ``S = exp(G_C) S0 + K^T (U tail)``; float32, ``HIGHEST``) and writes
    the new state over the old line. No gathered copy of the states and no
    scatter exists: ``state`` is the WHOLE leaf, aliased to the result, and
    the index map sends place ``i`` to slot ``at[i]``. As plain ``jax.numpy``
    the chip's compiler read the gathered states five times and scattered them
    back (PERF.md, PR 78). What never touches the state stays outside
    (``_chunk_operands``).

    ``q`` .. ``beta`` as ``delta_chunk`` takes them, a row a place of the
    list; ``state`` (slots, nv, dk, dv) float32; ``fresh`` (R,) bool; ``at``
    (R,) int32, the slot of each place's row, distinct, the filled places
    FIRST and ``slots`` in every place no row fills: such a place moves
    nothing and writes nothing. Returns ``(o (R, C, nv, dv), state)``, ``o``
    undefined at a place no row fills."""
    _paged._ensure_pallas()
    count_kernel_build(CHUNK_KERNEL_NAME, interpret)
    R, C, nk, dk = q.shape
    slots, nv, _, dv = state.shape
    q, k, v, g, beta = _padded(q, k, v, g, beta)
    w = q.shape[1]
    T, A, decay, tail = _chunk_operands(q, k, g, beta)
    # a key head's columns side by side: (R, nk, w, [decay | beta | tail] x per)
    cols = jnp.stack([decay, beta, tail], axis=2).reshape(R, w, 3, nk, nv // nk)
    cols = jnp.moveaxis(cols, 3, 1).reshape(R, nk, w, 3 * (nv // nk))
    real = at < slots
    # an unfilled place sits on the last filled place's blocks. NOT on slot
    # ``slots - 1``'s: under the aliasing its stale copy would be written over
    # a line that a filled place of this very call advances
    src = jnp.minimum(jnp.arange(R, dtype=jnp.int32),
                      jnp.maximum(jnp.sum(real, dtype=jnp.int32) - 1, 0))
    line = jnp.minimum(at[src], slots - 1).astype(jnp.int32)
    new_state, o = _chunk_rows_call(
        src, line, real.astype(jnp.int32), fresh.astype(jnp.int32),
        q.reshape(R, w, nk * dk), k.reshape(R, w, nk * dk),
        v.reshape(R, w, nv * dv), T, A, cols, state, interpret=interpret)
    return o.reshape(R, w, nv, dv)[:, :C], new_state


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_rows_call(src, line, real, fresh, q, k, v, T, A, cols, state, *,
                     interpret: bool):
    """``delta_chunk_rows``' kernel call, a ``jit`` of its own inside the
    caller's: a model's delta layers have one shape, so they share ONE trace
    of the kernel and one lowering a program (the compiler inlines the calls).
    Traced a layer, it was 2.6 s of the cell's set-up (PERF.md, PR 78)."""
    pl, pltpu = _paged.pl, _paged.pltpu
    R, w, _ = q.shape
    nv, dk, dv = state.shape[1:]
    nk = cols.shape[1]

    def place(rank):            # the place's own block of an operand
        return lambda i, src, *_: (src[i],) + (0,) * (rank - 1)

    def slot(i, src, line, *_):
        return line[i], 0, 0, 0

    block = 2 * nv * dk * dv * 4                                # state in and out
    beside = 2 * 4 * w * (2 * nk * dk + 2 * nv * dv + 2 * nv * max(w, LANES)
                          + nk * LANES)
    return pl.pallas_call(
        _delta_chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(R,),
            in_specs=[pl.BlockSpec((1, w, nk * dk), place(3)),
                      pl.BlockSpec((1, w, nk * dk), place(3)),
                      pl.BlockSpec((1, w, nv * dv), place(3)),
                      pl.BlockSpec((1, nv, w, w), place(4)),
                      pl.BlockSpec((1, nv, w, w), place(4)),
                      pl.BlockSpec((1, nk, w, 3 * (nv // nk)), place(4)),
                      pl.BlockSpec((1, nv, dk, dv), slot)],
            out_specs=[pl.BlockSpec((1, nv, dk, dv), slot),
                       pl.BlockSpec((1, w, nv * dv), place(3))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32),
                   jax.ShapeDtypeStruct((R, w, nv * dv), F32)],
        # operands counted with the four prefetched: the state in place
        input_output_aliases={10: 0},
        compiler_params=pltpu.CompilerParams(
            # a place no row fills REVISITS the blocks of the one before it
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(2 * block + beside + (8 << 20), 16 << 20)),
        interpret=interpret,
        name=CHUNK_KERNEL_NAME,  # the trace's and the HLO's name for it
    )(src, line, real, fresh, q, k, v, T, A, cols, state)


class GatedDeltaMixer(BaseLayer):
    # the view of the serving state a layer with this mixer is handed
    STATE_VIEW = DeltaStateView

    def __init__(self, hidden_size: int, num_key_heads: int,
                 num_value_heads: int, key_head_dim: int, value_head_dim: int,
                 conv_kernel: int, norm_eps: float = 1e-6,
                 time_step_min: float = 0.001, time_step_max: float = 0.1,
                 time_step_floor: float = 1e-4, dtype=None):
        assert num_value_heads % num_key_heads == 0, (
            num_value_heads, num_key_heads)
        self.hidden_size = hidden_size
        self.nk, self.nv = num_key_heads, num_value_heads
        self.dk, self.dv = key_head_dim, value_head_dim
        self.conv_kernel = conv_kernel
        self.norm_eps = norm_eps
        self.time_step = (time_step_min, time_step_max, time_step_floor)
        self.dtype = dtype or jnp.float32
        self.key_dim = num_key_heads * key_head_dim
        self.value_dim = num_value_heads * value_head_dim
        self.conv_dim = 2 * self.key_dim + self.value_dim
        self.in_width = self.conv_dim + self.value_dim

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        """Seeded init: ``A`` uniform in [1, 16] and the time step log-uniform
        in [time_step_min, time_step_max] (stored as its inverse softplus,
        ``dt_bias``), as ``Mamba2Mixer`` and the delta rule's released code
        start them, so that a fresh state's memory is tens of positions
        long; the conv uniform in +-1/sqrt(K), matrices Xavier-normal."""
        ks = jax.random.split(key, 6)
        H, K = self.hidden_size, self.conv_kernel
        lo, hi, floor = self.time_step

        def xavier(k, shape):
            std = math.sqrt(2.0 / (shape[0] + shape[1]))
            return (jax.random.normal(k, shape) * std).astype(self.dtype)

        dt = jnp.exp(jax.random.uniform(ks[2], (self.nv,))
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        bound = 1.0 / math.sqrt(K)
        return {
            "in_proj": {"weight": xavier(ks[0], (H, self.in_width))},
            "ba_proj": {"weight": xavier(ks[5], (H, 2 * self.nv))},
            "conv": {"weight": jax.random.uniform(
                ks[1], (self.conv_dim, K), minval=-bound, maxval=bound
            ).astype(self.dtype)},
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(F32),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (self.nv,), minval=1.0, maxval=16.0)).astype(F32),
            "norm": {"weight": jnp.ones((self.dv,), self.dtype)},
            "out_proj": {"weight": xavier(ks[4], (self.value_dim, H))},
        }

    def param_metas(self) -> dict:
        def replicated(name, dims):
            return ParamMeta(parameter_name=name,
                             partition_spec=(None,) * dims,
                             is_model_parallel_duplicate=True)

        # model parallelism over a pattern stack is refused (config.py): the
        # specs say how the matrices WOULD split, nothing runs sharded yet
        return {
            "in_proj": {"weight": replicated("in_proj.weight", 2)},
            "ba_proj": {"weight": replicated("ba_proj.weight", 2)},
            "conv": {"weight": replicated("conv.weight", 2)},
            "dt_bias": replicated("dt_bias", 1),
            "A_log": replicated("A_log", 1),
            "norm": {"weight": replicated("norm.weight", 1)},
            "out_proj": {"weight": ParamMeta(
                parameter_name="out_proj.weight",
                partition_spec=(MODEL_AXIS, None), is_model_parallel=True,
                model_parallel_dimension=0)},
        }

    # --------------------------------------------------------------- forward
    def _conv(self, params, window):
        return causal_conv(window, params["conv"]["weight"], jnp.zeros((), F32))

    def _delta_inputs(self, params, conved, ba, real):
        """The recurrence's operands from the conv's output (.., conv_dim)
        float32 and the raw ``[b | a]`` (.., 2 nv); ``real`` (..) bool or
        None: ``(q, k (.., nk, dk), v (.., nv, dv), g, beta (.., nv))``."""
        lead = conved.shape[:-1]
        q = conved[..., :self.key_dim].reshape(*lead, self.nk, self.dk)
        k = conved[..., self.key_dim:2 * self.key_dim].reshape(
            *lead, self.nk, self.dk)
        v = conved[..., 2 * self.key_dim:].reshape(*lead, self.nv, self.dv)
        q = q * jax.lax.rsqrt(
            jnp.sum(q * q, -1, keepdims=True) + L2_EPS) * self.dk ** -0.5
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
        ba = ba.astype(F32)
        beta = jax.nn.sigmoid(ba[..., :self.nv])
        g = -jnp.exp(params["A_log"]) * jax.nn.softplus(
            ba[..., self.nv:] + params["dt_bias"])
        if real is not None:
            beta = jnp.where(real[..., None], beta, 0.0)
            g = jnp.where(real[..., None], g, 0.0)
        return q, k, v, g, beta

    def _gated(self, params, o, z):
        """Each head's ``o`` RMS-normed, times ``silu(z)``: ``out_proj``'s
        input. ``o`` float32 (.., nv, dv), ``z`` the model's dtype (.., nv
        dv)."""
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + self.norm_eps)
        o = o * params["norm"]["weight"].astype(F32)
        return (o.reshape(z.shape) * jax.nn.silu(z.astype(F32))).astype(z.dtype)

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext,
                 state: Optional[DeltaStateView] = None,
                 return_state: bool = False):
        """``x`` (b, s, H). Without ``state`` each of the ``b`` sequences is
        walked whole from a zero state (``return_state``: also its final
        ``(state, conv)`` lines); with ``state`` the batch is the tick's, and
        the second result is the view with its lines advanced."""
        with jax.named_scope("delta"):
            proj = x @ params["in_proj"]["weight"].astype(x.dtype)
            ba = x @ params["ba_proj"]["weight"].astype(x.dtype)
            if state is not None:
                y, lines = self._serve(params, proj, ba, state)
            else:
                y, lines = self._whole(params, proj, ba)
            out = y @ params["out_proj"]["weight"].astype(x.dtype)
            return (out, lines) if state is not None or return_state else out

    def _advance(self, q, k, v, g, beta, S0, fresh, at=None):
        """``(r, w)`` whole rows from their states: one chunk, or ``CHUNK``
        positions at a time where a row is wider. ``at`` None: ``S0`` holds a
        state a row (the uncached pass: plain ``jax.numpy``, differentiated).
        Else ``S0`` is the pool's whole leaf and row ``i`` advances its line
        ``at[i]`` in place (``delta_chunk_rows``)."""
        chunk = delta_chunk if at is None else functools.partial(
            delta_chunk_rows, at=at, interpret=_paged.paged_kernel_interpret())
        with jax.named_scope("delta_rule"):
            r, w = g.shape[:2]
            if w <= CHUNK:
                return chunk(q, k, v, g, beta, S0, fresh)
            pad = -w % CHUNK
            parts = tuple(
                jnp.moveaxis(_pad_positions(t, pad).reshape(
                    r, -1, CHUNK, *t.shape[2:]), 1, 0)
                for t in (q, k, v, g, beta))
            first = jnp.arange(parts[0].shape[0]) == 0

            def step(S, part):
                *operands, is_first = part
                zero = None if fresh is None else fresh & is_first
                o, S = chunk(*operands, S, zero)
                return S, o

            S, o = jax.lax.scan(step, S0, (*parts, first))
            o = jnp.moveaxis(o, 0, 1).reshape(r, w + pad, *o.shape[3:])
            return o[:, :w], S

    def _whole(self, params, proj, ba):
        """Every sequence of a ``(b, s)`` batch from a zero state: ``(y (b, s,
        nv dv), (state, conv))``."""
        b, s, _ = proj.shape
        K = self.conv_kernel
        window = jnp.pad(proj[..., :self.conv_dim], ((0, 0), (K - 1, 0), (0, 0)))
        operands = self._delta_inputs(params, self._conv(params, window), ba, None)
        S0 = jnp.zeros((b, self.nv, self.dk, self.dv), F32)
        o, S = self._advance(*operands, S0, None)
        y = self._gated(params, o, proj[..., self.conv_dim:])
        return y, (S, conv_line(window[:, s:]))

    def _serve(self, params, proj, ba, view: DeltaStateView):
        """The tick's batch ``(g, s)`` against the slots' lines: whole rows
        where the batch has a place for every row's widest chunk, else each
        row in the form its ``new_len`` asks for. Returns ``(y (g, s, nv dv),
        the view advanced)``."""
        g, s = proj.shape[:2]
        lines = (view.state, view.conv, view.context_len.astype(jnp.int32),
                 view.new_len.astype(jnp.int32))
        C = self.conv_dim
        tmap = view.token_map
        if tmap is None:  # row-major: position (r, j) is row r's j-th token
            o, S, tail = self._chunk_rows(params, proj[..., :C], ba, *lines)
            y = self._gated(params, o, proj[..., C:])
        else:
            rows, w = tmap.row_tokens.shape
            proj, ba = proj.reshape(g * s, -1), ba.reshape(g * s, -1)
            if g * s < rows * w:
                y, S, tail = self._split_rows(params, proj, ba, *lines, tmap)
            else:
                flat = tmap.row_tokens
                o, S, tail = self._chunk_rows(
                    params, proj[:, :C][flat], ba[flat], *lines)
                # back to the batch's token order
                o = o[tmap.row.reshape(-1),
                      jnp.minimum(tmap.offset.reshape(-1), w - 1)]
                y = self._gated(params, o, proj[:, C:])
            y = y.reshape(g, s, self.value_dim)
        return y, view._replace(state=S.astype(view.state.dtype),
                                conv=tail.astype(view.conv.dtype))

    def _chunk_rows(self, params, qkv, ba, state, conv, ctx_len, new_len,
                    at=None):
        """The whole-rows form: every row of ``(r, w)`` advances by the chunk
        form, ``new_len`` of its places real, from line ``at[r]`` of ``state``
        (the pool's whole leaf, advanced in place; ``at`` as
        ``delta_chunk_rows`` takes it, None: row ``r`` is slot ``r``) and from
        its tail ``conv[r]``. Returns ``(o (r, w, nv, dv), state, conv)``,
        float32 but the tail (``qkv``'s dtype)."""
        r, w = ba.shape[:2]
        if at is None:
            at = jnp.arange(r, dtype=jnp.int32)
        K = self.conv_kernel
        real = jnp.arange(w, dtype=jnp.int32)[None, :] < new_len[:, None]
        # a row at context 0 starts from zeros, whatever its slot held
        fresh = (ctx_len == 0) & (new_len > 0)
        tail = jnp.where(fresh[:, None, None], 0, conv.reshape(r, K - 1, -1))
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
        operands = self._delta_inputs(
            params, self._conv(params, window), ba, real)
        o, S = self._advance(*operands, state.astype(F32), fresh, at)
        # each channel's last K - 1 inputs, the row's new ones included: the
        # window's places new_len .. new_len + K - 2 (new_len 0: the old tail)
        last = new_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(window, last[:, :, None], axis=1)
        return o, S, conv_line(new_tail)

    def _split_rows(self, params, proj, ba, state, conv, ctx_len, new_len, tmap):
        """A token-major batch ``(T, ..)`` narrower than ``rows x w``: rows
        that bring one token step where they lie, ``delta_step`` taking each
        from ``proj`` to its gated output; the at most ``T // w`` that bring
        more (the caller sees to that: ``split_capacity``) are listed, their
        places gathered, and advanced as whole rows, each state line where it
        lies (``delta_chunk_rows``). Returns ``(y (T, nv dv), state,
        conv)``."""
        rows, w = tmap.row_tokens.shape
        R = split_capacity(proj.shape[0], w)
        with jax.named_scope("delta_rule"):
            y_step, S, tail = delta_step(
                proj, ba, tmap.row_tokens[:, 0], ctx_len, new_len,
                state.astype(F32), conv.astype(proj.dtype),
                params["conv"]["weight"], params["A_log"], params["dt_bias"],
                params["norm"]["weight"], self.norm_eps,
                _paged.paged_kernel_interpret())
        multi = new_len > 1
        # the multi-token rows in slot order, then `rows`: past the pool, the
        # mark of a place no row fills, of which nothing is moved or written.
        # A chunk row has stepped with beta = g = 0: its lines are still the
        # old ones, and the chunk's kernel advances its state where it lies
        at, = jnp.nonzero(multi, size=R, fill_value=rows)
        held = jnp.minimum(at, rows - 1)
        flat = tmap.row_tokens[held]                         # (R, w)
        o_chunk, S, tail_chunk = self._chunk_rows(
            params, proj[:, :self.conv_dim][flat], ba[flat], S, tail[held],
            ctx_len[held], jnp.where(at < rows, new_len[held], 0), at)
        tail = tail.at[at].set(tail_chunk.astype(tail.dtype), mode="drop")
        # a token of a chunk row reads its place in its row's chunk and is
        # gated where it lies (its z needs no gather); any other reads its
        # row's step, gated by the kernel
        row, offset = tmap.row.reshape(-1), tmap.offset.reshape(-1)
        place = jnp.clip(jnp.cumsum(multi)[row] - 1, 0, R - 1)
        place = place * w + jnp.minimum(offset, w - 1)
        y_chunk = self._gated(
            params, o_chunk.reshape(R * w, self.nv, self.dv)[place],
            proj[:, self.conv_dim:])
        return jnp.where(multi[row][:, None], y_chunk, y_step[row]), S, tail
