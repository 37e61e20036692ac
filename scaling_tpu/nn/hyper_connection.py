"""Manifold-constrained hyper-connections: the residual path as ``n`` streams.

(mHC, arXiv:2512.24880, on hyper-connections, arXiv:2409.19606.) The residual
``x <- x + F(norm(x))`` keeps ONE stream. Here a token carries ``n`` of them,
``X`` in ``R^{n x C}``, and every sub-layer has a mapping of its own that
reads a learned mix of the streams, and writes its output back into all of
them while mixing them with a doubly stochastic matrix. Per token, float32:

    x      = vec(X)                                     in R^{nC}
    r      = rsqrt(mean(x^2) + norm_eps)
    m      = (x phi) r                                  phi (nC, n^2 + 2n)
    H_pre  = sigmoid(a_pre m[0:n] + b_pre) + eps                     (n,)
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                      (n,)
    S      = clip(a_res mat(m[2n:]) + b_res, clamp)                  (n, n)
    M      = softmax over each row of S, + eps;  M <- M / (column sums + eps)
             then ``sinkhorn_iters - 1`` times:  M <- M / (row sums + eps);
                                                 M <- M / (column sums + eps)
    u      = sum_j H_pre[j] X[j]                        what the sub-layer reads
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y           y = F(norm(u))

After the last sub-layer ``HyperReadout`` folds the streams into one:
``h = sum_j (sigmoid(a_h (x phi_h) r + b_h) + eps)[j] X[j]``.

Layout, because it decides the cost. The stream is ``(..., n * C)``: stream
``j`` is lanes ``[j C, (j + 1) C)`` of the minor axis, so a stream is a
lane-aligned slice (``C`` a multiple of 128 at a model's widths), ``vec(X)``
is the array itself and every ``(batch, seq, width)`` consumer (the serving
program's gather of the sampled positions among them) sees one more width. A
``(..., n, C)`` array would put ``n = 4`` on the sublanes of a tile of 8 or 16.
Everything a token has ``n^2 + 2n`` of keeps the TOKEN axis minor: ``m`` is
``(n^2 + 2n, tokens)`` and the entries of ``M`` are ``n^2`` vectors over the
tokens, the ``n x n`` unrolled over Python integers, so that a Sinkhorn step is
a handful of elementwise operations on full registers of tokens
(``sinkhorn_tokens``, a Pallas kernel); a ``(tokens, n, n)`` float32 array would
tile to ``(8, 128)`` a token, 4 KiB for 64 bytes. ``pre`` is a pass over ``X``
for the statistic, one for ``x phi`` (a matmul) and one for ``u``; ``post`` one
pass (``X``, ``y`` in; ``X'`` out). On a v5e the compiler keeps a tick's stream
(25.7 MB at 896 tokens) in fast memory between those passes, and the statistic,
a lane reduction of 14,336 values a token, is the largest part of the path
(PERF.md, PR 65).

``x phi`` in float32 in earnest at the price of one bf16 pass: a bf16 stream
is exact in bf16, so only ``phi`` needs its 24 bits, and it is split into
three bf16 terms (``reduce_precision``: a convert round trip could be
simplified away) that ride the matmul's output lanes, ``3 (n^2 + 2n) = 72`` of
a tile's 128; the three partial products are summed in float32. A stream in
any other dtype takes the plain float32 matmul at the highest precision.
"""

from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp

from ..obs import count_kernel_build
from . import paged_attention as _paged
from .base_layer import BaseLayer
from .param import replicated_meta

F32 = jnp.float32
KERNEL_NAME = "hc_sinkhorn"
# a register of float32: the kernel's tile of tokens
SUBLANES, LANES = 8, 128
TILE_TOKENS = SUBLANES * LANES
# a mapping's seeded leaves (``HyperConnection.init`` says why each is wide)
RES_BIAS_RANGE = 60.0
RES_ALPHA = 2.0
GATE_BIAS_STD = 3.0


def project(x: jax.Array, phi: jax.Array) -> jax.Array:
    """``(x phi)^T`` in float32: ``(k, tokens)`` of ``x`` (tokens, d) and
    ``phi`` (d, k) float32 (the module docstring: one bf16 pass for a bf16
    ``x``)."""
    if x.dtype != jnp.bfloat16:
        return jnp.einsum("td,dk->kt", x.astype(F32), phi,
                          precision=jax.lax.Precision.HIGHEST)
    k = phi.shape[1]
    terms, rest = [], phi
    for _ in range(3):
        term = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        terms.append(term.astype(jnp.bfloat16))
        rest = rest - term
    parts = jnp.einsum("td,dk->kt", x, jnp.concatenate(terms, axis=1),
                       preferred_element_type=F32)
    return parts[:k] + parts[k:2 * k] + parts[2 * k:]


def scaled_projection(x: jax.Array, phi: jax.Array, norm_eps: float) -> jax.Array:
    """``m = (x phi) r``, ``(k, tokens)`` float32, of ``x`` (tokens, d)."""
    x32 = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1) + norm_eps)
    return project(x, phi) * r[None, :]


def sinkhorn(rows: List[List[jax.Array]], iters: int, eps: float
             ) -> List[List[jax.Array]]:
    """``rows[i][j]``: the entries of ``S`` (after the clamp), each an array
    over tokens. The first step is the softmax over each row (+ ``eps``),
    then the columns; ``iters - 1`` further steps of rows then columns."""
    n = len(rows)

    def over_columns(M):
        sums = [sum(M[i][j] for i in range(n)) + eps for j in range(n)]
        return [[M[i][j] / sums[j] for j in range(n)] for i in range(n)]

    def step(_, M):
        M = [[x / (sum(row) + eps) for x in row] for row in M]
        return over_columns(M)

    M = []
    for row in rows:
        top = row[0]
        for s in row[1:]:
            top = jnp.maximum(top, s)
        e = [jnp.exp(s - top) for s in row]
        total = sum(e)
        M.append([x / total + eps for x in e])
    return jax.lax.fori_loop(0, iters - 1, step, over_columns(M))


def _sinkhorn_kernel(s_ref, out_ref, *, n: int, iters: int, eps: float):
    """One tile of tokens: every entry of the ``n x n`` a full ``(8, 128)``
    register of them."""
    M = sinkhorn([[s_ref[i * n + j] for j in range(n)] for i in range(n)],
                 iters, eps)
    for i in range(n):
        for j in range(n):
            out_ref[i * n + j] = M[i][j]


@functools.partial(jax.jit, static_argnames=("n", "iters", "eps", "interpret"))
def sinkhorn_tokens(s: jax.Array, *, n: int, iters: int, eps: float,
                    interpret: bool) -> jax.Array:
    """``s`` (n * n, tokens) float32, the clamped mixing logits row-major ->
    the Sinkhorn projections, same shape. A Pallas kernel (off a TPU
    interpreted): as plain XLA the steps, unrolled or as a ``fori_loop`` (which
    the chip's compiler unrolls), fuse into one operation that it takes
    minutes to compile (6 steps 4 s, 14 over a minute, 20 not in ten; a
    mapping a kind of layer a program). Here a step is ~60 operations on full
    registers, the loop stays a loop and compiles in a second."""
    _paged._ensure_pallas()
    pl, pltpu = _paged.pl, _paged.pltpu
    count_kernel_build(KERNEL_NAME, interpret)
    tokens = s.shape[1]
    s = jnp.pad(s, ((0, 0), (0, -tokens % TILE_TOKENS))).reshape(n * n, -1, LANES)
    block = pl.BlockSpec((n * n, SUBLANES, LANES), lambda t: (0, t, 0))
    out = pl.pallas_call(
        functools.partial(_sinkhorn_kernel, n=n, iters=iters, eps=eps),
        grid=(s.shape[1] // SUBLANES,),
        in_specs=[block], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(s.shape, F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
        name=KERNEL_NAME,  # the trace's and the HLO's name for it
    )(s)
    return out.reshape(n * n, -1)[:, :tokens]


def combine(x: jax.Array, weights: jax.Array, hidden_size: int) -> jax.Array:
    """``sum_j weights[:, j] X[j]`` in float32: ``x`` (tokens, n C),
    ``weights`` (tokens, n) float32."""
    return sum(
        weights[:, j, None]
        * x[:, j * hidden_size:(j + 1) * hidden_size].astype(F32)
        for j in range(weights.shape[1]))


def leaf_metas() -> dict:
    """The metas of a mapping's or the readout's three float32 leaves."""
    return {"phi": replicated_meta(2, parameter_name="phi"),
            "alpha": replicated_meta(1, parameter_name="alpha",
                                     no_weight_decay=True),
            "bias": replicated_meta(1, parameter_name="bias",
                                    no_weight_decay=True)}


class HyperConnection(BaseLayer):
    """The mapping of ONE sub-layer: ``pre`` gives what the sub-layer reads
    and the mix, ``post`` the streams after it."""

    def __init__(self, hidden_size: int, streams: int, sinkhorn_iters: int,
                 eps: float, clamp: Tuple[float, float], norm_eps: float):
        self.hidden_size = hidden_size
        self.n = streams
        self.sinkhorn_iters = sinkhorn_iters
        self.eps = eps
        self.clamp = clamp
        self.norm_eps = norm_eps
        self.width = streams * hidden_size
        self.k = streams * streams + 2 * streams

    def init(self, key: jax.Array) -> dict:
        """Seeded, and random enough that every part of the mapping shows in
        the logits: ``phi`` at ``N(0, 1 / nC)`` (``m`` then has unit
        variance); ``a_pre = a_post = 1``, ``a_res = RES_ALPHA``; ``b_pre`` /
        ``b_post`` at ``N(0, GATE_BIAS_STD^2)``, so that the gates differ by
        stream and the streams drift apart (all start as the embedding, and
        ``H_res`` only shows as far as they differ); ``b_res`` uniform over
        ``+-RES_BIAS_RANGE``: half the entries of ``S`` lie beyond a clamp of
        +-30, 7 mappings in 10 have a row with TWO entries above +30 that the
        clamp ties, and rows that are nearly one-hot leave Sinkhorn far from
        its limit after 20 steps (the configuration's ``assumed.init`` has what
        each wrong form then moves)."""
        n = self.n
        k_phi, k_gate, k_res = jax.random.split(key, 3)
        return {
            "phi": jax.random.normal(k_phi, (self.width, self.k), F32)
            * self.width ** -0.5,
            # a_pre, a_post, a_res
            "alpha": jnp.array([1.0, 1.0, RES_ALPHA], F32),
            # b_pre (n), b_post (n), b_res (n x n, row-major)
            "bias": jnp.concatenate([
                GATE_BIAS_STD * jax.random.normal(k_gate, (2 * n,), F32),
                jax.random.uniform(
                    k_res, (n * n,), F32, -RES_BIAS_RANGE, RES_BIAS_RANGE)]),
        }

    def param_metas(self) -> dict:
        return leaf_metas()

    def pre(self, params: dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """``x`` (..., n C) -> ``u`` (..., C) in ``x``'s dtype and the mix,
        ``(tokens, n^2 + n)`` float32: ``H_res`` row-major, then ``H_post``."""
        n, C = self.n, self.hidden_size
        x2 = x.reshape(-1, self.width)
        with jax.named_scope("hc"):
            m = scaled_projection(x2, params["phi"], self.norm_eps)
            a_pre, a_post, a_res = (params["alpha"][i] for i in range(3))
            bias = params["bias"]
            h_pre = [jax.nn.sigmoid(a_pre * m[j] + bias[j]) + self.eps
                     for j in range(n)]
            h_post = [2.0 * jax.nn.sigmoid(a_post * m[n + j] + bias[n + j])
                      for j in range(n)]
            low, high = self.clamp
            s = jnp.clip(a_res * m[2 * n:] + bias[2 * n:, None], low, high)
            h_res = sinkhorn_tokens(
                s, n=n, iters=self.sinkhorn_iters, eps=self.eps,
                interpret=_paged.paged_kernel_interpret())
            u = combine(x2, jnp.stack(h_pre, axis=-1), C).astype(x.dtype)
            mix = jnp.concatenate([h_res, jnp.stack(h_post)]).T
        return u.reshape(*x.shape[:-1], C), mix

    def post(self, x: jax.Array, y: jax.Array, mix: jax.Array) -> jax.Array:
        """``X'`` (..., n C) in ``x``'s dtype of the streams ``x``, the
        sub-layer's output ``y`` (..., C) and ``pre``'s mix."""
        n, C = self.n, self.hidden_size
        x2 = x.reshape(-1, self.width)
        with jax.named_scope("hc"):
            y32 = y.reshape(-1, C).astype(F32)
            out = [combine(x2, mix[:, i * n:(i + 1) * n], C)
                   + mix[:, n * n + i, None] * y32 for i in range(n)]
            out = jnp.concatenate(out, axis=-1).astype(x.dtype)
        return out.reshape(x.shape)


class HyperReadout(BaseLayer):
    """The streams folded into one after the last sub-layer, by a gate of the
    same input as a mapping's."""

    def __init__(self, hidden_size: int, streams: int, eps: float,
                 norm_eps: float):
        self.hidden_size = hidden_size
        self.n = streams
        self.eps = eps
        self.norm_eps = norm_eps
        self.width = streams * hidden_size

    def init(self, key: jax.Array) -> dict:
        k_phi, k_bias = jax.random.split(key)
        return {
            "phi": jax.random.normal(k_phi, (self.width, self.n), F32)
            * self.width ** -0.5,
            "alpha": jnp.ones((1,), F32),
            "bias": GATE_BIAS_STD * jax.random.normal(k_bias, (self.n,), F32),
        }

    def param_metas(self) -> dict:
        return leaf_metas()

    def __call__(self, params: dict, x: jax.Array, ctx=None) -> jax.Array:
        x2 = x.reshape(-1, self.width)
        with jax.named_scope("hc"):
            m = scaled_projection(x2, params["phi"], self.norm_eps)
            gate = [jax.nn.sigmoid(params["alpha"][0] * m[j] + params["bias"][j])
                    + self.eps for j in range(self.n)]
            h = combine(x2, jnp.stack(gate, axis=-1), self.hidden_size)
        return h.astype(x.dtype).reshape(*x.shape[:-1], self.hidden_size)
