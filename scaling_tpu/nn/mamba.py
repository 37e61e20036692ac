"""Mamba-2 mixer (Dao & Gu 2024, "Transformers are SSMs"), plain ``jax.numpy``.

The state-space layer of the Nemotron-H family (``hybrid_override_pattern``'s
``M``). ``u`` (.., H) is the normed residual stream; ``inner = num_heads *
head_dim`` (NOT an expansion factor times H), ``G`` groups share B and C,
``N`` is the state size a head:

- ``[z | xBC | dt] = u W_in`` (``inner | inner + 2 G N | num_heads`` columns,
  no bias);
- ``xBC_t = silu(b_c + sum_{j<K} w_c[:, j] * xBC_{t-K+1+j})``: a depthwise
  causal convolution over the last ``K`` inputs of each channel;
- ``xBC -> x (heads x head_dim) | B (G x N) | C (G x N)``; head ``h`` uses
  group ``h // (heads / G)``;
- ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` (a scalar a head);
- ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (S: head_dim x N a head),
  ``y_t = S_t C_t + D x_t``;
- ``y = w_n * GroupRMSNorm(y * silu(z))``: the gate BEFORE the norm, whose
  statistic runs over each of the ``G`` groups of ``inner / G`` values;
- ``out = y W_out``.

``A_log``, ``D``, ``dt_bias`` are float32 leaves; the convolution, the
recurrence and the gated norm run in float32 whatever the model's dtype.

**One chunk, one read of the state** (``ssd_chunk``): a run of ``w``
positions advances from a state ``S_0`` in closed form,

    Y = (L o C B^T) (dt x) + C (decay S_0),   L[t, j] = exp(a_t - a_j), j <= t
    S_w = decay_w S_0 + sum_j exp(a_w - a_j) dt_j x_j B_j^T,

with ``a`` the running sum of ``dt A``: two small matmuls a head, and the state
is read once and written once however many positions the run holds. A
``lax.scan`` over the positions would move it once a position. A position
with ``dt = 0`` neither decays the state nor adds to it: that is how what is no
token (a chunk's padding, an empty slot) leaves the state as it was. The form
pays for its width: ``w`` places of activations a row, and the read-out of
``S_0`` beside its update. At ``w = 1`` it IS the recurrence's single step, ``S'
= exp(dt A) S + (dt x) B^T``, ``y = S' C``, which reads the state once and takes
the read-out from the value just computed (``_step_rows``).

Two callers:

- uncached (training-shaped ``(b, s)`` batches, ``logits()``): the sequence is
  walked in chunks of ``CHUNK`` positions, the state carried from one to the
  next, starting from zeros;
- served (``state`` a :class:`RecurrentStateView`): the engine's mixed program
  holds one fixed-size line per (slot, layer), ``ssm (slots, heads, head_dim,
  N)`` float32 and ``conv (slots, inner + 2 G N, K - 1)`` (each channel's last
  inputs), and every row advances from ITS line in the form its ``new_len``
  asks for. The tick's tokens arrive token-major, ``T`` places for ``rows``
  rows of at most ``w`` tokens. Below the full width (``T < rows * w``: the
  engine's small program, where nearly every row decodes) a row that brings
  ONE token takes the single step where it lies, and the few that bring more,
  at most ``T // w`` (``split_capacity``: the engine sends a tick with more to
  the full width), are gathered through the ``PagedTokenMap`` the attention
  branch uses, run ``ssd_chunk`` as ``(R, w)`` whole rows from their lines,
  each fetched by a read of its own, and are written back over them. At the
  full width ``R`` would be every row: nothing to split, so every row is
  regrouped to ``(rows, w)`` and runs the chunk form (``_chunk_rows``), as the
  row-major caller's rows do (``token_map`` None); that whole-rows form is
  also what the tests hold the split to. The choice follows from the shapes
  alone. A row whose ``context_len`` is 0 starts from zeros in either form:
  the program does it, so a reused slot or a recomputed (preempted) sequence
  needs no reset by the host.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .attention import PagedTokenMap
from .base_layer import BaseLayer, ForwardContext, multiplied
from .param import ParamMeta
from ..topology.topology import MODEL_AXIS

F32 = jnp.float32
# positions the uncached pass advances at once (the released kernels' chunk
# is 128; the quadratic term of a chunk is w x w a head)
CHUNK = 64
HIGHEST = jax.lax.Precision.HIGHEST


class RecurrentStateView(NamedTuple):
    """One Mamba-2 layer's lines of the serving engine's recurrent-state pool
    (serve/kvcache.py), plus the tick's addressing: the counterpart of
    ``PagedKVCacheView`` for a layer whose state does not grow with the
    context. Row ``r`` of the tick is slot ``r``'s line."""

    # the fields the pool owns, a list of each over the layers (the rest is the
    # tick's addressing), and the name this kind's spans and counters carry
    LINES = ("ssm", "conv")
    NAME = "ssm"
    # rows of one token step, rows of more run the chunk form: the engine
    # picks a tick's width by ``split_capacity`` and counts both
    SPLITS = True

    ssm: jax.Array          # (slots, heads, head_dim, N) float32
    conv: jax.Array         # (slots, inner + 2 G N, K - 1) last conv inputs
    context_len: jax.Array  # (slots,) int32 tokens the state has seen
    new_len: jax.Array      # (slots,) int32 real tokens the row brings
    token_map: Optional[PagedTokenMap] = None  # token-major batches


def split_capacity(width: int, row_width: int) -> int:
    """Rows bringing MORE than one token that a token-major batch of ``width``
    places advances beside its stepping rows (``Mamba2Mixer._split_rows``): as
    many as it could hold at their widest. A tick with more of them runs at
    the full width ``rows * row_width``, whose capacity is every row."""
    return width // row_width


def ssd_chunk(x, dt, A, B, C, S0, fresh=None):
    """Advance every row by one chunk in closed form, float32.

    ``x`` (r, w, nh, P), ``dt`` (r, w, nh) (0 where the position is no
    token), ``A`` (nh,) negative, ``B`` and ``C`` (r, w, G, N), ``S0`` (r, nh,
    P, N). ``fresh`` (r,) bool: rows that start from zeros whatever ``S0``
    holds; the choice is made on what is COMPUTED from ``S0`` (its read-out,
    its decayed part), so no zeroed copy of the states is ever written.
    Returns ``(y (r, w, nh, P), S_w (r, nh, P, N))``, ``y`` without the ``D
    x`` skip."""
    r, w, nh, P = x.shape
    G = B.shape[2]
    per = nh // G
    a = jnp.cumsum(dt * A, axis=1)                       # (r, w, nh), <= 0
    # L[t, j] = exp(a_t - a_j) for j <= t; masked BEFORE the exponential (above
    # the diagonal the difference is positive and may overflow)
    diff = a[:, :, None, :] - a[:, None, :, :]           # (r, t, j, nh)
    causal = jnp.tril(jnp.ones((w, w), bool))[None, :, :, None]
    L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    CB = jnp.einsum("rtgn,rjgn->rtjg", C, B, precision=HIGHEST)
    # head h reads group h // per
    M = L.reshape(r, w, w, G, per) * CB[..., None]       # (r, t, j, G, per)
    xdt = (x * dt[..., None]).reshape(r, w, G, per, P)
    y = jnp.einsum("rtjgq,rjgqp->rtgqp", M, xdt, precision=HIGHEST)
    S0g = S0.reshape(r, G, per, P, S0.shape[-1])
    decay = jnp.exp(a).reshape(r, w, G, per)
    from_state = jnp.einsum("rtgn,rgqpn->rtgqp", C, S0g,
                            precision=HIGHEST) * decay[..., None]
    carried = S0g * jnp.exp(a[:, -1]).reshape(r, G, per, 1, 1)
    if fresh is not None:
        from_state = jnp.where(fresh[:, None, None, None, None], 0.0, from_state)
        carried = jnp.where(fresh[:, None, None, None, None], 0.0, carried)
    y = y + from_state
    # what position j adds to the state, decayed to the chunk's end
    tail = jnp.exp(a[:, -1:, :] - a).reshape(r, w, G, per)
    S = carried + jnp.einsum("rjgqp,rjgn->rgqpn", xdt * tail[..., None], B,
                             precision=HIGHEST)
    return y.reshape(r, w, nh, P), S.reshape(S0.shape)


def causal_conv(window, weight, bias):
    """``window`` (r, w + K - 1, c): each row's last ``K - 1`` inputs, then
    its ``w`` new ones. ``weight`` (c, K), ``bias`` (c,). Returns silu of the
    depthwise causal convolution at the ``w`` new positions, float32: a sum
    of ``K`` shifted products."""
    K = weight.shape[1]
    w = window.shape[1] - (K - 1)
    window, weight = window.astype(F32), weight.astype(F32)
    out = bias.astype(F32)
    for j in range(K):
        out = out + window[:, j:j + w] * weight[:, j]
    return jax.nn.silu(out)


class Mamba2Mixer(BaseLayer):
    # the view of the serving state a layer with this mixer is handed
    STATE_VIEW = RecurrentStateView

    def __init__(self, hidden_size: int, num_heads: int, head_dim: int,
                 state_size: int, n_groups: int, conv_kernel: int,
                 norm_eps: float = 1e-5, time_step_min: float = 0.001,
                 time_step_max: float = 0.1, time_step_floor: float = 1e-4,
                 dtype=None, in_multiplier: float = 1.0,
                 multipliers: Optional[Sequence[float]] = None):
        """``in_multiplier`` and ``multipliers`` are published constants of a
        configuration (Falcon-H1's ``ssm_in_multiplier`` and
        ``ssm_multipliers``): ``proj = ((in_multiplier * u) W_in) * m``, ``m``
        one float32 vector over ``in_proj``'s columns that holds
        ``multipliers`` = (z, x, B, C, dt) by segment. None or all ones:
        nothing is multiplied (Nemotron-H's mixer)."""
        assert num_heads % n_groups == 0, (num_heads, n_groups)
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.state_size = state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.norm_eps = norm_eps
        self.time_step = (time_step_min, time_step_max, time_step_floor)
        self.dtype = dtype or jnp.float32
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.in_width = self.inner + self.conv_dim + num_heads
        self.in_multiplier = float(in_multiplier)
        self.multipliers = None
        if multipliers is not None and any(m != 1.0 for m in multipliers):
            self.multipliers = tuple(float(m) for m in multipliers)

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        """Seeded init as Mamba-2 publishes it: ``A`` uniform in [1, 16],
        ``dt`` log-uniform in [time_step_min, time_step_max] floored at
        ``time_step_floor`` (stored as the inverse softplus, ``dt_bias``),
        ``D`` ones, the conv uniform in +-1/sqrt(K), matrices Xavier-normal."""
        ks = jax.random.split(key, 5)
        H, K = self.hidden_size, self.conv_kernel
        lo, hi, floor = self.time_step

        def xavier(k, shape):
            std = math.sqrt(2.0 / (shape[0] + shape[1]))
            return (jax.random.normal(k, shape) * std).astype(self.dtype)

        dt = jnp.exp(jax.random.uniform(ks[2], (self.num_heads,))
                     * (math.log(hi) - math.log(lo)) + math.log(lo))
        dt = jnp.maximum(dt, floor)
        bound = 1.0 / math.sqrt(K)
        w_in = xavier(ks[0], (H, self.in_width))
        if self.multipliers is not None or self.in_multiplier != 1.0:
            # each column starts at its Xavier scale over what multiplies it
            by = self._column_multipliers() * self.in_multiplier
            w_in = (w_in.astype(F32) / by).astype(self.dtype)
        return {
            "in_proj": {"weight": w_in},
            "conv": {
                "weight": jax.random.uniform(
                    ks[1], (self.conv_dim, K), minval=-bound, maxval=bound
                ).astype(self.dtype),
                "bias": jnp.zeros((self.conv_dim,), self.dtype),
            },
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(F32),
            "A_log": jnp.log(jax.random.uniform(
                ks[3], (self.num_heads,), minval=1.0, maxval=16.0)).astype(F32),
            "D": jnp.ones((self.num_heads,), F32),
            "norm": {"weight": jnp.ones((self.inner,), self.dtype)},
            "out_proj": {"weight": xavier(ks[4], (self.inner, H))},
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
            "conv": {"weight": replicated("conv.weight", 2),
                     "bias": replicated("conv.bias", 1)},
            "dt_bias": replicated("dt_bias", 1),
            "A_log": replicated("A_log", 1),
            "D": replicated("D", 1),
            "norm": {"weight": replicated("norm.weight", 1)},
            "out_proj": {"weight": ParamMeta(
                parameter_name="out_proj.weight",
                partition_spec=(MODEL_AXIS, None), is_model_parallel=True,
                model_parallel_dimension=0)},
        }

    # --------------------------------------------------------------- forward
    def _column_multipliers(self):
        """``multipliers`` over ``in_proj``'s columns, float32 ``(in_width,)``:
        z | x | B | C | dt."""
        GN = self.n_groups * self.state_size
        widths = (self.inner, self.inner, GN, GN, self.num_heads)
        return jnp.concatenate([
            jnp.full((width,), m, F32)
            for width, m in zip(widths, self.multipliers or (1.0,) * 5)])

    def _split(self, proj):
        z = proj[..., :self.inner]
        xBC = proj[..., self.inner:self.inner + self.conv_dim]
        return z, xBC, proj[..., self.inner + self.conv_dim:]

    def _ssm_inputs(self, params, conved, dt, real):
        """The recurrence's operands from the conv's output (r, w, conv_dim)
        float32 and the raw ``dt`` (r, w, nh); ``real`` (r, w) bool or None."""
        r, w = conved.shape[:2]
        GN = self.n_groups * self.state_size
        x = conved[..., :self.inner].reshape(r, w, self.num_heads, self.head_dim)
        B = conved[..., self.inner:self.inner + GN].reshape(
            r, w, self.n_groups, self.state_size)
        C = conved[..., self.inner + GN:].reshape(
            r, w, self.n_groups, self.state_size)
        dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"])
        if real is not None:
            dt = jnp.where(real[..., None], dt, 0.0)
        return x, dt, -jnp.exp(params["A_log"]), B, C

    def _gated_out(self, params, y, z):
        """``(y * silu(z))`` group-normed, projected out. ``y`` float32
        (.., inner), ``z`` the model's dtype."""
        g = y * jax.nn.silu(z.astype(F32))
        grouped = g.reshape(*g.shape[:-1], self.n_groups, -1)
        grouped = grouped * jax.lax.rsqrt(
            jnp.mean(grouped * grouped, -1, keepdims=True) + self.norm_eps)
        g = grouped.reshape(g.shape) * params["norm"]["weight"].astype(F32)
        return g.astype(z.dtype) @ params["out_proj"]["weight"].astype(z.dtype)

    def __call__(self, params: dict, u: jax.Array, ctx: ForwardContext,
                 state: Optional[RecurrentStateView] = None,
                 return_state: bool = False):
        """``u`` (b, s, H). Without ``state`` each of the ``b`` sequences is
        walked whole from a zero state (``return_state``: also its final
        ``(ssm, conv)`` lines); with ``state`` the batch is the tick's, and the
        second result is the view with its lines advanced."""
        with jax.named_scope("ssm"):
            u = multiplied(u, self.in_multiplier)
            proj = u @ params["in_proj"]["weight"].astype(u.dtype)
            if self.multipliers is not None:
                proj = (proj.astype(F32) * self._column_multipliers()).astype(
                    proj.dtype)
            z, xBC, dt = self._split(proj)
            if state is not None:
                return self._serve(params, z, xBC, dt, state)
            y, lines = self._whole(params, xBC, dt)
            out = self._gated_out(params, y, z)
            return (out, lines) if return_state else out

    def _whole(self, params, xBC, dt):
        """Every sequence of a ``(b, s)`` batch from a zero state, ``CHUNK``
        positions at a time."""
        b, s, _ = xBC.shape
        K = self.conv_kernel
        window = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
        conved = causal_conv(window, params["conv"]["weight"],
                             params["conv"]["bias"])
        w = min(CHUNK, s)
        pad = -s % w
        real = jnp.arange(s + pad) < s                      # (s + pad,)
        conved = jnp.pad(conved, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        x, dt, A, B, C = self._ssm_inputs(
            params, conved, dt, jnp.broadcast_to(real, (b, s + pad)))

        def chunks(t):  # (b, s + pad, ...) -> (chunks, b, w, ...)
            return jnp.moveaxis(t.reshape(b, -1, w, *t.shape[2:]), 1, 0)

        def step(S, part):
            y, S = ssd_chunk(*part[:2], A, *part[2:], S)
            return S, y

        S0 = jnp.zeros((b, self.num_heads, self.head_dim, self.state_size), F32)
        S, y = jax.lax.scan(step, S0, tuple(chunks(t) for t in (x, dt, B, C)))
        y = jnp.moveaxis(y, 0, 1).reshape(b, s + pad, self.num_heads,
                                          self.head_dim)[:, :s]
        y = y + x[:, :s] * params["D"][:, None]
        tail = jnp.swapaxes(window[:, s:], 1, 2)            # (b, conv_dim, K-1)
        return y.reshape(b, s, self.inner), (S, tail)

    def _serve(self, params, z, xBC, dt, view: RecurrentStateView):
        """The tick's batch ``(g, s)`` against the slots' lines: whole rows
        where the batch has a place for every row's widest chunk, else each
        row in the form its ``new_len`` asks for."""
        g, s = xBC.shape[:2]
        lines = (view.ssm, view.conv, view.context_len.astype(jnp.int32),
                 view.new_len.astype(jnp.int32))
        tmap = view.token_map
        if tmap is None:  # row-major: position (r, j) is row r's j-th token
            y, S, tail = self._chunk_rows(params, xBC, dt, *lines)
        else:
            rows, w = tmap.row_tokens.shape
            xBC, dt = xBC.reshape(g * s, -1), dt.reshape(g * s, -1)
            if g * s < rows * w:
                y, S, tail = self._split_rows(params, xBC, dt, *lines, tmap)
            else:
                flat = tmap.row_tokens
                y, S, tail = self._chunk_rows(params, xBC[flat], dt[flat], *lines)
                # back to the batch's token order
                y = y[tmap.row, jnp.minimum(tmap.offset, w - 1)]
        new_view = view._replace(ssm=S.astype(view.ssm.dtype),
                                 conv=tail.astype(view.conv.dtype))
        return self._gated_out(params, y, z), new_view

    def _chunk_rows(self, params, xBC, dt, ssm, conv, ctx_len, new_len):
        """The whole-rows form: every row of ``(r, w)`` advances from its line
        in one chunk, ``new_len`` of its places real. Returns ``(y (r, w,
        inner), ssm, conv)``, float32 but the tail (``xBC``'s dtype)."""
        r, w = dt.shape[:2]
        K = self.conv_kernel
        real = jnp.arange(w, dtype=jnp.int32)[None, :] < new_len[:, None]
        # a row at context 0 starts from zeros, whatever its slot held
        fresh = (ctx_len == 0) & (new_len > 0)
        tail = jnp.where(fresh[:, None, None], 0, conv)
        window = jnp.concatenate(
            [jnp.swapaxes(tail, 1, 2).astype(xBC.dtype), xBC], axis=1)
        conved = causal_conv(window, params["conv"]["weight"],
                             params["conv"]["bias"])
        x, dt, A, B, C = self._ssm_inputs(params, conved, dt, real)
        y, S = ssd_chunk(x, dt, A, B, C, ssm.astype(F32), fresh)
        y = y + x * params["D"][:, None]
        # each channel's last K - 1 inputs, the row's new ones included: the
        # window's places new_len .. new_len + K - 2 (new_len 0: the old tail)
        last = new_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        new_tail = jnp.take_along_axis(window, last[:, :, None], axis=1)
        return y.reshape(r, w, self.inner), S, jnp.swapaxes(new_tail, 1, 2)

    def _step_rows(self, params, xBC, dt, ssm, conv, ctx_len, new_len):
        """The recurrence's single step for the rows that bring ONE token,
        ``xBC`` (r, conv_dim) and ``dt`` (r, nh) each row's first place of the
        tick: the chunk form at ``w = 1`` with no ``w`` axis. Every other row
        carries ``dt = 0`` and keeps its lines. The state is read once, and
        the read-out comes from the value just computed. Returns ``(y (r,
        inner), ssm, conv)`` as :meth:`_chunk_rows` does."""
        r = dt.shape[0]
        G, per = self.n_groups, self.num_heads // self.n_groups
        steps = new_len == 1
        fresh = steps & (ctx_len == 0)
        tail = jnp.where(fresh[:, None, None], 0, conv).astype(xBC.dtype)
        window = jnp.concatenate([tail, xBC[:, :, None]], axis=2)  # (r, c, K)
        conved = causal_conv(jnp.swapaxes(window, 1, 2),
                             params["conv"]["weight"], params["conv"]["bias"])
        x, dt, A, B, C = self._ssm_inputs(
            params, conved, dt[:, None], steps[:, None])
        x, dt, B, C = x[:, 0], dt[:, 0], B[:, 0], C[:, 0]
        xdt = (x * dt[..., None]).reshape(r, G, per, -1, 1)
        carried = jnp.exp(dt * A).reshape(r, G, per, 1, 1) * ssm.astype(
            F32).reshape(r, G, per, self.head_dim, self.state_size)
        # as ssd_chunk: zeros chosen on what is computed from the state
        carried = jnp.where(fresh[:, None, None, None, None], 0.0, carried)
        S = carried + xdt * B[:, :, None, None, :]
        y = jnp.sum(S * C[:, :, None, None, :], axis=-1)     # (r, G, per, P)
        y = y.reshape(x.shape) + x * params["D"][:, None]
        new_tail = jnp.where(steps[:, None, None], window[:, :, 1:], tail)
        return y.reshape(r, self.inner), S.reshape(ssm.shape), new_tail

    def _split_rows(self, params, xBC, dt, ssm, conv, ctx_len, new_len, tmap):
        """A token-major batch ``(T, ..)`` narrower than ``rows x w``: rows
        that bring one token step where they lie; the at most ``T // w`` that
        bring more (the caller sees to that: ``split_capacity``) are gathered,
        advanced as whole rows and written back over their lines. Returns
        ``(y (g, s, inner), ssm, conv)``."""
        rows, w = tmap.row_tokens.shape
        R = split_capacity(xBC.shape[0], w)
        first = tmap.row_tokens[:, 0]
        y_step, S, tail = self._step_rows(
            params, xBC[first], dt[first], ssm, conv, ctx_len, new_len)
        multi = new_len > 1
        # the multi-token rows in slot order, then `rows`: past the pool, so
        # that nothing of a place no row fills is written back. A chunk row
        # has stepped with dt = 0: its lines are still the old ones
        at, = jnp.nonzero(multi, size=R, fill_value=rows)
        held = jnp.minimum(at, rows - 1)
        flat = tmap.row_tokens[held]                         # (R, w)
        # each line by a read of its own: a general gather over a state wider
        # than the 128 lanes first copies EVERY slot's line into lane halves
        S_held = jnp.stack([
            jax.lax.dynamic_index_in_dim(S, row, 0, keepdims=False)
            for row in held])
        y_chunk, S_chunk, tail_chunk = self._chunk_rows(
            params, xBC[flat], dt[flat], S_held, tail[held], ctx_len[held],
            jnp.where(at < rows, new_len[held], 0))
        S = S.at[at].set(S_chunk, mode="drop")
        tail = tail.at[at].set(tail_chunk.astype(tail.dtype), mode="drop")
        # a token reads its row's step, or its place in its row's chunk
        place = jnp.cumsum(multi)[tmap.row] - 1
        place = jnp.clip(place, 0, R - 1) * w + jnp.minimum(tmap.offset, w - 1)
        y = jnp.concatenate([y_step, y_chunk.reshape(R * w, self.inner)])
        return y[jnp.where(multi[tmap.row], rows + place, tmap.row)], S, tail
