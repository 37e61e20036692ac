"""Gated short convolution (LFM2's ``conv`` operator), plain ``jax.numpy``.

The operator of three quarters of LiquidAI's LFM2 layers (``layer_types``'s
``conv``). ``x`` (.., H) is the normed residual stream, ``K`` the filter's
taps (``conv_L_cache``, 3):

- ``[B | C | X] = x W_in`` (``3 H`` columns, no bias);
- ``u = B * X``, elementwise;
- ``v_t = sum_{j<K} w[:, j] * u_{t-K+1+j}``: a depthwise causal filter over the
  last ``K`` values of each channel, no bias, NO activation; before the
  sequence ``u`` is 0;
- ``out = (C * v) W_out``.

The filter runs in float32 whatever the model's dtype.

Two callers, as ``nn/mamba.py`` has them:

- uncached (``(b, s)`` batches, ``logits()``): each sequence from a zero
  history;
- served (``state`` a :class:`ConvTailView`): the engine's mixed program holds
  ONE line per (slot, layer), ``tail (slots, K - 1, H)``: the last ``K - 1``
  values of ``u`` the slot's sequence produced, whatever its length. The
  tick's tokens stay token-major: a row's tokens lie back to back, so the
  value ``d`` places before a token is the token ``d`` before it in the batch,
  or, for a row's first ``d`` tokens, a value of the row's line. A row whose
  ``context_len`` is 0 starts from zeros whatever its line holds (a reused
  slot or a recomputed sequence needs no reset by the host); a row advances
  its line by its ``new_len`` REAL tokens only (a chunk's padding, an empty
  slot: the line stays as it was).
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .attention import PagedTokenMap
from .base_layer import BaseLayer, ForwardContext
from .linear import xavier_normal_init
from .param import ParamMeta
from ..topology.topology import MODEL_AXIS

F32 = jnp.float32


class ConvTailView(NamedTuple):
    """One short-convolution layer's lines of the serving engine's state pool
    (serve/kvcache.py), plus the tick's addressing: what ``RecurrentStateView``
    is to a Mamba-2 layer. Row ``r`` of the tick is slot ``r``'s line."""

    # the field the pool owns and the name in spans and counters, as there
    LINES = ("tail",)
    NAME = "conv"

    tail: jax.Array         # (slots, K - 1, H) the last values of u = B * X
    context_len: jax.Array  # (slots,) int32 tokens the line has seen
    new_len: jax.Array      # (slots,) int32 real tokens the row brings
    token_map: Optional[PagedTokenMap] = None  # token-major batches


def row_major_map(rows: int, width: int) -> PagedTokenMap:
    """The map of a ROW-MAJOR batch ``(rows, width)``: position ``(r, j)`` is
    row ``r``'s ``j``-th token."""
    row = jnp.broadcast_to(jnp.arange(rows, dtype=jnp.int32)[:, None], (rows, width))
    offset = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32)[None, :], (rows, width))
    return PagedTokenMap(row=row, offset=offset, row_tokens=row * width + offset)


class GatedShortConv(BaseLayer):
    STATE_VIEW = ConvTailView

    def __init__(self, hidden_size: int, kernel: int, dtype=None):
        self.hidden_size = hidden_size
        self.kernel = kernel
        self.dtype = dtype or jnp.float32

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        """Matrices Xavier-normal, the filter uniform in +-1/sqrt(K) (a
        depthwise ``Conv1d``'s default)."""
        ks = jax.random.split(key, 3)
        H, K = self.hidden_size, self.kernel
        bound = 1.0 / math.sqrt(K)
        return {
            "in_proj": {"weight": xavier_normal_init(ks[0], (H, 3 * H), self.dtype)},
            "conv": {"weight": jax.random.uniform(
                ks[1], (H, K), minval=-bound, maxval=bound).astype(self.dtype)},
            "out_proj": {"weight": xavier_normal_init(ks[2], (H, H), self.dtype)},
        }

    def param_metas(self) -> dict:
        def replicated(name):
            return ParamMeta(parameter_name=name, partition_spec=(None, None),
                             is_model_parallel_duplicate=True)

        # model parallelism over a pattern stack is refused (config.py): the
        # specs say how the matrices WOULD split, nothing runs sharded yet
        return {
            "in_proj": {"weight": replicated("in_proj.weight")},
            "conv": {"weight": replicated("conv.weight")},
            "out_proj": {"weight": ParamMeta(
                parameter_name="out_proj.weight",
                partition_spec=(MODEL_AXIS, None), is_model_parallel=True,
                model_parallel_dimension=0)},
        }

    # --------------------------------------------------------------- forward
    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext,
                 state: Optional[ConvTailView] = None,
                 return_state: bool = False):
        """``x`` (b, s, H). Without ``state`` each of the ``b`` sequences is
        filtered whole from a zero history (``return_state``: also its final
        ``(b, K - 1, H)`` tail); with ``state`` the batch is the tick's, and
        the second result is the view with its lines advanced."""
        with jax.named_scope("conv"):
            proj = x @ params["in_proj"]["weight"].astype(x.dtype)
            B, C, X = jnp.split(proj, 3, axis=-1)
            u = B * X
            weight = params["conv"]["weight"].astype(F32)
            new_state = None
            if state is not None:
                v, new_state = self._serve(weight, u, state)
            else:
                v, tail = self._whole(weight, u)
                if return_state:
                    new_state = tail
            out = (C * v.astype(x.dtype)) @ params["out_proj"]["weight"].astype(x.dtype)
            return out if new_state is None else (out, new_state)

    def _whole(self, weight, u):
        """Every sequence of a ``(b, s)`` batch from a zero history: a sum
        of ``K`` shifted products."""
        K, s = self.kernel, u.shape[1]
        window = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        v = sum(window[:, j:j + s].astype(F32) * weight[:, j] for j in range(K))
        return v, window[:, s:]

    def _serve(self, weight, u, view: ConvTailView):
        """The tick's batch ``(g, s)`` against the slots' lines, token-major:
        nothing is regrouped to ``(rows, row width)`` places."""
        g, s, H = u.shape
        K = self.kernel
        tmap = view.token_map
        if tmap is None:
            tmap = row_major_map(g, s)
        ctx_len = view.context_len.astype(jnp.int32)
        new_len = view.new_len.astype(jnp.int32)
        row, offset = tmap.row.reshape(-1), tmap.offset.reshape(-1)
        flat = u.reshape(g * s, H)
        # a row at context 0 starts from zeros, whatever its slot held
        fresh = (ctx_len == 0) & (new_len > 0)
        tail = jnp.where(fresh[:, None, None], 0, view.tail).astype(u.dtype)
        v = flat.astype(F32) * weight[:, K - 1]
        for d in range(1, K):
            # the value d places before a token: the token d before it in the
            # batch, or, for a row's first d tokens, place K - 1 - d + offset
            # of the row's line
            before = jnp.where(
                (offset >= d)[:, None], jnp.roll(flat, d, axis=0),
                tail[row, jnp.minimum(K - 1 - d + offset, K - 2)])
            v = v + before.astype(F32) * weight[:, K - 1 - d]
        # the line's next K - 1 values are places new_len .. new_len + K - 2
        # of [the line | the row's new tokens] (new_len 0: the line as it was)
        place = new_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        token = jnp.take_along_axis(
            tmap.row_tokens, jnp.clip(place - (K - 1), 0, tmap.row_tokens.shape[1] - 1),
            axis=1)
        new_tail = jnp.where(
            (place < K - 1)[:, :, None],
            jnp.take_along_axis(tail, jnp.minimum(place, K - 2)[:, :, None], axis=1),
            flat[token])
        return v.reshape(g, s, H), view._replace(tail=new_tail.astype(view.tail.dtype))
