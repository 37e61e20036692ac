"""Mixture-of-Experts MLP with expert parallelism (beyond the reference).

The reference has no MoE (SURVEY §2.4: "EP absent; TPU build may treat as
out of scope or future mesh axis"); this is the future mesh axis built the
TPU way — the GShard/Switch dense-dispatch formulation:

- the router scores every token against ``num_experts`` experts; top-k
  gating with a Switch-style load-balance auxiliary loss;
- training: a static ``capacity_factor`` bounds tokens per expert, so every
  shape is static and the whole block is three einsums on the MXU (dispatch,
  expert FFN, combine) — no sorting, no ragged tensors, no host control
  flow (serving sorts: below);
- the expert dimension is sharded over the ``data`` mesh axis (canonical
  expert-parallel: EP reuses the DP devices) and the expert FFN's hidden
  dim over ``model``; GSPMD derives the token all-to-alls from these
  shardings, the same way the rest of the stack gets its collectives.

Dropped tokens (over capacity) fall through on the residual path, exactly
as in Switch Transformers (Fedus et al. 2021).

Serving (``serve``; ``TransformerLayer`` takes it whenever the pass is one
of ``TransformerInferenceModule``'s: ``ForwardContext.serving``) drops
nothing, as the published models do not, and has no capacity at all. A
capacity taken over the rows of a serving batch has no meaning: a row of the
engine's mixed program is a prefill chunk padded to its fixed width or a
single decode token, so ``capacity_factor * k * s / E`` would count padding
as tokens, and which assignment fell over the edge would depend on how a
prompt was cut into chunks: prefill-then-decode would no longer equal the
full forward pass. The one-hot form at room for the whole row (``C = s``;
``_experts``) says that, and prices every held expert on every place: at a
decode tick's 256 places and 64 experts, 16 x the FLOPs that 4 experts a
token need, and the experts' weight read has become compute (PERF.md, PR
50). So ``serve`` runs the experts over the tick's ``A = places x k``
ASSIGNMENTS themselves (``_experts_grouped``): each takes the index of its
held expert (or of a trailing group that has no matrix, where the expert is
absent); they are
ordered by that index, stable, which is the token-major order the one-hot's
running count defines; the ``A`` rows of ``x`` are gathered in that order
and the up / gate / down matrices are grouped matmuls over the
``(held,)`` group sizes (``ops/grouped_matmul.py``: operands in ``x.dtype``,
float32 accumulation; a Pallas kernel under this ``moe`` scope on the chip,
``jax.lax.ragged_dot`` off it): an expert's matrices are read once and
multiplied with its own rows, an expert nobody chose is not read; each row is
weighted with its float32 gate, put back in ``(place, choice)`` order and
summed over ``k``, the trailing group's rows selected out (a kernel leaves
them unwritten). This equals ``_experts`` at ``C = s``, which stays as the tests' reference for it, as
training's form (``__call__``: Switch's drop rule is another semantics, and
its gradient runs through the einsums), and as ``serve``'s form wherever the
expert leaves are sharded over a mesh axis (``serve_rows``: GSPMD partitions
einsums, not a kernel). Padded positions are routed and computed like any
other (their outputs are never read and their KV goes to the trash block;
the kernel is bound by the matrices it reads, not by its rows, so leaving
them out would buy nothing: PERF.md, PR 50), but they are left out of the
load vector ``serve`` returns: how many assignments of REAL
positions each expert received. The auxiliary loss is a training term and is
not computed when serving.

``norm_topk_prob`` (a fact of the model, not a knob): whether a token's k
gate weights are renormalised to sum to one (Switch/GShard; the default)
or used as the softmax over all experts gave them (OLMoE).

Further facts of a model, all from its configuration: ``router``
(``'softmax'``, or ``'sigmoid_bias'``: every expert's ``s_e = sigmoid(logit_e)``
on its own, the k with the largest ``s_e + b_e`` chosen, ``b`` a float32
selection bias that takes part in the CHOICE only, the gates ``scale * s_e /
(sum of the chosen s + norm_topk_eps)``: 1e-20 in Nemotron-H, 1e-6 in LFM2;
with ``n_group > 1`` the choice is GROUP-LIMITED: the experts lie in
``n_group`` contiguous groups, a group's score is the sum of its two largest
``s_e + b_e``, and the k are chosen inside the ``topk_group`` best groups:
``_group_limited``, over all ``num_experts`` outputs whatever share is held),
``glu`` (false: two matrices an expert,
``act(x W_in) W_out``), and a ``shared expert`` of a width of its own that
every token runs, added once inside the ``moe`` scope; with
``shared_expert_gate`` its output is first scaled by ``sigmoid(x w_s)``, one
value a token (leaf ``shared_scale``, ``(h, 1)``: Qwen3-Next's).

**A share of the experts** (``experts_first``, ``experts_held``): the layer
is TOLD which contiguous range ``[first, first + held)`` of the
``num_experts`` it holds, one rank's share of an expert-parallel deployment.
The router keeps its ``num_experts`` outputs and its k a token; dispatch,
expert FFNs and combine run over the held experts only (their leaves are
``(held, ...)``); the gates of absent experts are dropped and NOT
renormalised over those present, so the shares of all ranks, the shared
expert counted once, add up to the whole layer. Nothing stands in for the
other ranks or their exchange. ``serve`` then counts the load over the held
experts, and how many of the real positions' assignments fell on absent ones.

**A small share** (under a quarter of the experts held: ``serve_bound``):
most of a tick's assignments name an absent expert, and carrying all ``A``
rows through the gather, the matmuls and the combine prices the tick by what
the OTHER ranks hold (12 of 384 held: 97% of 4,096 rows, and a ``lhs`` the
kernel has to cut into four calls a matrix: PERF.md, PR 56). After the stable
sort the held assignments are the first ``sum(sizes)`` entries of the order,
so the grouped form works on a static bound of rows, ``_SKEW_ROOM`` x the
balanced share in whole row tiles: it gathers ``x`` for that many entries,
runs the matmuls with the groups' sizes clipped to them, weights the rows
with their gates and adds them at their places in a float32 ``(places, h)``
result (a one-hot matmul: ``_sum_rows_at``). One such pass serves a common
tick; what a skewed tick holds beyond the bound takes further passes of the
same body (a rolled loop after the first), so nothing is ever dropped and the
result is ``_experts``'s at ``C = s`` under any routing. ``serve`` returns the
passes beyond the first after the absent count. With ``real`` given, a padded
position's assignments go to the trailing group too: padding takes no row of
the bound. From a quarter held the bound is ``A`` and the form is the one
above, operation for operation.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .activation_function import ActivationFunction, get_activation_function
from .base_layer import BaseLayer, ForwardContext
from .param import ParamMeta
from ..topology.topology import DATA_AXIS, MODEL_AXIS

# A rank that holds E_held of E experts is sent, at the deployment's load
# (its further ranks' tokens beside its own), the balanced share of the
# assignments it sees: rows x E_held / E. A tick's routing is skewed
# (moe_load_max_over_mean reads 1.3-2.3 over the routed cells: PERF.md), so a
# pass has room for this many times the share; what a tick holds beyond it
# takes a further pass (`_experts_grouped`), never a drop
_SKEW_ROOM = 4
# rows a pass is rounded up to: the grouped kernel's largest window, so no
# pass is padded (ops/grouped_matmul.py grouped_tiles)
_ROW_TILE = 128


class ParallelMoEMLP(BaseLayer):
    """Top-k routed expert MLPs (SwiGLU or plain): a dense one-hot dispatch
    at a capacity in training, grouped matmuls over the sorted assignments
    when serving."""

    def __init__(
        self,
        io_features: int,
        intermediate_feature_factor: float,
        num_experts: int,
        top_k: int = 2,
        capacity_factor: float = 1.25,
        aux_loss_coef: float = 0.01,
        norm_topk_prob: bool = True,
        norm_topk_eps: float = 1e-20,
        glu: bool = True,
        activation: ActivationFunction = ActivationFunction.SILU,
        dtype=None,
        intermediate: Optional[int] = None,
        router: str = "softmax",
        routed_scaling_factor: float = 1.0,
        shared_expert_width: Optional[int] = None,
        shared_expert_gate: bool = False,
        experts_first: int = 0,
        experts_held: Optional[int] = None,
        n_group: int = 1,
        topk_group: int = 1,
    ):
        dtype = dtype or jnp.float32
        if intermediate is None:
            intermediate = int(io_features * intermediate_feature_factor)
            assert float(intermediate) == io_features * intermediate_feature_factor
        assert 1 <= top_k <= num_experts
        assert router in ("softmax", "sigmoid_bias"), router
        assert n_group == 1 or (
            router == "sigmoid_bias" and num_experts % n_group == 0
            and 1 <= topk_group <= n_group
            and top_k <= topk_group * (num_experts // n_group)
            and num_experts // n_group >= 2), (
            "a group-limited choice divides the sigmoid router's experts "
            "into n_group groups of at least two", n_group, topk_group)
        self.router = router
        self.n_group, self.topk_group = n_group, topk_group
        self.routed_scaling_factor = routed_scaling_factor
        self.shared_expert_width = shared_expert_width
        # the shared expert's output times sigmoid(x w_s), one value a token
        # (Qwen2-MoE's and Qwen3-Next's shared_expert_gate)
        self.shared_expert_gate = shared_expert_gate
        assert shared_expert_width or not shared_expert_gate
        self.experts_first = experts_first
        self.experts_held = (
            num_experts - experts_first if experts_held is None else experts_held
        )
        assert 0 < self.experts_held <= num_experts - experts_first
        self.io_features = io_features
        self.intermediate = intermediate
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.aux_loss_coef = aux_loss_coef
        self.norm_topk_prob = norm_topk_prob
        self.norm_topk_eps = norm_topk_eps
        self.glu = glu
        self.activation_fn = get_activation_function(activation)
        self.dtype = dtype

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        import math

        ks = jax.random.split(key, 4)
        # the router scores every expert; the leaves hold those held here
        E, h, f = self.num_experts, self.io_features, self.intermediate
        held = self.experts_held

        def expert_init(k, shape, dtype):
            # xavier over the PER-EXPERT matmul fans (the leading expert dim
            # is a batch dim, not a fan — feeding it to a 2-D initializer
            # over-scales every expert)
            fan_in, fan_out = shape[-2:]
            std = math.sqrt(2.0 / (fan_in + fan_out))
            return (jax.random.normal(k, shape) * std).astype(dtype)

        params = {
            # router in fp32, near-zero init: routing starts ~uniform and the
            # decisions should not quantize (Switch Transformer practice)
            "router": {
                "weight": (jax.random.normal(ks[0], (h, E)) * 0.02).astype(
                    jnp.float32
                )
            },
            "w_in": expert_init(ks[1], (held, h, f), self.dtype),
            "w_out": expert_init(ks[2], (held, f, h), self.dtype),
        }
        if self.glu:
            params["w_gate"] = expert_init(ks[3], (held, h, f), self.dtype)
        if self.router == "sigmoid_bias":
            # the selection bias: zeros (a trained model's balances the load)
            params["router"]["bias"] = jnp.zeros((E,), jnp.float32)
        fs = self.shared_expert_width
        for i, name in enumerate(self._shared_leaves()):
            # keys of their own: the four above stay what they were
            shape = {"shared_out": (fs, h), "shared_scale": (h, 1)}.get(name, (h, fs))
            params[name] = expert_init(
                jax.random.fold_in(key, 4 + i), shape, self.dtype)
        return params

    def _shared_leaves(self):
        """The shared expert's leaves (none without one)."""
        if not self.shared_expert_width:
            return ()
        return (("shared_in", "shared_out") + (("shared_gate",) if self.glu else ())
                + (("shared_scale",) if self.shared_expert_gate else ()))

    def param_metas(self) -> dict:
        def expert_meta(name, spec):
            return ParamMeta(
                parameter_name=name,
                partition_spec=spec,
                is_model_parallel=True,
                model_parallel_dimension=spec.index(MODEL_AXIS),
            )

        metas = {
            "router": {
                "weight": ParamMeta(
                    parameter_name="router.weight",
                    partition_spec=(None, None),
                    is_model_parallel_duplicate=True,
                )
            },
            # experts over data (EP), ffn hidden over model (TP inside expert)
            "w_in": expert_meta("w_in", (DATA_AXIS, None, MODEL_AXIS)),
            "w_out": expert_meta("w_out", (DATA_AXIS, MODEL_AXIS, None)),
        }
        if self.glu:
            metas["w_gate"] = expert_meta("w_gate", (DATA_AXIS, None, MODEL_AXIS))
        if self.router == "sigmoid_bias":
            metas["router"]["bias"] = ParamMeta(
                parameter_name="router.bias", partition_spec=(None,),
                is_model_parallel_duplicate=True,
            )
        for name in self._shared_leaves():
            if name == "shared_scale":
                metas[name] = ParamMeta(
                    parameter_name=name, partition_spec=(None, None),
                    is_model_parallel_duplicate=True)
                continue
            spec = (MODEL_AXIS, None) if name == "shared_out" else (None, MODEL_AXIS)
            metas[name] = ParamMeta(
                parameter_name=name, partition_spec=spec,
                is_model_parallel=True,
                model_parallel_dimension=spec.index(MODEL_AXIS),
            )
        return metas

    def __call__(
        self, params: dict, x: jax.Array, ctx: ForwardContext
    ) -> Tuple[jax.Array, jax.Array]:
        """Training: the static capacity of the module docstring. Returns
        (output (b,s,h), aux_loss scalar — already coefficient-scaled,
        ready to add to the training loss)."""
        s = x.shape[1]
        E, k = self.num_experts, self.top_k
        with jax.named_scope("moe"):
            probs, gate_vals, gate_idx = self._route(params, x)
            # Switch load-balance loss: E * sum_e mean_prob_e *
            # assigned_frac_e, with assignment fractions from the top-1 choice
            top1 = jnp.argmax(probs, axis=-1)
            assigned = jax.nn.one_hot(top1, E, dtype=jnp.float32)  # (b, s, E)
            aux = E * jnp.sum(probs.mean(axis=(0, 1)) * assigned.mean(axis=(0, 1)))
            aux = (aux * self.aux_loss_coef).astype(jnp.float32)
            capacity = max(1, int(self.capacity_factor * k * s / E))
            y = self._experts(params, x, gate_vals, gate_idx, capacity)
            return self._add_shared(params, x, y), aux

    @property
    def holds_all(self) -> bool:
        return self.experts_held == self.num_experts

    def serve(
        self, params: dict, x: jax.Array, real: Optional[jax.Array] = None,
        mesh=None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """Serving: nothing is dropped, and no auxiliary loss. ``real``
        ((b, s) bool): which positions hold a token. ``mesh``: the mesh the
        leaves live on (``ForwardContext.mesh``), which decides the form
        (``serve_rows``). Returns (output (b,s,h), the (held,) int32 count
        of the real positions' assignments each HELD expert received; None
        without ``real``). A layer that holds a share of the experts appends
        one more count: the real positions' assignments that fell on absent
        experts (the two sum to ``top_k`` a real position); and where its
        rows are bounded (``serve_bound``) one more after it: the passes
        this call ran beyond its first."""
        with jax.named_scope("moe"):
            _, gate_vals, gate_idx = self._route(params, x)
            extra = None
            if self.serve_rows(x.shape[0] * x.shape[1], mesh)[0] == "grouped":
                y, extra = self._experts_grouped(
                    params, x, gate_vals, gate_idx, real)
            else:
                y = self._experts(
                    params, x, gate_vals, gate_idx, capacity=x.shape[1])
            y = self._add_shared(params, x, y)
            if real is None:
                return y, None
            chosen = jax.nn.one_hot(
                self._local(gate_idx), self.experts_held, dtype=jnp.int32)
            load = (chosen * real[:, :, None, None].astype(jnp.int32)).sum((0, 1, 2))
            if not self.holds_all:
                absent = self.top_k * real.sum(dtype=jnp.int32) - load.sum()
                load = jnp.concatenate([load, absent[None]])
            if extra is not None:
                load = jnp.concatenate([load, extra[None]])
            return y, load

    def serve_bound(self, places: int) -> int:
        """The rows a pass of the grouped form works on: all ``places x k``
        assignments where a quarter of the experts or more are held, else
        ``_SKEW_ROOM`` x the held share of them, in whole row tiles. From
        what the layer is (``experts_held`` of ``num_experts``), never set."""
        rows = places * self.top_k
        share = -(-_SKEW_ROOM * rows * self.experts_held // self.num_experts)
        return min(rows, -(-share // _ROW_TILE) * _ROW_TILE)

    def serve_rows(self, places: int, mesh=None) -> Tuple[str, int]:
        """The form ``serve`` takes over ``places`` positions, and the rows
        its expert matmuls are given: ``("grouped", serve_bound(places))``,
        the rows of one pass (``places x k`` where the bound does not bite),
        wherever the expert leaves are whole on the device; ``("dense", held
        x places)``, the one-hot at room for the whole row, where a mesh axis
        their partition names (experts over ``data``, their width over
        ``model``) has more than one device: GSPMD partitions the einsums,
        it cannot partition the kernel. Static for a program (the engine
        counts it: ``serve_moe_rows_total``)."""
        sharded = mesh is not None and any(
            mesh.shape.get(axis, 1) > 1 for axis in (DATA_AXIS, MODEL_AXIS))
        if sharded:
            return "dense", self.experts_held * places
        return "grouped", self.serve_bound(places)

    def _local(self, gate_idx: jax.Array) -> jax.Array:
        """The chosen experts' places among those held; an absent expert's
        lies outside ``[0, held)``, where ``one_hot`` is all zero."""
        return gate_idx - self.experts_first if self.experts_first else gate_idx

    def _add_shared(self, params: dict, x: jax.Array, y: jax.Array) -> jax.Array:
        """``y`` + the shared expert every token runs (none: ``y``)."""
        if not self.shared_expert_width:
            return y
        up = x @ params["shared_in"].astype(x.dtype)
        if self.glu:
            act = self.activation_fn(x @ params["shared_gate"].astype(x.dtype)) * up
        else:
            act = self.activation_fn(up)
        shared = act @ params["shared_out"].astype(x.dtype)
        if self.shared_expert_gate:
            scale = jax.nn.sigmoid(
                (x @ params["shared_scale"].astype(x.dtype)).astype(jnp.float32))
            shared = (shared.astype(jnp.float32) * scale).astype(shared.dtype)
        return y + shared

    def _route(self, params: dict, x: jax.Array):
        """Router probabilities over all experts in float32 (b, s, E), and
        each token's top-k gate weights and expert indices (b, s, k)."""
        # float32 in earnest: on a TPU a float32 matmul at the default
        # precision rounds both operands to bf16, and a router logit moved
        # by that much re-orders near-ties among the top k
        logits = jnp.einsum(
            "bsh,he->bse", x.astype(jnp.float32), params["router"]["weight"],
            precision=jax.lax.Precision.HIGHEST,
        )
        if self.router == "sigmoid_bias":
            probs = jax.nn.sigmoid(logits)
            # the bias moves the CHOICE; the gates are the chosen s_e
            choice = probs + params["router"]["bias"]
            if self.n_group > 1:
                choice = self._group_limited(choice)
            _, gate_idx = jax.lax.top_k(choice, self.top_k)
            gate_vals = jnp.take_along_axis(probs, gate_idx, axis=-1)
            if self.norm_topk_prob:
                gate_vals = gate_vals / (
                    gate_vals.sum(axis=-1, keepdims=True) + self.norm_topk_eps)
            return probs, gate_vals * self.routed_scaling_factor, gate_idx
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, self.top_k)
        if self.norm_topk_prob:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(axis=-1, keepdims=True), 1e-9
            )
        if self.routed_scaling_factor != 1.0:
            gate_vals = gate_vals * self.routed_scaling_factor
        return probs, gate_vals, gate_idx

    def _group_limited(self, choice: jax.Array) -> jax.Array:
        """The choice scores ``(b, s, E)`` with every expert outside the
        token's ``topk_group`` best groups at ``-inf``: a group's score is
        the sum of its two largest choice scores, over ALL ``num_experts``
        outputs in ``n_group`` contiguous groups, whatever share of them the
        layer holds (DeepSeek-V3's ``noaux_tc``)."""
        b, s, E = choice.shape
        grouped = choice.reshape(b, s, self.n_group, E // self.n_group)
        # the two largest of a group without a sort: the largest, and the
        # largest of the rest (an equal score elsewhere in the group counts)
        first = jnp.argmax(grouped, axis=-1, keepdims=True)
        rest = jnp.where(
            first == jnp.arange(E // self.n_group, dtype=first.dtype),
            -jnp.inf, grouped)
        group_score = grouped.max(axis=-1) + rest.max(axis=-1)
        _, best = jax.lax.top_k(group_score, self.topk_group)
        kept = jnp.any(
            best[..., None] == jnp.arange(self.n_group, dtype=best.dtype),
            axis=-2)                                       # (b, s, n_group)
        return jnp.where(kept[..., None], grouped, -jnp.inf).reshape(b, s, E)

    def _experts(
        self, params: dict, x: jax.Array, gate_vals: jax.Array,
        gate_idx: jax.Array, capacity: int,
    ) -> jax.Array:
        """Dispatch, expert FFNs, combine: three einsums over per-row
        buffers of ``capacity`` places an expert."""
        b, s, h = x.shape
        # E: the experts held here; a choice that fell on an absent one has
        # an all-zero row below and so takes no place and no part
        E, k, C = self.experts_held, self.top_k, capacity
        gate_idx = self._local(gate_idx)

        # position of each (token, choice) in its expert's capacity buffer:
        # running count of prior tokens routed to the same expert. Choices
        # are flattened (s, k) -> priority order matches GShard's
        # token-major, choice-minor scan.
        choice_exp = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)  # (b,s,k,E)
        flat = choice_exp.reshape(b, s * k, E)
        position = jnp.cumsum(flat, axis=1) - flat  # prior count, (b, s*k, E)
        pos_in_exp = jnp.einsum("bte,bte->bt", position, flat).reshape(b, s, k)
        pos_in_exp = pos_in_exp.astype(jnp.int32)  # exact small counts
        keep = (pos_in_exp < C).astype(jnp.float32)  # dropped past capacity

        # dispatch/combine (b, s, E, C)
        pos_oh = jax.nn.one_hot(pos_in_exp, C, dtype=jnp.float32)  # (b,s,k,C)
        combine = jnp.einsum(
            "bsk,bsk,bske,bskc->bsec", gate_vals, keep, choice_exp, pos_oh
        )
        dispatch = jnp.einsum("bsk,bske,bskc->bsec", keep, choice_exp, pos_oh)

        xin = jnp.einsum("bsec,bsh->ebch", dispatch.astype(x.dtype), x)
        w_in = params["w_in"].astype(x.dtype)
        up = jnp.einsum("ebch,ehf->ebcf", xin, w_in)
        if self.glu:
            gate = jnp.einsum(
                "ebch,ehf->ebcf", xin, params["w_gate"].astype(x.dtype)
            )
            act = self.activation_fn(gate) * up
        else:
            act = self.activation_fn(up)
        out = jnp.einsum("ebcf,efh->ebch", act, params["w_out"].astype(x.dtype))
        return jnp.einsum("bsec,ebch->bsh", combine.astype(x.dtype), out)

    def _expert_rows(
        self, params: dict, rows: jax.Array, sizes: jax.Array,
    ) -> jax.Array:
        """The held experts' FFNs over ``rows`` sorted by expert, ``sizes``
        rows each: two or three grouped matmuls and the activation."""
        from ..ops.grouped_matmul import grouped_matmul

        up = grouped_matmul(rows, params["w_in"].astype(rows.dtype), sizes)
        if self.glu:
            gate = grouped_matmul(
                rows, params["w_gate"].astype(rows.dtype), sizes)
            act = self.activation_fn(gate) * up
        else:
            act = self.activation_fn(up)
        return grouped_matmul(act, params["w_out"].astype(rows.dtype), sizes)

    def _experts_grouped(
        self, params: dict, x: jax.Array, gate_vals: jax.Array,
        gate_idx: jax.Array, real: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, Optional[jax.Array]]:
        """The held experts over the tick's assignments themselves: sorted
        by expert, each expert's matrices multiplied with its own rows
        (``ops/grouped_matmul.py``), weighted and summed back in place.
        Equal to ``_experts`` at ``capacity = s``. Where the bound bites
        (``serve_bound``; module docstring) the matmuls see ``bound`` rows a
        pass, and the second result is the int32 count of the passes beyond
        the first (None where the rows are all the assignments)."""
        b, s, h = x.shape
        E, k = self.experts_held, self.top_k
        places = b * s
        bound = self.serve_bound(places)
        bounded = bound < places * k
        # an assignment's group: its held expert, or, where the expert is
        # absent, the trailing group E, which has no matrix and which no
        # matmul visits
        group = self._local(gate_idx).reshape(places * k)
        taken = (group >= 0) & (group < E)
        if bounded and real is not None:
            # what a padded position would take of the bound's rows is left
            # to the trailing group too: its output is never read
            taken &= jnp.repeat(real.reshape(places), k)
        group = jnp.where(taken, group, E)
        # stable: token-major within an expert, the order the one-hot's
        # running count defines
        order = jnp.argsort(group, stable=True)

        def group_sizes():  # each form counts them where it needs them
            return (group[:, None] == jnp.arange(E, dtype=group.dtype)).sum(
                0, dtype=jnp.int32)

        if not bounded:
            place = jnp.zeros_like(order).at[order].set(
                jnp.arange(places * k, dtype=order.dtype), unique_indices=True)
            sizes = group_sizes()
            out = self._expert_rows(
                params, x.reshape(places, h)[order // k], sizes)
            # back in (place, choice) order; a row of the trailing group was
            # never written: selected out, not multiplied by zero
            out = out[place].reshape(b, s, k, h).astype(jnp.float32)
            taken = taken.reshape(b, s, k, 1)
            y = jnp.where(taken, gate_vals[..., None] * out, 0.0).sum(axis=2)
            return y.astype(x.dtype), None

        # the held assignments are the first sum(sizes) entries of order: a
        # pass takes the next `bound` of them, each group's rows clipped to
        # the pass (as grouped_matmul clips them to its own blocks)
        sizes = group_sizes()
        ends = jnp.cumsum(sizes)
        starts, held_rows = ends - sizes, ends[-1]
        xs = x.reshape(places, h)
        order = jnp.pad(order, (0, bound))  # the last pass may reach past it
        gates = gate_vals.reshape(places * k)
        at = jnp.arange(bound, dtype=jnp.int32)

        def one_pass(lo, y):
            mine = jax.lax.dynamic_slice(order, (lo,), (bound,))
            live = (lo + at < held_rows)[:, None]
            out = self._expert_rows(
                params, xs[mine // k],
                jnp.clip(ends, lo, lo + bound) - jnp.clip(starts, lo, lo + bound))
            # a row past the held ones was never written: selected out
            out = jnp.where(
                live, gates[mine][:, None] * out.astype(jnp.float32), 0.0)
            return y + _sum_rows_at(out, mine // k, places)

        # the first pass is every common tick's only one and runs unrolled
        # (a `while` around it would be one device operation that spans the
        # pass); what a skewed tick holds beyond the bound takes further
        # passes of the same body, so nothing is ever dropped
        y = one_pass(jnp.int32(0), jnp.zeros((places, h), jnp.float32))
        lo, y = jax.lax.while_loop(
            lambda carry: carry[0] < held_rows,
            lambda carry: (carry[0] + bound, one_pass(*carry)),
            (jnp.int32(bound), y))
        # the loop leaves lo at bound x the passes run
        return y.reshape(b, s, h).astype(x.dtype), lo // bound - 1


def _sum_rows_at(rows: jax.Array, at: jax.Array, places: int) -> jax.Array:
    """``(places, h)`` float32: ``rows[r]`` added at place ``at[r]``, as a
    one-hot matmul in float32 in earnest (see ``_route``). On the chip at the
    Kimi-K2 cell's 512 rows onto 512 places of 7,168 this is 0.06 ms where the
    scatter-add (a sort, a gather and a segmented update) is 0.40 (PERF.md,
    PR 56); it is quadratic in a tick's width, and level with the scatter at
    5,120 x 5,120."""
    hot = at[None, :] == jnp.arange(places, dtype=at.dtype)[:, None]
    return jnp.dot(hot.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST)
