"""Final norm + LM heads.

(reference: src/scaling/transformer/model/layers/layernorm.py:13-56,
lm_head.py:16-66, lm_head_tied.py:17-55, embedding_head.py:12-80)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ....nn import (
    BaseLayer,
    ColumnParallelLinear,
    ForwardContext,
    ParamMeta,
    get_norm,
    normal_init,
    tree_prefix,
    xavier_normal_init,
)
from ....nn.base_layer import multiplied
from ....nn.hyper_connection import HyperReadout
from ....nn.linear import column_parallel_matmul
from ....parallel.sharding import constrain, shard_logits, vocab_shards
from ....topology.topology import MODEL_AXIS
from ..config import EmbeddingHeadConfig, TransformerArchitectureConfig


class LayerNormWrapper(BaseLayer):
    """Final norm; records the normed hidden state into ``embeddings`` for
    downstream embedding heads (reference: layernorm.py:13-56)."""

    def __init__(self, architecture: TransformerArchitectureConfig,
                 record_embeddings: bool = False):
        arch = architecture
        bitfit = arch.bitfit_bias_config.name if arch.bitfit_bias_config else None
        self.norm = get_norm(arch.norm_type, arch.hidden_size, arch.layernorm,
                             arch.dtype, bitfit)
        self.record_embeddings = record_embeddings
        self.random_signs = arch.layer_pattern is not None and arch.weight_tying
        # hc_streams residual streams are folded into one before the norm
        self.readout: Optional[HyperReadout] = None
        if arch.hc_streams > 1:
            self.readout = HyperReadout(
                arch.hidden_size, arch.hc_streams, arch.hc_eps,
                arch.layernorm.layernorm_epsilon)

    def init(self, key: jax.Array) -> dict:
        """A norm's own init; before a head TIED to the table of a
        ``layer_pattern`` stack the weight starts at random signs. A tied
        head scores token ``v`` by ``sum_i w_i n_i E_vi``, and the stream
        ``n`` holds the token's own embedding ``e``: at ``w = 1`` the token
        itself scores its share of ``|e|^2``, up to ``sqrt(hidden)``
        deviations above every other, so fresh weights repeat their input
        whatever the layers compute and a comparison of logits sees none of
        it. With signs that sum to nothing its score is one among the others
        (a trained norm's weight is some vector too). ``MixerLayer.init``
        has the rest of what a tied head asks of seeded weights."""
        params = self.norm.init(key)
        if self.random_signs:
            weight = params["weight"]
            signs = jax.random.rademacher(key, weight.shape, jnp.float32)
            params["weight"] = weight * signs.astype(weight.dtype)
        params = {"norm": params}
        if self.readout is not None:
            params["hc"] = self.readout.init(jax.random.fold_in(key, 1))
        return params

    def param_metas(self) -> dict:
        metas = {"norm": tree_prefix(self.norm.param_metas(), "norm")}
        if self.readout is not None:
            metas["hc"] = tree_prefix(self.readout.param_metas(), "hc")
        return metas

    def __call__(self, params: dict, x: dict, ctx: ForwardContext) -> dict:
        out = dict(x)
        h = x["activations"]
        if self.readout is not None:
            h = self.readout(params["hc"], h)
        out["activations"] = self.norm(params["norm"], h, ctx)
        if self.record_embeddings:
            out["embeddings"] = out["activations"]
        return out


class LoopExitGate(BaseLayer):
    """The exit gate of a looped trunk (``loop_exit_gate``): ``Linear(hidden,
    1)`` with a bias on each step's normed output, ``lambda_u = sigmoid(w .
    h_u + b)``. Its place in the stack, after the final norm, holds its
    parameters; walked as a layer it passes the activations on unchanged.
    The looped walk (inference.py) calls :meth:`exit_probability` after
    every step and :func:`exit_distribution` once over all of them.
    Replicated over the model axis: 2049 parameters."""

    def __init__(self, architecture: TransformerArchitectureConfig):
        self.hidden_size = architecture.hidden_size
        self.dtype = architecture.dtype

    def init(self, key: jax.Array) -> dict:
        return {"linear": {
            "weight": xavier_normal_init(key, (self.hidden_size, 1), self.dtype),
            "bias": jnp.zeros((1,), self.dtype),
        }}

    def param_metas(self) -> dict:
        return {"linear": {
            "weight": ParamMeta(parameter_name="linear.weight",
                                partition_spec=(None, None),
                                is_model_parallel_duplicate=True),
            "bias": ParamMeta(parameter_name="linear.bias",
                              partition_spec=(None,),
                              is_model_parallel_duplicate=True),
        }}

    def __call__(self, params: dict, x: dict, ctx: ForwardContext) -> dict:
        return x

    def exit_probability(self, params: dict, h: jax.Array) -> jax.Array:
        """``lambda`` of every position of ``h`` (..., hidden), float32: a
        product and a sum on the vector unit, no matmul pass to round it."""
        w = params["linear"]["weight"].astype(jnp.float32)[:, 0]
        b = params["linear"]["bias"].astype(jnp.float32)[0]
        return jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32) * w, axis=-1) + b)


def exit_distribution(lambdas: jax.Array) -> jax.Array:
    """``p_u`` over the steps (leading axis) from the steps' gates: ``p_0 =
    lambda_0``, ``p_u = lambda_u * prod_{j<u}(1 - lambda_j)``, and the last
    step takes what is left, ``prod_{j<last}(1 - lambda_j)``: sums to 1."""
    stay = jnp.cumprod(1.0 - lambdas, axis=0)  # prod_{j<=u}(1 - lambda_j)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([(lambdas * before)[:-1], before[-1:]], axis=0)


class TransformerLMHead(BaseLayer):
    """Untied head: column-parallel projection to the vocabulary
    (reference: lm_head.py:16-66). Under muP the readout zero-initializes
    and logits carry the tunable output_mult; the width correction is the
    readout's 1/m learning-rate scale, NOT a logit multiplier — applying
    both (the two equivalent muP output formulations) over-suppresses
    updates by an extra 1/m, which the coordinate check catches.

    Under tensor parallelism the logits LEAVE the head as the matmul makes
    them, ``(data, seq, model)``: each model rank holds its ``vocab / mp``
    columns of one global ``(b, s, vocab)`` array (the reference gathers
    them here, lm_head.py:60-66; so did this head until PR 54, and every
    rank then ran the whole head from a gathered weight). Under pipeline
    stages the weight's vocabulary lies over ``(pipe, model)`` and so do
    the logits: every device of a data rank takes ``vocab / (pp * mp)``
    columns of head and loss, where each used to gather the whole weight.
    The loss (ops/cross_entropy.py) and the accuracy's argmax reduce over
    those axes one number a position; a consumer that needs whole rows
    (sampling, a caller's ``np.asarray``) has XLA gather them there. A
    sharding is a layout: values are the same at every ``mp``."""

    READOUT_LOGIT_STD = 0.5

    def __init__(self, architecture: TransformerArchitectureConfig):
        arch = architecture
        mup = arch.mup
        init_method = xavier_normal_init
        self.logit_mult = None
        if mup is not None:
            self.logit_mult = mup.output_mult
            if mup.readout_zero_init:
                init_method = lambda key, shape, dtype: jnp.zeros(shape, dtype)  # noqa: E731
        # a published constant on the logits (Falcon-H1's lm_head_multiplier):
        # the seeded head starts that much higher, and from a deviation that
        # counts its fan-in only, muP's readout: fresh logits then lie at a
        # deviation of READOUT_LOGIT_STD whatever the vocabulary's size, where
        # Xavier's fan-out term shrinks them with it (0.196 at 261,120 rows,
        # under which the benchmark's fp8 control read 0.035 against its limit
        # of 0.05; a 32,768-row Xavier head over 4096 gives 0.47: PERF.md, PR 52)
        self.multiplier = arch.multipliers.lm_head
        if self.multiplier != 1.0:
            init_method = normal_init(
                self.READOUT_LOGIT_STD * arch.hidden_size ** -0.5)
        self.linear = ColumnParallelLinear(
            arch.hidden_size,
            arch.vocab_size,
            bias=False,
            dtype=arch.dtype,
            parallel_output=False,
            init_method=init_method,
        )

    def init(self, key: jax.Array) -> dict:
        params = self.linear.init(key)
        params["weight"] = multiplied(params["weight"], 1.0 / self.multiplier)
        return {"linear": params}

    def param_metas(self) -> dict:
        return {"linear": tree_prefix(self.linear.param_metas(), "linear")}

    def vocab_shards(self, mesh) -> int:
        """Over how many devices the logits' vocabulary is split when they
        reach the loss (``ParallelModule.loss_vocab_shards``)."""
        return vocab_shards(mesh)

    def __call__(self, params: dict, x: dict, ctx: ForwardContext) -> dict:
        out = dict(x)
        # ``self.linear``'s own matmul (it makes the weight and its metas),
        # without the layout its call would pin: the reference's gather, or
        # (data, seq, model) where stages put the vocabulary over (pipe, model)
        h = x["activations"]
        weight = params["linear"]["weight"].astype(h.dtype)
        if ctx.zero_gathers_on_entry:
            # ZeRO-1 gathers the weight on the step's entry, the region below
            # gathers the rows: say that the weight's gather comes first, so
            # that it can start under the last layer's matmul. Left to itself
            # the scheduler may order the rows' gather first, and the weight's
            # 590 MB (train-pharia7b-4chip) then cross the data pairs with
            # nothing beside them: 6.3 ms where 3.9 are waited for (PERF.md,
            # PR 72). ONE matmul reads these rows; where siblings share a
            # gather (query / key / value, gate / up) the same barrier splits
            # it and costs more than it hides (+3.8 ms a step there)
            h, weight = jax.lax.optimization_barrier((h, weight))
        logits = column_parallel_matmul(h, weight, ctx)
        if logits.ndim == 3:
            logits = shard_logits(logits, ctx.mesh)
        if self.logit_mult is not None:
            logits = logits * jnp.asarray(self.logit_mult, logits.dtype)
        out["activations"] = multiplied(logits, self.multiplier)
        return out


class TransformerLMHeadTied(BaseLayer):
    """Weight-tied head reusing the embedding table. Assembled as a
    TiedLayerSpec with key "embedding_lm_head" and tied attribute
    ``embedding.weight``, so the params alias the EmbeddingInput table —
    gradients flow into one array and the reference's tied-grad all-reduce
    (tied_layer_index.py:74-224) has no equivalent to need.
    """

    def __init__(self, architecture: TransformerArchitectureConfig):
        self.architecture = architecture
        self.dtype = architecture.dtype

    def init(self, key: jax.Array) -> dict:
        arch = self.architecture
        return {
            "embedding": {
                "weight": xavier_normal_init(
                    key, (arch.vocab_size, arch.hidden_size), self.dtype
                )
            }
        }

    def param_metas(self) -> dict:
        return {
            "embedding": {
                "weight": ParamMeta(
                    parameter_name="embedding.weight",
                    partition_spec=(MODEL_AXIS, None),
                    is_model_parallel=True,
                    model_parallel_dimension=0,
                    lr_group="embedding",
                )
            }
        }

    def __call__(self, params: dict, x: dict, ctx: ForwardContext) -> dict:
        weight = params["embedding"]["weight"].astype(self.dtype)
        h = x["activations"]
        logits = jnp.einsum("bsh,vh->bsv", h, weight)
        # vocab-sharded matmul output -> gathered full logits (the
        # reference's all-concat, lm_head_tied.py:41-53); XLA emits the
        # all-gather from the sharding constraint
        logits = constrain(logits, ctx.mesh, None, None, None)
        out = dict(x)
        out["activations"] = logits
        return out


class TransformerEmbeddingHead(BaseLayer):
    """Weighted-mean-pool over the sequence + projection stack for
    embedding models (reference: embedding_head.py:12-80)."""

    def __init__(self, architecture: TransformerArchitectureConfig):
        arch = architecture
        assert arch.embedding_head_config is not None
        cfg: EmbeddingHeadConfig = arch.embedding_head_config
        self.name = cfg.name
        self.dims = [arch.hidden_size] + list(cfg.proj_layers)
        self.dtype = arch.dtype

    def init(self, key: jax.Array) -> dict:
        params = {}
        for i, (d_in, d_out) in enumerate(zip(self.dims[:-1], self.dims[1:])):
            params[f"proj_{i}_{self.name}"] = xavier_normal_init(
                jax.random.fold_in(key, i), (d_in, d_out), self.dtype
            )
        return params

    def param_metas(self) -> dict:
        metas = {}
        for i, _ in enumerate(self.dims[:-1]):
            name = f"proj_{i}_{self.name}"
            metas[name] = ParamMeta(
                parameter_name=name,
                partition_spec=(None, None),
                is_model_parallel_duplicate=True,
            )
        return metas

    def __call__(self, params: dict, x: dict, ctx: ForwardContext) -> dict:
        h = x["embeddings"] if x.get("embeddings") is not None else x["activations"]
        weights = x.get("loss_weights")
        if weights is None:
            weights = jnp.ones(h.shape[:2], dtype=jnp.float32)
        weights = weights.astype(jnp.float32)
        denom = jnp.maximum(weights.sum(axis=1, keepdims=True), 1.0)
        pooled = (h.astype(jnp.float32) * weights[..., None]).sum(axis=1) / denom
        pooled = pooled.astype(h.dtype)
        for i, _ in enumerate(self.dims[:-1]):
            pooled = pooled @ params[f"proj_{i}_{self.name}"].astype(pooled.dtype)
            if i < len(self.dims) - 2:
                pooled = jax.nn.gelu(pooled)
        out = dict(x)
        out["embeddings"] = pooled
        return out
