"""Transformer suite configuration.

TPU-native re-design of the reference's transformer config composition
(reference: src/scaling/transformer/context/config.py:28-459): one frozen
pydantic tree wiring topology + optimizer + LR schedules + trainer + data +
architecture. ``Precision`` maps straight onto jnp dtypes (bf16 is the TPU
native compute type); fp16 keeps the dynamic loss scaler for parity.
"""

from __future__ import annotations

from enum import Enum
from pathlib import Path
from typing import Any, List, Optional

import jax.numpy as jnp
from pydantic import AliasChoices, Field, model_validator

from ...config import BaseConfig
from ...context.context import ContextConfig
from ...logging import LoggerConfig
from ...nn.activation_function import ActivationFunction
from ...nn.lora import LoRaConfig
from ...nn.masked_softmax import MaskedSoftmaxConfig
from ...nn.norm import LayerNormConfig, NormType
from ...nn.rotary import RopeScalingConfig
from ...optimizer import LearningRateSchedulerConfig, OptimizerConfig
from ...topology import TopologyConfig
from ...trainer import TrainerConfig


class Precision(Enum):
    FLOAT16 = "float16"
    BFLOAT16 = "bfloat16"
    FLOAT32 = "float32"

    @property
    def dtype(self):
        return {
            Precision.FLOAT16: jnp.float16,
            Precision.BFLOAT16: jnp.bfloat16,
            Precision.FLOAT32: jnp.float32,
        }[self]


class MLPType(Enum):
    DEFAULT = "default"
    SWIGLU = "swiglu"
    # beyond the reference: routed mixture-of-experts FFN with expert
    # parallelism over the data mesh axis (nn/moe.py; SURVEY §2.4 lists EP
    # as absent upstream)
    MOE = "moe"


class LayerKind(Enum):
    """The ONE mixer a layer of a ``layer_pattern`` stack has (Nemotron-H's
    ``hybrid_override_pattern``: ``M``, ``E``, ``*``). A block of two
    pre-norm sub-blocks, operator then FFN (LFM2's), is two such layers."""

    MAMBA = "mamba"          # Mamba-2 state-space mixer (nn/mamba.py)
    MOE = "moe"              # routed MLP (nn/moe.py)
    ATTENTION = "attention"  # softmax attention
    CONV = "conv"            # gated short convolution (nn/short_conv.py)
    MLP = "mlp"              # dense MLP of mlp_type / mlp_factor (nn/mlp.py)
    # multi-head latent attention (nn/latent_attention.py): low-rank q and kv
    # projections, ONE latent line a token in the paged pool
    LATENT = "latent"
    # softmax attention over a sliding window, with a head count, a window and
    # a rotary of its own beside the 'attention' layers' (nn/window_attention.py):
    # served, it keeps a RING of lines a slot, not pages
    WINDOW = "window"
    # gated delta rule (nn/gated_delta.py): a recurrent layer whose transition
    # is NOT diagonal (the state is read, corrected by what it holds for the
    # key, written); served, it keeps a float32 state and a conv tail a slot
    DELTA = "delta"
    # latent attention over a sliding window, with sizes (ranks, heads, head
    # widths, rotary base) of its own beside the 'latent' layers'
    # (nn/window_latent_attention.py): served, it keeps a RING of latent lines
    # a slot, not pages
    WINDOW_LATENT = "window_latent"


class AttentionGate(Enum):
    """The gate on the output of a ``layer_pattern`` stack's softmax attention
    layers ('attention', 'window', 'latent' and 'window_latent'): none, or
    ``per_head``: ``g =
    sigmoid(x W_g)``, one value a query head from the layer's normed input,
    on the head's output before the output projection (the head-wise gate of
    arXiv:2505.06708); or ``elementwise``: a value a LANE of every head's
    output, ``sigmoid(gate)`` with ``[q | gate] = x W_q`` head by head, the
    query projection twice as wide and no leaf of its own (Qwen3-Next's;
    'attention' layers only)."""

    NONE = "none"
    PER_HEAD = "per_head"
    ELEMENTWISE = "elementwise"


class MoERouter(Enum):
    """How a routed MLP scores the experts: ``softmax`` over all of them,
    the top k taken from the probabilities; or ``sigmoid_bias``: each
    expert's score ``s_e = sigmoid(logit_e)`` on its own, the k experts with
    the largest ``s_e + b_e`` chosen (``b``: a selection bias, used for the
    choice only) and gated by their ``s_e`` (DeepSeek-V3's router)."""

    SOFTMAX = "softmax"
    SIGMOID_BIAS = "sigmoid_bias"


class KeyQueryNormScope(Enum):
    """What ``key_query_norm`` normalises: each head's ``head_dim`` values
    with one learned weight of ``head_dim`` (the reference's), or the whole
    projection before it is split into heads, with one learned weight of
    its full width (OLMoE's ``q_norm`` / ``k_norm``)."""

    HEAD = "head"
    PROJECTION = "projection"


class RelativePositionEmbeddingType(Enum):
    NONE = "none"
    ROTARY = "rotary"
    ROTARY_COMPLEX = "rotary_complex"


class BitfitConfig(BaseConfig):
    """BitFit fine-tuning: fresh named bias parameters on linears/norms
    (reference: config.py:72-78)."""

    name: str = Field("bitfit", description="name suffix of the fresh bias parameters")


class AdapterConfig(BaseConfig):
    """Bottleneck adapters inserted after attention and/or MLP blocks
    (reference: config.py:80-97, layers/layer.py:140-187)."""

    name: str = Field("adapter", description="adapter parameter name suffix")
    attention_downsampling_factor: Optional[float] = Field(
        None,
        description="adapter width = hidden * factor after the attention "
        "block (multiplicative like the reference, config.py:105 — e.g. "
        "0.25 for a 4x bottleneck)",
        gt=0,
    )
    mlp_downsampling_factor: Optional[float] = Field(
        None,
        description="adapter width = hidden * factor after the mlp block",
        gt=0,
    )
    init_std: float = Field(1.0e-3, description="std of the adapter init")


class SoftpromptConfig(BaseConfig):
    """Learned prompt embeddings overwriting the first ``n_tokens``
    positions (reference: config.py:99-105, layers/embedding.py:63-81)."""

    name: str = Field("softprompt", description="softprompt parameter name suffix")
    n_tokens: int = Field(8, description="number of learned prompt positions", gt=0)


class EmbeddingHeadConfig(BaseConfig):
    """Projection stack on weighted-mean-pooled hidden states for
    embedding models (reference: config.py:107-124, embedding_head.py:12-80)."""

    name: str = Field("embedding_head", description="")
    proj_layers: List[int] = Field(
        default_factory=list,
        description="hidden sizes of the projection stack; last entry is the "
        "embedding dimension",
    )


class MupConfig(BaseConfig):
    """Maximal-update parametrization (Tensor Programs V, Yang & Hu 2021):
    tune hyperparameters on a small base width, transfer them to any width.

    The reference shipped a ``umup`` flag that implemented nothing; this is
    the real thing, wired through four rules (Adam variant):

    - hidden-matrix AND readout learning rates scale by
      base_hidden_size / hidden_size (applied as ``lr_scale`` on the
      optimizer param groups; embedding, norms, biases and softprompts
      stay unscaled);
    - attention logits scale 1/d beyond the base width
      (sqrt(base_head_dim)/head_dim — equal to 1/sqrt(head_dim) at base);
    - LM-head logits multiply by the width-independent tunable output_mult
      (the width correction is the readout LR scale — the multiplier and
      LR formulations of the muP output rule are alternatives, not
      composable);
    - the LM head zero-initializes (readout_zero_init), removing the
      width-dependent readout noise at init.

    Hidden weights keep xavier init (variance already ~1/width). Verified
    by the coordinate-check test: logit updates stay width-independent
    where standard parametrization grows with width
    (tests/transformer/test_mup.py)."""

    base_hidden_size: int = Field(
        description="hidden size of the tuned base model; scaling rules "
        "activate as hidden_size grows past it",
        gt=0,
    )
    base_num_attention_heads: Optional[int] = Field(
        None,
        description="head count of the tuned base model; defaults to this "
        "model's head count (width grown by head_dim). Set it when width "
        "is grown by ADDING heads instead — the attention rule needs the "
        "base model's true head_dim, not hidden/width-mult",
        gt=0,
    )
    output_mult: float = Field(
        1.0, description="tunable multiplier on the LM-head logits", gt=0
    )
    readout_zero_init: bool = Field(
        True, description="zero-initialize the LM head projection"
    )


class FixedMultipliers(BaseConfig):
    """Constants a published configuration multiplies its activations by
    (Falcon-H1's ``*_multiplier(s)`` keys): numbers of the model like an
    epsilon, not ``MupConfig``, which derives scales from a base width and a
    learning-rate rule. All ones: the plain model, and nothing is lowered for
    them. A seeded init starts each matrix a multiplier FOLLOWS at its usual
    scale divided by that multiplier (the owner of the multiplier does it),
    so that fresh weights are the map they would be without multipliers."""

    embedding: float = Field(1.0, description="on the embedded tokens", gt=0)
    lm_head: float = Field(1.0, description="on the logits", gt=0)
    attention_in: float = Field(
        1.0, description="on the normed input of attention's projections", gt=0)
    attention_out: float = Field(
        1.0, description="on attention's output (after its projection)", gt=0)
    key: float = Field(
        1.0, description="on the keys, before rotary and the cache", gt=0)
    ssm_in: float = Field(
        1.0, description="on the normed input of a Mamba-2 mixer's in_proj",
        gt=0)
    ssm_out: float = Field(
        1.0, description="on a Mamba-2 mixer's output (after out_proj)", gt=0)
    ssm: List[float] = Field(
        [1.0] * 5, description="on in_proj's output by segment: z, x, B, C, dt",
        min_length=5, max_length=5)
    mlp_gate: float = Field(
        1.0, description="on a SwiGLU MLP's gate projection, inside the "
        "activation", gt=0)
    mlp_down: float = Field(
        1.0, description="on a SwiGLU MLP's output (after down_proj)", gt=0)

    @property
    def plain(self) -> bool:
        return self == FixedMultipliers()


# what sizes a 'latent' layer (nn/latent_attention.py)
LATENT_FIELDS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                 "qk_rope_head_dim", "v_head_dim")
# what makes the 'latent' layers (nn/sparse_latent_attention.py) or, in a
# stack without them, the 'attention' layers (nn/sparse_attention.py) SPARSE
INDEX_FIELDS = ("index_n_heads", "index_head_dim", "index_topk")
# what sizes a 'window_latent' layer: window_latent_<name>
WINDOW_LATENT_FIELDS = tuple(
    f"window_latent_{name}" for name in ("num_attention_heads",) + LATENT_FIELDS)
# what sizes a 'delta' layer (nn/gated_delta.py)
DELTA_FIELDS = ("delta_num_key_heads", "delta_num_value_heads",
                "delta_key_head_dim", "delta_value_head_dim")


class TransformerArchitectureConfig(BaseConfig):
    """Model shape + feature switches
    (reference: src/scaling/transformer/context/config.py:126-330)."""

    vocab_size: int = Field(description="size of the vocabulary", gt=0)
    vocab_file: Optional[Path] = Field(None, description="tokenizer vocab json")
    hidden_size: int = Field(description="transformer hidden size", gt=0)
    num_layers: int = Field(description="number of transformer layers", ge=0)
    num_attention_heads: int = Field(description="number of attention heads", gt=0)
    attention_num_kv_heads: Optional[int] = Field(
        None, description="number of kv heads for grouped-query attention"
    )
    attention_head_dim: Optional[int] = Field(
        None,
        description="size of one attention head when it is not hidden_size / "
        "num_attention_heads (config.json's head_dim): q and the output "
        "projection are then num_attention_heads * head_dim wide",
        gt=0,
    )
    attention_qkv_in_one: bool = Field(
        True, description="store q,k,v projections in one fused weight"
    )
    attention_bias: bool = Field(
        True, description="add bias terms to the attention projections"
    )
    attention_use_matmul: bool = Field(
        False,
        description="kept for config parity with the reference's "
        "torch.matmul/baddbmm switch (config.py:215); XLA picks the matmul "
        "strategy itself, so this has no effect on TPU",
    )
    num_local_attention_heads: int = Field(
        0, description="number of heads restricted to a local window", ge=0
    )
    local_attention_window_size: Optional[int] = Field(
        None, description="window size of local attention heads"
    )
    rotary_embedding_base: int = Field(10000, description="rotary base theta")
    rotary_percentage: float = Field(
        1.0, description="fraction of head dim that is rotated", gt=0.0, le=1.0
    )
    sequence_length: int = Field(2048, description="training sequence length", gt=0)
    norm_type: NormType = Field(NormType.LAYERNORM, description="")
    relative_position_embedding_type: RelativePositionEmbeddingType = Field(
        RelativePositionEmbeddingType.ROTARY, description=""
    )
    mlp_type: MLPType = Field(MLPType.DEFAULT, description="")
    mlp_factor: float = Field(4.0, description="mlp intermediate = factor * hidden", gt=0)
    mlp_bias: bool = Field(True, description="add bias terms to the mlp projections")
    moe_num_experts: int = Field(
        8, description="expert count for mlp_type 'moe'", gt=0
    )
    moe_top_k: int = Field(2, description="experts routed per token", gt=0)
    moe_capacity_factor: float = Field(
        1.25, description="per-expert token buffer slack over the uniform share",
        gt=0,
    )
    moe_aux_loss_coef: float = Field(
        0.01, description="Switch-style load-balance loss coefficient", ge=0
    )
    moe_norm_topk_prob: bool = Field(
        True,
        description="renormalise a token's top-k router probabilities to sum "
        "to one (Switch/GShard practice); false uses them as the softmax over "
        "all experts gave them (OLMoE's norm_topk_prob: false)",
    )
    moe_expert_width: Optional[int] = Field(
        None,
        description="intermediate width of ONE routed expert "
        "(moe_intermediate_size); absent: mlp_factor * hidden_size",
        gt=0,
    )
    moe_glu: bool = Field(
        True,
        description="experts are gated (act(x W_gate) * x W_in) W_out; false: "
        "two matrices, act(x W_in) W_out",
    )
    moe_router: MoERouter = Field(MoERouter.SOFTMAX, description="")
    moe_routed_scaling_factor: float = Field(
        1.0, description="factor on the chosen experts' gates "
        "(routed_scaling_factor)", gt=0,
    )
    moe_norm_topk_eps: float = Field(
        1e-20, description="with moe_router 'sigmoid_bias' and "
        "moe_norm_topk_prob: what is added to the sum of the chosen scores "
        "before they are divided by it (Nemotron-H 1e-20, LFM2 1e-6)", ge=0,
    )
    moe_shared_expert_width: Optional[int] = Field(
        None,
        description="intermediate width of the ONE shared expert every token "
        "runs beside its routed ones (un-gated like them when moe_glu is "
        "false); absent: no shared expert",
        gt=0,
    )
    moe_shared_expert_gate: bool = Field(
        False, description="the shared expert's output is scaled by "
        "sigmoid(x w_s), one value a token (Qwen3-Next's shared_expert_gate)")
    moe_experts_first: int = Field(
        0,
        description="first expert this program holds: the routed layers hold "
        "the contiguous range [first, first + held) of moe_num_experts, one "
        "rank's share of an expert-parallel deployment. The router keeps "
        "all moe_num_experts outputs and moe_top_k a token; gates of absent "
        "experts are dropped, not renormalised",
        ge=0,
    )
    moe_experts_held: Optional[int] = Field(
        None, description="experts held from moe_experts_first on; absent: "
        "all of them", gt=0,
    )
    moe_n_group: int = Field(
        1, description="contiguous groups the 'sigmoid_bias' router's "
        "moe_num_experts are divided into (n_group); above 1 the choice is "
        "group-limited: a group scores the sum of its two largest s_e + b_e "
        "and a token chooses inside its moe_topk_group best groups", ge=1)
    moe_topk_group: int = Field(
        1, description="groups a token may choose its experts from "
        "(topk_group)", ge=1)
    q_lora_rank: Optional[int] = Field(
        None, description="a 'latent' layer: width of the query latent "
        "(x W_DQ, RMSNorm'd, then up to the heads' nope + rope queries)", gt=0)
    kv_lora_rank: Optional[int] = Field(
        None, description="a 'latent' layer: width of the KV latent c_kv, "
        "what a token leaves in the cache beside its ONE rotary key", gt=0)
    qk_nope_head_dim: Optional[int] = Field(
        None, description="a 'latent' head's query/key part without "
        "position", gt=0)
    qk_rope_head_dim: Optional[int] = Field(
        None, description="a 'latent' head's rotary query part, and the ONE "
        "rotary key all heads share", gt=0)
    v_head_dim: Optional[int] = Field(
        None, description="a 'latent' head's value size", gt=0)
    rope_scaling: Optional[RopeScalingConfig] = Field(
        None, description="the checkpoint's rope_scaling (YaRN alone is "
        "built, nn/rotary.py); applied by a layer_pattern's 'latent' layers "
        "and by its 'attention' layers, on the rotary_percentage of a head "
        "that is rotated; never by its 'window' layers")
    window_size: Optional[int] = Field(
        None, description="a 'window' or 'window_latent' layer: lines a query "
        "sees, itself included (query t attends over keys s with t - "
        "window_size < s <= t: the published sliding_window)", gt=0)
    window_num_attention_heads: Optional[int] = Field(
        None, description="a 'window' layer's query heads, over the same "
        "attention_num_kv_heads and attention_head_dim as the 'attention' "
        "layers'; absent: num_attention_heads", gt=0)
    window_rotary_embedding_base: int = Field(
        10000, description="a 'window' layer's rotary base theta; its "
        "frequencies are the base's, unscaled")
    window_rotary_percentage: float = Field(
        1.0, description="fraction of a 'window' layer's head that is rotated",
        gt=0.0, le=1.0)
    window_latent_num_attention_heads: Optional[int] = Field(
        None, description="a 'window_latent' layer's heads", gt=0)
    window_latent_q_lora_rank: Optional[int] = Field(
        None, description="a 'window_latent' layer's query latent", gt=0)
    window_latent_kv_lora_rank: Optional[int] = Field(
        None, description="a 'window_latent' layer's KV latent c_kv: what a "
        "token leaves in the layer's ring beside its ONE rotary key", gt=0)
    window_latent_qk_nope_head_dim: Optional[int] = Field(
        None, description="a 'window_latent' head's query/key part without "
        "position", gt=0)
    window_latent_qk_rope_head_dim: Optional[int] = Field(
        None, description="a 'window_latent' head's rotary query part and the "
        "layer's ONE rotary key", gt=0)
    window_latent_v_head_dim: Optional[int] = Field(
        None, description="a 'window_latent' head's value size", gt=0)
    window_latent_rotary_embedding_base: int = Field(
        10000, description="a 'window_latent' layer's rotary base theta; its "
        "frequencies are the base's, unscaled")
    latent_lora_rescale: bool = Field(
        False, description="a 'latent' or 'window_latent' layer multiplies "
        "what comes out of its two latent norms by (hidden_size / rank) ** "
        "0.5, the query latent by its q_lora_rank's and the KV latent by its "
        "kv_lora_rank's; the rotary key is not scaled")
    attention_gate: AttentionGate = Field(
        AttentionGate.NONE, description="a gate on the output of a "
        "layer_pattern's 'attention', 'window', 'latent' and 'window_latent' "
        "layers (see AttentionGate)")
    index_n_heads: Optional[int] = Field(
        None, description="a SPARSE attention layer: a layer_pattern's "
        "'latent' layers (nn/sparse_latent_attention.py) or, in a pattern "
        "without them, its 'attention' layers (nn/sparse_attention.py): "
        "heads of the indexer that scores every cached line for every query; "
        "with index_head_dim and index_topk, all three or none", gt=0)
    index_head_dim: Optional[int] = Field(
        None, description="width of an indexer head and of the ONE index key "
        "a token leaves in the cache (the third leaf of its line); in a "
        "'latent' layer its first qk_rope_head_dim lanes are rotary (the "
        "indexer's query comes from the query latent), in an 'attention' "
        "layer all of them (its query comes from the hidden state)", gt=0)
    index_topk: Optional[int] = Field(
        None, description="lines a query attends over: its index_topk best "
        "by the indexer's scores, chosen exactly; every visible line while "
        "there are no more than that", gt=0)
    layer_pattern: Optional[List[LayerKind]] = Field(
        None,
        description="a kind a layer: each layer is ONE norm, ONE mixer of its "
        "kind and the residual (x <- x + Mixer(Norm(x))); absent: the "
        "homogeneous stack of attention + MLP layers",
    )
    hc_streams: int = Field(
        1, description="residual streams a token carries through a "
        "layer_pattern stack (manifold-constrained hyper-connections, "
        "nn/hyper_connection.py: the published hc_mult): every single-mixer "
        "layer reads a learned mix of them and writes its output back into "
        "all of them under a doubly stochastic mixing matrix; the stream is "
        "(batch, seq, hc_streams * hidden_size), the embedding in every "
        "stream at the start, folded into one by a learned gate before the "
        "final norm. 1 is the plain residual x <- x + Mixer(Norm(x)). Served "
        "only", ge=1)
    hc_sinkhorn_iters: int = Field(
        20, description="Sinkhorn steps that project a mapping's hc_streams x "
        "hc_streams matrix onto the doubly stochastic ones: the softmax over "
        "rows then the columns, then rows and columns hc_sinkhorn_iters - 1 "
        "times more", ge=1)
    hc_eps: float = Field(
        1e-6, description="added to a mapping's gates and to every sum a "
        "Sinkhorn step divides by", ge=0.0)
    hc_res_clamp_min: float = Field(
        -30.0, description="a mapping's mixing logits are clipped to "
        "[hc_res_clamp_min, hc_res_clamp_max] before the exponential (the "
        "published mhc_h_res_clamp_min / _max)")
    hc_res_clamp_max: float = Field(30.0, description="see hc_res_clamp_min")
    delta_num_key_heads: Optional[int] = Field(
        None, description="a 'delta' layer (nn/gated_delta.py): heads of q "
        "and k (linear_num_key_heads); with the three sizes below, all four "
        "or none; its conv has conv_kernel taps", gt=0)
    delta_num_value_heads: Optional[int] = Field(
        None, description="a 'delta' layer's heads of v, z and the state, a "
        "multiple of delta_num_key_heads (linear_num_value_heads)", gt=0)
    delta_key_head_dim: Optional[int] = Field(
        None, description="lanes of a 'delta' layer's q and k head: the "
        "state's rows (linear_key_head_dim)", gt=0)
    delta_value_head_dim: Optional[int] = Field(
        None, description="lanes of a 'delta' layer's v head: the state's "
        "columns (linear_value_head_dim)", gt=0)
    mamba_num_heads: int = Field(
        64, description="heads of a Mamba-2 mixer; its inner width is "
        "mamba_num_heads * mamba_head_dim", gt=0)
    mamba_head_dim: int = Field(64, description="", gt=0)
    ssm_state_size: int = Field(128, description="state size N a head", gt=0)
    n_groups: int = Field(
        8, description="groups sharing B and C, and of the gated norm", gt=0)
    conv_kernel: int = Field(
        4, description="depthwise causal conv taps, of a Mamba-2 mixer and of "
        "a gated short convolution (LFM2's conv_L_cache)", gt=1)
    time_step_min: float = Field(0.001, description="dt init range", gt=0)
    time_step_max: float = Field(0.1, description="dt init range", gt=0)
    time_step_floor: float = Field(1e-4, description="dt init floor", gt=0)
    parallel_ssm: bool = Field(
        False,
        description="every layer of the homogeneous stack runs a Mamba-2 "
        "mixer (mamba_num_heads x mamba_head_dim, ssm_state_size, n_groups, "
        "conv_kernel) BESIDE its attention, both on the one normed input, "
        "summed into one residual (Falcon-H1's block): x <- x + SSM(N(x)) + "
        "Attn(N(x)), then the MLP sub-block. Served only: a layer then keeps "
        "a paged KV line and a recurrent line a slot",
    )
    multipliers: FixedMultipliers = Field(
        FixedMultipliers(),
        description="published constants on the activations (see "
        "FixedMultipliers); all ones by default",
    )
    activation_function: ActivationFunction = Field(ActivationFunction.GELU, description="")
    precision: Precision = Field(Precision.FLOAT32, description="compute/param dtype")
    layernorm: LayerNormConfig = Field(LayerNormConfig(), description="")
    masked_softmax: MaskedSoftmaxConfig = Field(MaskedSoftmaxConfig(), description="")
    causal: bool = Field(True, description="use a causal attention mask")
    key_query_norm: bool = Field(False, description="normalise q/k per head")
    key_query_norm_scope: KeyQueryNormScope = Field(
        KeyQueryNormScope.HEAD,
        description="with key_query_norm: 'head' normalises each head's "
        "values, 'projection' the whole q (and k) projection before the "
        "split into heads, one learned weight over its full width",
    )
    weight_tying: bool = Field(False, description="tie lm head to the embedding")
    loop_steps: int = Field(
        1,
        description="times the trunk of num_layers layers is run over the "
        "SAME weights (a looped / universal transformer): step u starts from "
        "step u-1's output after the final norm, which so runs at the end of "
        "EVERY step; the K and V of (step u, layer l) are a cache line of "
        "their own, u * num_layers + l. 1 is the plain decoder",
        ge=1,
    )
    sandwich_norm: bool = Field(
        False,
        description="norm each sub-layer's OUTPUT before it is added to the "
        "residual stream (h = h + norm(attn), h = h + norm(mlp)), beside the "
        "pre-norms of its input: four norms a layer",
    )
    loop_exit_gate: bool = Field(
        False,
        description="a learned gate Linear(hidden, 1) + bias on each step's "
        "normed output: lambda_u = sigmoid(w . h_u + b), from which the exit "
        "distribution p_u over the steps is read (needs loop_steps > 1)",
    )
    loop_exit_threshold: float = Field(
        1.0,
        description="cumulative exit probability at which a token's last "
        "step is taken; 1 runs every step for every token, the only value "
        "served (under 1 the work would depend on the data)",
    )
    masked_softmax_fusion: bool = Field(True, description="kept for config parity")
    layernorm_epsilon: float = Field(1.0e-5, description="kept for config parity")

    dropout_embedding: float = Field(0.0, description="", ge=0.0, le=1.0)
    dropout_attention_probs: float = Field(0.0, description="", ge=0.0, le=1.0)
    dropout_after_attention: float = Field(0.0, description="", ge=0.0, le=1.0)
    dropout_after_mlp: float = Field(0.0, description="", ge=0.0, le=1.0)

    mup: Optional[MupConfig] = Field(
        None,
        description="maximal-update parametrization for width-transferable "
        "hyperparameters (see MupConfig)",
    )

    # fine tuning / PEFT
    bitfit_bias_config: Optional[BitfitConfig] = Field(None, description="")
    adapter_config: Optional[AdapterConfig] = Field(None, description="")
    softprompt_config: Optional[SoftpromptConfig] = Field(None, description="")
    lora_config: Optional[LoRaConfig] = Field(None, description="")
    embedding_head_config: Optional[EmbeddingHeadConfig] = Field(None, description="")
    finetunable_token_ids: List[int] = Field(
        default_factory=list,
        description="restrict embedding gradients to these token ids",
    )
    image_encoder: bool = Field(
        False,
        description="multimodal image encoder: 384x384 images become 144 "
        "prefix tokens spliced into the embedding stream (ViT patch "
        "backbone; the reference uses a CLIP ResNet, image_encoder.py)",
    )
    image_encoder_width: int = Field(768, description="vision tower width", gt=0)
    image_encoder_layers: int = Field(6, description="vision tower depth", gt=0)
    image_encoder_heads: int = Field(12, description="vision tower heads", gt=0)
    image_encoder_backbone: str = Field(
        "vit",
        description="'vit' trains the patch backbone from scratch; 'clip' "
        "builds a CLIP-ViT trunk that loads pretrained huggingface "
        "CLIPVisionModel weights; 'clip_resnet' builds the reference's "
        "actual trunk — the CLIP ModifiedResNet (RN50x16 at the defaults, "
        "clip.py) — so reference/magma vision checkpoints transfer. Set "
        "image_encoder_clip_checkpoint to load the weights at startup, or "
        "call ImageEncoder.load_clip_weights manually",
        pattern="^(vit|clip|clip_resnet)$",
    )
    image_encoder_resnet_stages: List[int] = Field(
        [6, 8, 18, 8],
        description="bottleneck blocks per stage for the clip_resnet "
        "backbone (default: RN50x16); exactly 4 stages (CLIP layout)",
        min_length=4,
        max_length=4,
    )
    image_encoder_resnet_channels: int = Field(
        96,
        description="stem output channels for the clip_resnet backbone "
        "(default: RN50x16; feature dim is 8*channels*4)",
        gt=0,
    )
    image_encoder_clip_checkpoint: Optional[str] = Field(
        None,
        description="path to pretrained CLIP vision weights applied at "
        "train startup (fresh runs only, not resumes): a torch state_dict "
        "file (torch.load) or a local transformers CLIPVisionModel "
        "directory; requires a clip backbone with geometry matching the "
        "checkpoint",
    )
    dropout_image_encoder: float = Field(
        0.0, description="dropout applied after the image encoder projection",
        ge=0.0, le=1.0,
    )

    @model_validator(mode="after")
    def _validate(self):
        if self.num_local_attention_heads > 0 and self.local_attention_window_size is None:
            raise ValueError("local attention heads require local_attention_window_size")
        if (self.key_query_norm_scope == KeyQueryNormScope.PROJECTION
                and not self.key_query_norm):
            raise ValueError(
                "key_query_norm_scope 'projection' says which q/k norm the "
                "model has; set key_query_norm true as well"
            )
        held = self.moe_experts_held
        if held is not None and self.moe_experts_first + held > self.moe_num_experts:
            raise ValueError(
                f"experts [{self.moe_experts_first}, {self.moe_experts_first} + "
                f"{held}) do not lie in moe_num_experts {self.moe_num_experts}"
            )
        if (self.attention_head_dim is not None and self.layer_pattern is None
                and not self.parallel_ssm):
            raise ValueError(
                "attention_head_dim without layer_pattern or parallel_ssm: "
                "the homogeneous TransformerLayer sizes its heads hidden_size "
                "/ num_attention_heads (rotary, muP); a head size of its own "
                "is built for the single-mixer stack and the parallel block"
            )
        if self.parallel_ssm:
            self._validate_parallel_ssm()
        if not self.multipliers.plain:
            for name, what in (
                    ("layer_pattern", "the single-mixer layer applies none"),
                    ("mup", "MupConfig scales the logits and the attention "
                     "scores itself")):
                if getattr(self, name) is not None:
                    raise ValueError(
                        f"multipliers with {name}: {what}; not supported")
            if self.mlp_type != MLPType.SWIGLU and (
                    self.multipliers.mlp_gate != 1.0
                    or self.multipliers.mlp_down != 1.0):
                raise ValueError(
                    "multipliers.mlp_gate / mlp_down are a SwiGLU MLP's; "
                    f"mlp_type is {self.mlp_type.value!r}")
            if self.weight_tying and (self.multipliers.embedding != 1.0
                                      or self.multipliers.lm_head != 1.0):
                raise ValueError(
                    "multipliers.embedding / lm_head with weight_tying: the "
                    "tied head applies none; not supported")
        if self.attention_head_dim is not None and self.lora_config is not None:
            raise ValueError(
                "attention_head_dim with lora_config: the LoRA modules are "
                "sized from hidden_size; not supported"
            )
        given = [name for name in INDEX_FIELDS if getattr(self, name) is not None]
        pattern = self.layer_pattern or ()
        if given and (len(given) < len(INDEX_FIELDS) or (
                (LayerKind.LATENT in pattern) == (LayerKind.ATTENTION in pattern))):
            raise ValueError(
                f"{given} without {[n for n in INDEX_FIELDS if n not in given]}"
                " or without ONE kind of attention layer to make sparse: the "
                "indexer of a sparse attention layer is sized by "
                "index_n_heads, index_head_dim and index_topk together, and "
                "a layer_pattern's 'latent' layers have one, or, in a pattern "
                "without them, its 'attention' layers")
        if self.hc_streams > 1:
            self._validate_hyper_connection()
        if self.layernorm.weight_offset and self.norm_type != NormType.RMS:
            raise ValueError(
                "layernorm.weight_offset with norm_type "
                f"{self.norm_type.value!r}: the offset-from-one weight is a "
                "kind of the RMS norm")
        if self.moe_shared_expert_gate and not self.moe_shared_expert_width:
            raise ValueError(
                "moe_shared_expert_gate without moe_shared_expert_width: the "
                "gate scales the shared expert's output")
        if (self.attention_gate != AttentionGate.NONE
                and self.layer_pattern is None):
            raise ValueError(
                "attention_gate without layer_pattern: the gate is built for "
                "the pattern stack's 'attention' and 'window' layers; the "
                "homogeneous TransformerLayer has none")
        if self.layer_pattern is not None:
            self._validate_pattern()
        elif self.rope_scaling is not None:
            raise ValueError(
                "rope_scaling without layer_pattern: only a pattern stack's "
                "'latent' layers apply YaRN")
        if self.moe_n_group > 1 or self.moe_topk_group > 1:
            per_group = self.moe_num_experts // self.moe_n_group
            if (self.moe_router != MoERouter.SIGMOID_BIAS
                    or self.moe_num_experts % self.moe_n_group
                    or self.moe_topk_group > self.moe_n_group
                    or per_group < 2
                    or self.moe_top_k > self.moe_topk_group * per_group):
                raise ValueError(
                    f"moe_n_group {self.moe_n_group} / moe_topk_group "
                    f"{self.moe_topk_group}: the group-limited choice is the "
                    "'sigmoid_bias' router's, over moe_num_experts "
                    f"({self.moe_num_experts}) in whole groups of at least "
                    "two, moe_topk_group <= moe_n_group of them holding "
                    f"moe_top_k ({self.moe_top_k}) experts")
        if self.mlp_type == MLPType.MOE:
            if self.moe_top_k > self.moe_num_experts:
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) cannot exceed "
                    f"moe_num_experts ({self.moe_num_experts})"
                )
            if self.mlp_bias:
                raise ValueError(
                    "mlp_type 'moe' does not support mlp_bias; set it false "
                    "(experts are GLU FFNs without bias)"
                )
        if self.loop_exit_gate and self.loop_steps < 2:
            raise ValueError(
                "loop_exit_gate reads the exit distribution over the steps of "
                "a looped trunk; set loop_steps > 1 or drop the gate"
            )
        if self.loop_exit_threshold != 1.0:
            raise ValueError(
                f"loop_exit_threshold {self.loop_exit_threshold}: only 1 is "
                "served (every token runs every step and the logits are the "
                "last step's); a threshold under 1 makes the steps a token "
                "runs depend on the data, which scheduler, cache and program "
                "do not do yet"
            )
        if self.loop_steps > 1 and self.mlp_type == MLPType.MOE:
            raise ValueError(
                "loop_steps > 1 with mlp_type 'moe': the routed MLP's load "
                "and auxiliary loss are summed over a stack walked once; a "
                "looped routed trunk is not supported"
            )
        if self.mup is not None and self.weight_tying:
            raise ValueError(
                "mup does not compose with weight_tying: the tied table "
                "would need embedding-scale init and readout-scale LR at "
                "once; untie the head to use mup"
            )
        return self

    def _validate_parallel_ssm(self):
        """What the parallel block does not build, each by name."""
        if self.layer_pattern is not None:
            raise ValueError(
                "parallel_ssm with layer_pattern: a pattern's layers have ONE "
                "mixer each; the parallel block is the homogeneous stack's")
        if self.loop_steps > 1:
            raise ValueError(
                "parallel_ssm with loop_steps > 1: a looped trunk keeps one "
                "cache line a (step, layer) and no recurrent line; not "
                "supported")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"mamba_num_heads {self.mamba_num_heads} is not a multiple "
                f"of n_groups {self.n_groups}")
        for name in ("adapter_config", "lora_config", "bitfit_bias_config",
                     "mup", "softprompt_config"):
            if getattr(self, name) is not None:
                raise ValueError(
                    f"parallel_ssm with {name}: the parallel block builds "
                    "none of the fine-tuning modules and is not trained")
        if self.sandwich_norm:
            raise ValueError(
                "parallel_ssm with sandwich_norm: the two mixers' sum has no "
                "published output norm; not supported")
        if self.mlp_type == MLPType.MOE:
            raise ValueError(
                "parallel_ssm with mlp_type 'moe': the parallel block's MLP "
                "is dense (mlp_type 'swiglu' or 'default')")
        if self.num_local_attention_heads:
            raise ValueError(
                "parallel_ssm with num_local_attention_heads: windowed heads "
                "beside a recurrent state are not built")

    def _validate_hyper_connection(self):
        """What a stack of hc_streams > 1 does not build, each by name."""
        if self.loop_steps > 1:
            raise ValueError(
                "hc_streams > 1 with loop_steps > 1: a looped trunk starts a "
                "step from the final norm's ONE stream; a looped "
                "hyper-connected trunk is not built")
        if self.layer_pattern is None:
            raise ValueError(
                "hc_streams > 1 without layer_pattern: the mapping belongs to "
                "a sub-layer (one norm, one mixer), which is the pattern "
                "stack's single-mixer layer; the homogeneous TransformerLayer "
                "(attention and MLP in one layer, parallel_ssm) keeps the "
                "plain residual")
        if self.hc_res_clamp_min >= self.hc_res_clamp_max:
            raise ValueError(
                f"hc_res_clamp_min {self.hc_res_clamp_min} is not under "
                f"hc_res_clamp_max {self.hc_res_clamp_max}")

    def _validate_pattern(self):
        """What a ``layer_pattern`` stack does not build, each by name."""
        if len(self.layer_pattern) != self.num_layers:
            raise ValueError(
                f"layer_pattern names {len(self.layer_pattern)} layers, "
                f"num_layers is {self.num_layers}"
            )
        if self.loop_steps > 1:
            raise ValueError(
                "layer_pattern with loop_steps > 1: a looped trunk walks "
                "TransformerLayers and one kind of cache; not supported"
            )
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(
                f"mamba_num_heads {self.mamba_num_heads} is not a multiple "
                f"of n_groups {self.n_groups}"
            )
        if LayerKind.MOE in self.layer_pattern:
            if self.moe_top_k > self.moe_num_experts:
                raise ValueError(
                    f"moe_top_k ({self.moe_top_k}) cannot exceed "
                    f"moe_num_experts ({self.moe_num_experts})"
                )
        for name in ("adapter_config", "lora_config", "bitfit_bias_config",
                     "mup"):
            if getattr(self, name) is not None:
                raise ValueError(
                    f"layer_pattern with {name}: the single-mixer layer "
                    "builds none of the fine-tuning modules"
                )
        if self.sandwich_norm:
            raise ValueError(
                "layer_pattern with sandwich_norm: the single-mixer layer has "
                "one norm, before its mixer"
            )
        if (self.key_query_norm
                and self.key_query_norm_scope != KeyQueryNormScope.HEAD):
            raise ValueError(
                "layer_pattern with key_query_norm_scope 'projection': an "
                "attention mixer norms q and k per head only (before rotary)"
            )
        if LayerKind.MLP in self.layer_pattern and self.mlp_type == MLPType.MOE:
            raise ValueError(
                "layer_pattern with 'mlp' layers and mlp_type 'moe': a dense "
                "'mlp' layer is built from mlp_type 'swiglu' or 'default' and "
                "mlp_factor; the routed layers are the pattern's 'moe'"
            )
        if LayerKind.LATENT in self.layer_pattern:
            missing = [name for name in LATENT_FIELDS
                       if getattr(self, name) is None]
            if missing:
                raise ValueError(
                    f"layer_pattern with 'latent' layers needs {missing}: a "
                    "latent attention layer is sized by q_lora_rank, "
                    "kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim and "
                    "v_head_dim")
            if self.qk_rope_head_dim % 2:
                raise ValueError(
                    f"qk_rope_head_dim {self.qk_rope_head_dim} is odd: rotary "
                    "turns pairs of lanes")
            if (self.relative_position_embedding_type
                    != RelativePositionEmbeddingType.ROTARY):
                raise ValueError(
                    "layer_pattern with 'latent' layers and "
                    "relative_position_embedding_type "
                    f"{self.relative_position_embedding_type.value!r}: a "
                    "latent head's position is its rotary slice; use 'rotary'")
            if (self.index_head_dim is not None
                    and self.index_head_dim < self.qk_rope_head_dim):
                raise ValueError(
                    f"index_head_dim {self.index_head_dim} is narrower than "
                    f"qk_rope_head_dim {self.qk_rope_head_dim}: an indexer "
                    "head's first qk_rope_head_dim lanes are rotary")
        elif (self.rope_scaling is not None
              and LayerKind.ATTENTION not in self.layer_pattern):
            raise ValueError(
                "rope_scaling without 'latent' or 'attention' layers: the "
                "latent and the grouped-query attention mixers apply YaRN; a "
                "'window' layer's rotary table takes the base frequencies")
        given = [n for n in DELTA_FIELDS if getattr(self, n) is not None]
        if LayerKind.DELTA in self.layer_pattern:
            self._validate_delta(given)
        elif given:
            raise ValueError(
                f"{given} without 'delta' layers in layer_pattern: they size "
                "that kind alone")
        if (self.attention_gate == AttentionGate.ELEMENTWISE
                and (LayerKind.WINDOW in self.layer_pattern
                     or LayerKind.LATENT in self.layer_pattern
                     or LayerKind.WINDOW_LATENT in self.layer_pattern
                     or self.index_topk is not None
                     or (self.attention_qkv_in_one
                         and self.attention_num_kv_heads is None))):
            raise ValueError(
                "attention_gate 'elementwise' with 'window', 'latent' or "
                "'window_latent' layers, with index_* or with "
                "attention_qkv_in_one: the gate a lane comes out of a doubled "
                "query projection of a plain 'attention' layer; a window "
                "ring, a latent head or a sparse choice under it is not built")
        if LayerKind.WINDOW in self.layer_pattern:
            self._validate_window()
        elif self.window_num_attention_heads or (
                self.window_size is not None
                and LayerKind.WINDOW_LATENT not in self.layer_pattern):
            raise ValueError(
                "window_size / window_num_attention_heads without 'window' "
                "layers in layer_pattern: they size that kind alone")
        given = [n for n in WINDOW_LATENT_FIELDS if getattr(self, n) is not None]
        if LayerKind.WINDOW_LATENT in self.layer_pattern:
            self._validate_window_latent(given)
        elif given:
            raise ValueError(
                f"{given} without 'window_latent' layers in layer_pattern: "
                "they size that kind alone")
        if self.latent_lora_rescale and not (
                self.latent_layers or self.window_latent_layers):
            raise ValueError(
                "latent_lora_rescale without 'latent' or 'window_latent' "
                "layers: it scales what their two latent norms give")
        if self.index_topk is not None and LayerKind.ATTENTION in self.layer_pattern:
            # a sparse grouped-query layer (nn/sparse_attention.py): what it
            # does not build, each by name
            if self.attention_qkv_in_one and self.attention_num_kv_heads is None:
                raise ValueError(
                    "index_* with attention_qkv_in_one: a sparse attention "
                    "layer has a query, a key and a value projection of its "
                    "own; set attention_qkv_in_one false or "
                    "attention_num_kv_heads")
            if (self.relative_position_embedding_type
                    != RelativePositionEmbeddingType.ROTARY):
                raise ValueError(
                    "index_* with relative_position_embedding_type "
                    f"{self.relative_position_embedding_type.value!r}: the "
                    "indexer's whole head is rotary at the block's base; use "
                    "'rotary'")
            if self.index_head_dim % 2:
                raise ValueError(
                    f"index_head_dim {self.index_head_dim} is odd: rotary "
                    "turns pairs of lanes")
            if self.num_local_attention_heads:
                raise ValueError(
                    "index_* with num_local_attention_heads: a window over a "
                    "learned choice of lines is not built")
            if self.rope_scaling is not None:
                raise ValueError(
                    "index_* with rope_scaling on 'attention' layers: the "
                    "indexer's rotary takes the base frequencies and a scaled "
                    "head beside it has not been held to a reference")
            if self.attention_gate != AttentionGate.NONE:
                raise ValueError(
                    "index_* with attention_gate: a gated sparse attention "
                    "layer is not built")
        if self.num_local_attention_heads:
            raise ValueError(
                "num_local_attention_heads with layer_pattern: the "
                "single-mixer attention layer builds no per-head windows; a "
                "window a layer is the pattern's 'window' kind")

    def _validate_delta(self, given):
        """What a 'delta' layer does not build, each by name."""
        if len(given) < len(DELTA_FIELDS):
            raise ValueError(
                "layer_pattern with 'delta' layers needs "
                f"{[n for n in DELTA_FIELDS if n not in given]}: a gated "
                "delta-rule layer is sized by delta_num_key_heads, "
                "delta_num_value_heads, delta_key_head_dim and "
                "delta_value_head_dim")
        if self.delta_num_value_heads % self.delta_num_key_heads:
            raise ValueError(
                f"delta_num_value_heads {self.delta_num_value_heads} is not "
                f"a multiple of delta_num_key_heads {self.delta_num_key_heads}")
        for kind in (LayerKind.MAMBA, LayerKind.CONV, LayerKind.WINDOW,
                     LayerKind.LATENT, LayerKind.WINDOW_LATENT):
            if kind in self.layer_pattern:
                raise ValueError(
                    f"'delta' layers beside '{kind.value}' layers: two kinds "
                    "of line a slot (or a latent line) in one stack have not "
                    "been held to a reference; not supported")
        if self.hc_streams > 1:
            raise ValueError(
                "'delta' layers with hc_streams > 1: not held to a reference")

    def _validate_window(self):
        """What a 'window' layer does not build, each by name."""
        if self.window_size is None:
            raise ValueError(
                "layer_pattern with 'window' layers needs window_size: the "
                "lines a query of such a layer sees")
        if (self.relative_position_embedding_type
                != RelativePositionEmbeddingType.ROTARY):
            raise ValueError(
                "layer_pattern with 'window' layers and "
                "relative_position_embedding_type "
                f"{self.relative_position_embedding_type.value!r}: a window "
                "layer's position is its rotary at window_rotary_embedding_"
                "base; use 'rotary'")
        if self.attention_num_kv_heads is None:
            raise ValueError(
                "layer_pattern with 'window' layers needs "
                "attention_num_kv_heads: a window layer has a query, a key "
                "and a value projection of its own and shares the KV heads' "
                "count with the 'attention' layers")
        heads = self.window_num_attention_heads or self.num_attention_heads
        if heads % self.attention_num_kv_heads:
            raise ValueError(
                f"window_num_attention_heads {heads} is not a multiple of "
                f"attention_num_kv_heads {self.attention_num_kv_heads}")
        if not self.causal:
            raise ValueError(
                "layer_pattern with 'window' layers and causal false: the "
                "window reaches back from a query, never ahead")
        if self.num_local_attention_heads:
            raise ValueError(
                "'window' layers with num_local_attention_heads: a window a "
                "LAYER and windows a HEAD are two mechanisms; the pattern "
                "stack builds the first")
        if self.index_topk is not None:
            raise ValueError(
                "'window' layers with index_* : an indexer's choice inside a "
                "window is not built")
        if self.key_query_norm:
            raise ValueError(
                "'window' layers with key_query_norm: not held to a "
                "reference yet (the mixer would build it: "
                "nn/window_attention.py); set it false")

    def _validate_window_latent(self, given):
        """What a 'window_latent' layer does not build, each by name."""
        missing = [n for n in WINDOW_LATENT_FIELDS if n not in given]
        if missing or self.window_size is None:
            raise ValueError(
                "layer_pattern with 'window_latent' layers needs "
                f"{missing or ['window_size']}: a windowed latent attention "
                "layer is sized by window_size and by its own "
                f"{list(WINDOW_LATENT_FIELDS)}")
        if self.window_latent_qk_rope_head_dim % 2:
            raise ValueError(
                "window_latent_qk_rope_head_dim "
                f"{self.window_latent_qk_rope_head_dim} is odd: rotary turns "
                "pairs of lanes")
        if (self.relative_position_embedding_type
                != RelativePositionEmbeddingType.ROTARY):
            raise ValueError(
                "layer_pattern with 'window_latent' layers and "
                "relative_position_embedding_type "
                f"{self.relative_position_embedding_type.value!r}: a latent "
                "head's position is its rotary slice; use 'rotary'")
        if not self.causal:
            raise ValueError(
                "layer_pattern with 'window_latent' layers and causal false: "
                "the window reaches back from a query, never ahead")
        for kind in (LayerKind.WINDOW, LayerKind.MAMBA, LayerKind.CONV):
            if kind in self.layer_pattern:
                raise ValueError(
                    f"'window_latent' layers beside '{kind.value}' layers: "
                    "two kinds of line a slot in one stack beside a latent "
                    "ring have not been held to a reference; not supported")
        if self.hc_streams > 1:
            raise ValueError(
                "'window_latent' layers with hc_streams > 1: not held to a "
                "reference")

    def refuse_paged_serving(self, kv_dtype: str = "native") -> None:
        """What the paged serving engine does not serve of an architecture
        that trains and runs uncached, by name, before anything is traced
        (``ServeEngine`` calls it)."""
        if self.delta_layers and kv_dtype != "native":
            raise ValueError(
                f"kv_dtype {kv_dtype!r} with 'delta' layers: an int8 pool "
                "beside a float32 recurrent state has not been held to a "
                "reference; use kv_dtype='native'")
        if self.num_local_attention_heads:
            raise ValueError(
                "num_local_attention_heads with the paged serving engine: "
                "the paged kernel holds a row's tiles under ONE causal mask, "
                "per-head local windows are not built on it; serve a window "
                "a LAYER (layer_pattern's 'window' kind) or run uncached")

    @property
    def window_layers(self) -> int:
        """Layers whose mixer is windowed attention (a ring a slot)."""
        return (self.layer_pattern or []).count(LayerKind.WINDOW)

    @property
    def window_latent_layers(self) -> int:
        """Layers whose mixer is windowed latent attention (a ring of latent
        lines a slot)."""
        return (self.layer_pattern or []).count(LayerKind.WINDOW_LATENT)

    @property
    def delta_layers(self) -> int:
        """Layers whose mixer is the gated delta rule (a line a slot)."""
        return (self.layer_pattern or []).count(LayerKind.DELTA)

    @property
    def latent_layers(self) -> int:
        """Layers whose mixer is latent attention."""
        return (self.layer_pattern or []).count(LayerKind.LATENT)

    @property
    def sparse_layers(self) -> int:
        """Layers that attend over an indexer's choice of lines: the latent
        layers or, in a pattern without them, the attention layers."""
        if self.index_topk is None:
            return 0
        return self.latent_layers or self.layer_pattern.count(
            LayerKind.ATTENTION)

    @property
    def moe_held(self) -> int:
        """Experts a routed layer of this program holds."""
        if self.moe_experts_held is not None:
            return self.moe_experts_held
        return self.moe_num_experts - self.moe_experts_first

    @property
    def has_routed_layers(self) -> bool:
        if self.layer_pattern is not None:
            return LayerKind.MOE in self.layer_pattern
        return self.mlp_type == MLPType.MOE

    @property
    def pattern_embedding_std(self) -> float:
        """The deviation a ``layer_pattern`` stack's embedding table starts
        at, which its residual branches are sized against
        (``MixerLayer.init``): 1 (an embedding table's plain default), or,
        where the table is the head too (``weight_tying``), a head's Xavier
        deviation, so that the logits start where an untied head's do."""
        if self.weight_tying:
            return (2.0 / (self.vocab_size + self.hidden_size)) ** 0.5
        return 1.0

    @property
    def mup_width_mult(self) -> float:
        """Width multiplier m = hidden / base_hidden (1.0 when mup is off)."""
        if self.mup is None:
            return 1.0
        return self.hidden_size / self.mup.base_hidden_size

    @property
    def dtype(self):
        return self.precision.dtype

    @property
    def peft_names(self) -> List[str]:
        """Names of active PEFT modules — drives separate checkpoint files
        (reference: config.py:426-459)."""
        names = []
        if self.bitfit_bias_config:
            names.append(self.bitfit_bias_config.name)
        if self.adapter_config:
            names.append(self.adapter_config.name)
        if self.softprompt_config:
            names.append(self.softprompt_config.name)
        if self.lora_config:
            names.append(self.lora_config.name)
        if self.embedding_head_config:
            names.append(self.embedding_head_config.name)
        return names


class TrainingConfig(BaseConfig):
    weight_decay: float = Field(1.0e-4, description="weight decay for linear weights")
    finetune: bool = Field(
        False, description="train only parameters matched by finetunable_parameters"
    )
    finetunable_parameters: List[str] = Field(
        default_factory=list,
        description="regexes of parameter names to train when finetune is set",
    )
    parameters_exclude: List[str] = Field(
        default_factory=list,
        description="regexes of parameter names to exclude from training",
    )
    use_deterministic_torch_algorithms: bool = Field(
        False, description="kept for config parity; XLA is deterministic by default"
    )
    use_separate_lr_on_embeddings: bool = Field(
        False,
        description="use embedding_learning_rate_scheduler on embedding weights",
        validation_alias=AliasChoices(
            # the misspelled alias keeps legacy reference configs loading
            # (reference: context/config.py:55-57)
            "use_separate_lr_on_embeddings", "use_seperate_lr_on_embeddings"
        ),
    )


class DataConfig(BaseConfig):
    data_prefixes: Optional[List[Path]] = Field(
        None, description="prefixes of memory-map dataset files"
    )
    blended_dataset: Optional["BlendedDatasetConfig"] = Field(
        None, description="blending over data_prefixes"
    )
    eod_token_id: int = Field(
        0, description="token id marking end-of-document in tokenized data; "
        "drives segmenting, position resets and loss masking", ge=0
    )
    validation_data_prefixes: Optional[List[Path]] = Field(None, description="")
    legacy_dataset: bool = Field(False, description="load Megatron-format .bin/.idx data")
    finetuning_dataset: bool = Field(False, description="prompt/completion jsonl data")
    finetuning_chat_dataset: bool = Field(False, description="chat jsonl data")
    finetuning_dataset_memory_map: bool = Field(False, description="")
    use_mmap: bool = Field(True, description="")
    load_mmap_index_to_memory: bool = Field(False, description="")
    load_data_item_mmap_index_to_memory: bool = Field(False, description="")
    only_full_sequences: bool = Field(False, description="")
    allow_incomplete_sequences_every_n: int = Field(0, description="", ge=0)


from ...data.blended_dataset import BlendedDatasetConfig  # noqa: E402

DataConfig.model_rebuild()


from ...profiler import ProfilerConfig  # noqa: E402


# config keys that existed in earlier releases and were removed; configs
# baked into old checkpoints still carry them, and extra="forbid" would
# otherwise refuse to load those checkpoints
REMOVED_CONFIG_KEYS = (
    ("transformer_architecture", "umup"),
    ("data", "embedding_dataset"),
    ("data", "embedding_dataset_memory_map"),
)


def strip_removed_config_keys(d: dict) -> dict:
    """Drop known-removed keys from a checkpoint-embedded config dict."""
    d = {k: (dict(v) if isinstance(v, dict) else v) for k, v in d.items()}
    for section, key in REMOVED_CONFIG_KEYS:
        sub = d.get(section)
        if isinstance(sub, dict):
            sub.pop(key, None)
    return d


class TransformerConfig(BaseConfig):
    """Composition root (reference: config.py:364-425)."""

    version: str = Field("0.1.0", description="")
    runner: Optional["RunnerConfig"] = Field(None, description="")
    logger: LoggerConfig = Field(LoggerConfig(), description="")
    topology: TopologyConfig = Field(description="")
    optimizer: OptimizerConfig = Field(OptimizerConfig(), description="")
    learning_rate_scheduler: LearningRateSchedulerConfig = Field(
        LearningRateSchedulerConfig(), description=""
    )
    embedding_learning_rate_scheduler: LearningRateSchedulerConfig = Field(
        LearningRateSchedulerConfig(), description=""
    )
    training: TrainingConfig = Field(TrainingConfig(), description="")
    trainer: TrainerConfig = Field(TrainerConfig(), description="")
    profiler: ProfilerConfig = Field(ProfilerConfig(), description="")
    transformer_architecture: TransformerArchitectureConfig = Field(description="")
    data: DataConfig = Field(DataConfig(), description="")
    determined_experiment_id: Optional[int] = Field(None, description="")
    determined_trial_id: Optional[int] = Field(None, description="")
    context: ContextConfig = Field(ContextConfig(), description="")

    @model_validator(mode="after")
    def _validate_layout(self):
        arch = self.transformer_architecture
        for name, given, why in (
                ("layer_pattern", arch.layer_pattern is not None,
                 "layers of unequal kind are"),
                ("parallel_ssm", arch.parallel_ssm,
                 "a block's Mamba-2 mixer and its recurrent lines are")):
            for axis in ("pipe_parallel_size", "model_parallel_size"):
                if given and getattr(self.topology, axis) > 1:
                    raise ValueError(
                        f"{name} with {axis} {getattr(self.topology, axis)}: "
                        f"{why} neither stage-stacked nor tensor-parallel "
                        "yet; use 1"
                    )
        for kind, layers in (("window", arch.window_layers),
                             ("window_latent", arch.window_latent_layers)):
            if layers and self.topology.context_parallel_size > 1:
                raise ValueError(
                    f"'{kind}' layers with context_parallel_size "
                    f"{self.topology.context_parallel_size}: a window over a "
                    "sequence sharded on the context axis is not built (ring "
                    "and ulysses attend over the whole sequence); use 1")
        return self

    @classmethod
    def from_dict(cls, d: dict, overwrite_values: Optional[dict] = None):
        return super().from_dict(d, overwrite_values=overwrite_values)


from ...runner.config import RunnerConfig  # noqa: E402

TransformerConfig.model_rebuild()
