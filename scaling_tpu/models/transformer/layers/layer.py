"""The transformer block.

(reference: src/scaling/transformer/model/layers/layer.py:44-291) —
pre-norm attention with residual, pre-norm MLP with residual, dropout after
each block, optional bottleneck adapters after each block; with
``sandwich_norm`` each sub-layer's output is normed once more before the
residual takes it. Dropout keys come
from the ForwardContext, which derives them deterministically per call —
that is the whole of the reference's CudaRNGStateTracker on TPU: the same
key is computed on every model-parallel shard, so masks agree by
construction (reference: rng_tracker.py:59-96).
"""

from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from ....nn import (
    BaseLayer,
    ForwardContext,
    ParallelMLP,
    ParallelSelfAttention,
    ParallelSwiGLUMLP,
    ParamMeta,
    get_norm,
    normal_init,
    tree_prefix,
)
from ....nn.attention import PagedKVCacheView
from ....nn.base_layer import multiplied
from ....nn.hyper_connection import HyperConnection
from ....nn.rotary import RotaryConfig
from ....nn.latent_attention import LatentSelfAttention
from ....nn.sparse_attention import SparseSelfAttention
from ....nn.sparse_latent_attention import SparseLatentSelfAttention
from ....nn.mamba import Mamba2Mixer
from ....nn.short_conv import GatedShortConv
from ....nn.window_attention import WindowSelfAttention
from ..config import (
    AdapterConfig,
    AttentionGate,
    KeyQueryNormScope,
    LayerKind,
    MLPType,
    RelativePositionEmbeddingType,
    TransformerArchitectureConfig,
)


class Adapter(BaseLayer):
    """Bottleneck adapter: down-proj -> gelu -> up-proj, residual outside
    (reference: layers/layer.py:140-187). Replicated params (adapters are
    small; sharding them would waste ICI)."""

    def __init__(self, hidden_size: int, downsampling_factor: float, init_std: float, dtype):
        self.hidden_size = hidden_size
        # multiplicative, matching the reference's ParallelMLP factor
        # (layer.py:152): 0.25 -> a 4x bottleneck
        self.bottleneck = max(1, int(hidden_size * downsampling_factor))
        self.init_std = init_std
        self.dtype = dtype

    def init(self, key: jax.Array) -> dict:
        k1, k2 = jax.random.split(key)
        init = normal_init(self.init_std)
        return {
            "down": init(k1, (self.hidden_size, self.bottleneck), self.dtype),
            "up": init(k2, (self.bottleneck, self.hidden_size), self.dtype),
        }

    def param_metas(self) -> dict:
        return {
            "down": ParamMeta(parameter_name="down", partition_spec=(None, None),
                              is_model_parallel_duplicate=True),
            "up": ParamMeta(parameter_name="up", partition_spec=(None, None),
                            is_model_parallel_duplicate=True),
        }

    def __call__(self, params: dict, x: jax.Array, ctx: ForwardContext) -> jax.Array:
        h = jax.nn.gelu(x @ params["down"].astype(x.dtype))
        return h @ params["up"].astype(x.dtype)


def routed_mlp(arch: TransformerArchitectureConfig) -> BaseLayer:
    """The routed MLP the configuration describes (``nn/moe.py``)."""
    from ....nn.moe import ParallelMoEMLP

    return ParallelMoEMLP(
        io_features=arch.hidden_size,
        intermediate_feature_factor=arch.mlp_factor,
        num_experts=arch.moe_num_experts,
        top_k=arch.moe_top_k,
        capacity_factor=arch.moe_capacity_factor,
        aux_loss_coef=arch.moe_aux_loss_coef,
        norm_topk_prob=arch.moe_norm_topk_prob,
        norm_topk_eps=arch.moe_norm_topk_eps,
        glu=arch.moe_glu,
        activation=arch.activation_function,
        dtype=arch.dtype,
        intermediate=arch.moe_expert_width,
        router=arch.moe_router.value,
        routed_scaling_factor=arch.moe_routed_scaling_factor,
        shared_expert_width=arch.moe_shared_expert_width,
        shared_expert_gate=arch.moe_shared_expert_gate,
        experts_first=arch.moe_experts_first,
        experts_held=arch.moe_experts_held,
        n_group=arch.moe_n_group,
        topk_group=arch.moe_topk_group,
    )


def dense_mlp(arch: TransformerArchitectureConfig, bitfit=None) -> BaseLayer:
    """The dense MLP the configuration describes (``mlp_type`` 'swiglu' or
    'default', ``mlp_factor``)."""
    if arch.mlp_type == MLPType.SWIGLU:
        return ParallelSwiGLUMLP(
            io_features=arch.hidden_size,
            intermediate_feature_factor=arch.mlp_factor,
            bias=arch.mlp_bias,
            dtype=arch.dtype,
            bitfit_bias_name=bitfit,
            gate_multiplier=arch.multipliers.mlp_gate,
            down_multiplier=arch.multipliers.mlp_down,
        )
    return ParallelMLP(
        io_features=arch.hidden_size,
        intermediate_feature_factor=arch.mlp_factor,
        activation=arch.activation_function,
        bias=arch.mlp_bias,
        dtype=arch.dtype,
        bitfit_bias_name=bitfit,
    )


def hyper_connection(arch: TransformerArchitectureConfig) -> Optional[HyperConnection]:
    """The mapping of one sub-layer of a stack of ``hc_streams`` residual
    streams (``nn/hyper_connection.py``); None for the plain residual."""
    if arch.hc_streams == 1:
        return None
    return HyperConnection(
        arch.hidden_size, arch.hc_streams, arch.hc_sinkhorn_iters, arch.hc_eps,
        (arch.hc_res_clamp_min, arch.hc_res_clamp_max),
        arch.layernorm.layernorm_epsilon)


def mamba_mixer(arch: TransformerArchitectureConfig) -> Mamba2Mixer:
    """The Mamba-2 mixer the configuration describes (``nn/mamba.py``)."""
    return Mamba2Mixer(
        hidden_size=arch.hidden_size, num_heads=arch.mamba_num_heads,
        head_dim=arch.mamba_head_dim, state_size=arch.ssm_state_size,
        n_groups=arch.n_groups, conv_kernel=arch.conv_kernel,
        norm_eps=arch.layernorm.layernorm_epsilon,
        time_step_min=arch.time_step_min,
        time_step_max=arch.time_step_max,
        time_step_floor=arch.time_step_floor, dtype=arch.dtype,
        in_multiplier=arch.multipliers.ssm_in,
        multipliers=arch.multipliers.ssm,
    )


def delta_mixer(arch: TransformerArchitectureConfig) -> BaseLayer:
    """The gated delta-rule mixer the configuration describes
    (``nn/gated_delta.py``, imported where a 'delta' layer is built: no other
    stack's set-up pays for it)."""
    from ....nn.gated_delta import GatedDeltaMixer

    return GatedDeltaMixer(
        hidden_size=arch.hidden_size,
        num_key_heads=arch.delta_num_key_heads,
        num_value_heads=arch.delta_num_value_heads,
        key_head_dim=arch.delta_key_head_dim,
        value_head_dim=arch.delta_value_head_dim,
        conv_kernel=arch.conv_kernel,
        norm_eps=arch.layernorm.layernorm_epsilon,
        time_step_min=arch.time_step_min, time_step_max=arch.time_step_max,
        time_step_floor=arch.time_step_floor, dtype=arch.dtype,
    )


def latent_sizes(arch: TransformerArchitectureConfig, prefix: str = "") -> dict:
    """What sizes a latent attention mixer, as the configuration names it: the
    'latent' kind's fields or, under ``prefix`` ``window_latent_``, that
    kind's own (its rotary takes the base frequencies, never a scaling)."""
    def size(name):
        return getattr(arch, prefix + name)

    return dict(
        hidden_size=arch.hidden_size,
        num_attention_heads=size("num_attention_heads"),
        q_lora_rank=size("q_lora_rank"),
        kv_lora_rank=size("kv_lora_rank"),
        qk_nope_head_dim=size("qk_nope_head_dim"),
        qk_rope_head_dim=size("qk_rope_head_dim"),
        v_head_dim=size("v_head_dim"),
        rotary_config=RotaryConfig(
            dimensions=size("qk_rope_head_dim"),
            base=size("rotary_embedding_base"),
            max_seq_length=arch.sequence_length,
            scaling=None if prefix else arch.rope_scaling,
        ),
        layernorm_config=arch.layernorm,
        masked_softmax_config=arch.masked_softmax,
        dtype=arch.dtype,
        output_gate=arch.attention_gate == AttentionGate.PER_HEAD,
        lora_rescale=arch.latent_lora_rescale,
    )


class MixerLayer(BaseLayer):
    """A layer of a ``layer_pattern`` stack: ONE norm, ONE mixer of the
    layer's kind, the residual: ``x <- x + Mixer(Norm(x))`` (Nemotron-H's
    block; LFM2's block, an operator then an FFN each behind its own norm, is
    two of them). With ``hc_streams > 1`` the residual is that many streams
    and the layer has a mapping of its own (``self.hc``,
    ``nn/hyper_connection.py``): the norm reads ``u``, the mapping's mix of
    the streams, and the mixer's output goes back into all of them, ``X <-
    H_res X + H_post Mixer(Norm(u))``."""

    def __init__(self, architecture: TransformerArchitectureConfig, layer_index: int = 0):
        arch = architecture
        self.architecture = arch
        self.layer_index = layer_index
        self.kind = arch.layer_pattern[layer_index]
        dtype = arch.dtype
        self.norm = get_norm(arch.norm_type, arch.hidden_size, arch.layernorm, dtype)
        self.hc = hyper_connection(arch)
        # an indexer, where the configuration has one, makes this stack's
        # latent or attention layers sparse (config.py: one kind of them)
        sparse = {} if arch.index_topk is None else dict(
            index_n_heads=arch.index_n_heads,
            index_head_dim=arch.index_head_dim,
            index_topk=arch.index_topk)
        if self.kind == LayerKind.MAMBA:
            self.mixer: BaseLayer = mamba_mixer(arch)
        elif self.kind == LayerKind.DELTA:
            self.mixer = delta_mixer(arch)
        elif self.kind == LayerKind.MOE:
            self.mixer = routed_mlp(arch)
        elif self.kind == LayerKind.CONV:
            self.mixer = GatedShortConv(arch.hidden_size, arch.conv_kernel, dtype)
        elif self.kind == LayerKind.MLP:
            self.mixer = dense_mlp(arch)
        elif self.kind == LayerKind.LATENT:
            self.mixer = (SparseLatentSelfAttention if sparse
                          else LatentSelfAttention)(
                **sparse, **latent_sizes(arch))
        elif self.kind == LayerKind.WINDOW_LATENT:
            # the latent mixer at the window_latent_* sizes, under a window,
            # no indexer; its kernel's module is imported where it is built
            from ....nn.window_latent_attention import WindowLatentSelfAttention

            self.mixer = WindowLatentSelfAttention(
                window_size=arch.window_size,
                **latent_sizes(arch, "window_latent_"))
        else:
            # softmax attention, 'attention' or 'window': a window layer has a
            # head count and a rotary of its own and takes no scaling
            window = self.kind == LayerKind.WINDOW
            heads = arch.num_attention_heads
            if window and arch.window_num_attention_heads:
                heads = arch.window_num_attention_heads
            rotary_config = None
            head_dim = arch.attention_head_dim or arch.hidden_size // heads
            if arch.relative_position_embedding_type != RelativePositionEmbeddingType.NONE:
                share, base, scaling = (
                    (arch.window_rotary_percentage,
                     arch.window_rotary_embedding_base, None) if window else
                    (arch.rotary_percentage, arch.rotary_embedding_base,
                     arch.rope_scaling))
                rotary_config = RotaryConfig(
                    dimensions=max(2, int(head_dim * share)),
                    base=base,
                    max_seq_length=arch.sequence_length,
                    scaling=scaling,
                )
            own = {} if sparse else dict(
                output_gate=arch.attention_gate == AttentionGate.PER_HEAD)
            if arch.attention_gate == AttentionGate.ELEMENTWISE:
                own["lane_gate"] = True   # config.py: plain 'attention' alone
            if window:
                own["window_size"] = arch.window_size
            self.mixer = (SparseSelfAttention if sparse
                          else WindowSelfAttention if window
                          else ParallelSelfAttention)(
                **sparse, **own,
                hidden_size=arch.hidden_size,
                num_attention_heads=heads,
                masked_softmax_config=arch.masked_softmax,
                causal=arch.causal,
                rotary_config=rotary_config,
                relative_position_embedding_type=arch.relative_position_embedding_type.value,
                bias=arch.attention_bias,
                dtype=dtype,
                norm_type=arch.norm_type,
                # per head, before rotary (config.py refuses the other scope)
                key_query_norm=arch.key_query_norm,
                layernorm_config=arch.layernorm,
                qkv_in_one=arch.attention_qkv_in_one
                and arch.attention_num_kv_heads is None,
                num_kv_heads=arch.attention_num_kv_heads,
                head_dim=arch.attention_head_dim,
            )

    @property
    def consumes(self):
        """The view of the serving state this layer's mixer keeps, as the
        mixer declares it (``STATE_VIEW``): attention's paged KV cache line,
        or a view of lines a slot (Mamba-2's, a short convolution's); None
        for the routed and the dense MLP."""
        return getattr(self.mixer, "STATE_VIEW", None)

    # a routed expert's output projection starts this much below a plain
    # branch's (init, below)
    ROUTED_OUTPUT_SCALE = 0.25

    def init(self, key: jax.Array) -> dict:
        """The mixer's own init, then the depth scaling of a residual
        branch: every mixer's OUTPUT projection starts at ``1 / (2
        sqrt(num_layers))`` of its Xavier scale (GPT-2 gives its output
        projections ``1 / sqrt(2 L)``, Mamba's ``rescale_prenorm_residual``
        ``1 / sqrt(L)``), beside an embedding at unit variance
        (layers/embedding.py; times the table's deviation where a tied table
        starts lower, ``pattern_embedding_std``: behind RMSNorms the stack is
        the same map at any common scale): the stream is then mostly the embedding and
        each branch a small step, so that fresh weights are a stable map.
        The routed experts' start a further ``ROUTED_OUTPUT_SCALE`` lower:
        with fresh weights a sigmoid router's k chosen scores are all near
        0.5 and their renormalised gates all near ``scale / k``, so a
        near-tie at the edge of the choice, which a bf16 rounding of the
        router's input breaks either way in ~7% of (token, layer), swaps
        an expert that carries a k-th of the routed output. At Xavier scale
        and an embedding of 0.005 that moved served logits by up to 0.67
        against the float32 reference (PERF.md, PR 46); a trained router's
        edge experts carry small scores.

        Where the head is TIED to the table (LFM2) the stream must NOT be
        mostly the embedding: a token's logits are then ``e_t D E^T`` with
        ``D`` the final norm's weight, a SYMMETRIC table, and greedy decoding
        over a symmetric table climbs (``B[t0, t1] <= B[t1, t2] <= ...``) to
        two tokens that are each other's best and alternates between them,
        whatever the layers compute: the comparison of served logits with
        the reference then sees two transitions a request (on the chip: a
        largest gap of 0.0000 over 944 positions, PERF.md, PR 48). So there
        the branches keep their Xavier scale relative to the table (no depth
        factor: the stream is mostly what the layers computed) and the
        routed experts start ``ROUTED_OUTPUT_SCALE ** 2`` below them, the
        near-ties' share of the stream as small as before."""
        k1, k2 = jax.random.split(key)
        mixer = self.mixer.init(k2)
        arch = self.architecture
        # relative to where the stream starts: the embedding's deviation
        scale = 0.5 * arch.num_layers ** -0.5 * arch.pattern_embedding_std
        routed = self.ROUTED_OUTPUT_SCALE
        if arch.weight_tying:
            # a head tied to the table: no depth factor (the docstring)
            scale, routed = arch.pattern_embedding_std, routed ** 2

        scaled = multiplied   # float32 product, the leaf's dtype back

        if self.kind in (LayerKind.MAMBA, LayerKind.CONV, LayerKind.DELTA):
            mixer["out_proj"]["weight"] = scaled(mixer["out_proj"]["weight"], scale)
        elif self.kind == LayerKind.MLP:
            out = "down_proj" if "down_proj" in mixer else "dense_out"
            mixer[out]["weight"] = scaled(mixer[out]["weight"], scale)
        elif self.kind == LayerKind.MOE:
            mixer["w_out"] = scaled(mixer["w_out"], scale * routed)
            if "shared_out" in mixer:
                mixer["shared_out"] = scaled(mixer["shared_out"], scale)
        else:
            mixer["dense"]["weight"] = scaled(mixer["dense"]["weight"], scale)
        params = {"norm": self.norm.init(k1), "mixer": mixer}
        if self.hc is not None:
            # a key of its own: norm's and mixer's stay what they were
            params["hc"] = self.hc.init(jax.random.fold_in(key, 2))
        return params

    def param_metas(self) -> dict:
        metas = {"norm": tree_prefix(self.norm.param_metas(), "norm"),
                 "mixer": tree_prefix(self.mixer.param_metas(), "mixer")}
        if self.hc is not None:
            metas["hc"] = tree_prefix(self.hc.param_metas(), "hc")
        return metas

    def __call__(self, params: dict, x: dict, ctx: ForwardContext,
                 kv_cache=None, cache_offset=None, return_kv: bool = False,
                 real=None):
        """``kv_cache``: the serving state of this layer's kind, the view its
        mixer declares (``consumes``; attention's may be a dense ``(k, v)``
        too); with it or ``return_kv`` the result is ``(out, new state)``:
        attention's K/V or updated view, a per-slot kind's final lines or
        updated view. ``real`` ((b, s) bool): the positions that hold a
        token, for the routed MLP's load count when serving."""
        h = x["activations"]
        mix = None
        if self.hc is None:
            normed = self.norm(params["norm"], h, ctx)
        else:
            u, mix = self.hc.pre(params["hc"], h)
            normed = self.norm(params["norm"], u, ctx)
        out = dict(x)
        state = None
        tie_breaks = ()
        view = self.consumes
        if hasattr(view, "LINES"):  # a mixer that keeps lines a slot
            if kv_cache is not None and not isinstance(kv_cache, view):
                raise ValueError(
                    f"a {self.kind.value} layer takes a {view.__name__} (the "
                    "serving engine's state pool), not a KV cache: cached "
                    "generate() is not built for a layer_pattern stack; use "
                    "use_cache=False or ServeEngine")
            if self.kind in (LayerKind.WINDOW, LayerKind.WINDOW_LATENT):
                # attention all the same: its rotary and its mask are the
                # positions' and the segments'
                with jax.named_scope(f"{self.kind.value}_attn"):
                    y = self.mixer(
                        params["mixer"], normed, ctx,
                        segment_ids=x["segment_ids"],
                        position_ids=x["position_ids"],
                        state=kv_cache, return_state=return_kv)
            else:
                y = self.mixer(params["mixer"], normed, ctx, state=kv_cache,
                               return_state=return_kv)
            if return_kv or kv_cache is not None:
                y, state = y
        elif self.kind == LayerKind.MOE:
            if ctx.serving:
                y, load = self.mixer.serve(
                    params["mixer"], normed, real, ctx.mesh)
                if load is not None:
                    out["moe_load"] = x.get("moe_load", 0) + load
            else:
                y, aux = self.mixer(params["mixer"], normed, ctx)
                out["aux_loss"] = x.get("aux_loss", 0.0) + aux
        elif self.kind == LayerKind.MLP:
            with jax.named_scope("mlp"):
                y = self.mixer(params["mixer"], normed, ctx)
        elif self.kind == LayerKind.LATENT:
            with jax.named_scope("attn"):
                y = self.mixer(
                    params["mixer"], normed, ctx,
                    segment_ids=x["segment_ids"],
                    position_ids=x["position_ids"],
                    kv_cache=kv_cache, return_kv=return_kv,
                )
            if return_kv or kv_cache is not None:
                y, state, *tie_breaks = y
        else:
            # a sparse mixer's operations carry the scope its metrics read, as
            # the latent mixers' do; a plain one's never had it
            # (nor does one beside window layers: ``full_attn`` there, so that
            # each kind's share of a tick can be read)
            sparse = isinstance(self.mixer, SparseSelfAttention)
            # (nor does one beside delta layers: ``gated_attn`` there)
            arch = self.architecture
            scope = ("attn" if sparse else
                     "full_attn" if arch.window_layers else
                     "gated_attn" if arch.delta_layers else None)
            with jax.named_scope(scope) if scope else contextlib.nullcontext():
                y = self.mixer(
                    params["mixer"], normed, ctx,
                    segment_ids=x["segment_ids"], position_ids=x["position_ids"],
                    kv_cache=kv_cache, cache_offset=cache_offset,
                    return_kv=return_kv,
                )
            if return_kv or kv_cache is not None:
                y, state, *tie_breaks = y
        if tie_breaks:
            # a sparse mixer's row walk over the engine's view: its calls that
            # filled ties by position, summed over the layers
            out["sparse_tie_breaks"] = (
                x.get("sparse_tie_breaks", 0) + tie_breaks[0])
        if mix is None:
            out["activations"] = h + y.astype(h.dtype)
        else:
            out["activations"] = self.hc.post(h, y, mix)
        if view is not None and (return_kv or kv_cache is not None):
            return out, state
        return out


class TransformerLayer(BaseLayer):
    """Attention behind a norm, the residual; an MLP behind a norm, the
    residual. With ``parallel_ssm`` a Mamba-2 mixer runs BESIDE the attention
    on the same normed input and the two are summed into the one residual
    (Falcon-H1's block): ``x <- x + s_out SSM(N(x)) + a_out Attn(a_in N(x))``,
    the pattern's two-mixer case. Such a layer keeps state under BOTH rules of
    the serving pool, a paged KV line and a recurrent line a slot
    (``consumes`` is then the two views, attention's first), is handed the
    pair and gives the pair back."""

    def __init__(self, architecture: TransformerArchitectureConfig, layer_index: int = 0):
        arch = architecture
        self.architecture = arch
        self.layer_index = layer_index
        dtype = arch.dtype
        bitfit = arch.bitfit_bias_config.name if arch.bitfit_bias_config else None

        self.input_layernorm = get_norm(
            arch.norm_type, arch.hidden_size, arch.layernorm, dtype, bitfit
        )
        rotary_config = None
        if arch.relative_position_embedding_type != RelativePositionEmbeddingType.NONE:
            # a head size of its own only beside parallel_ssm (config.py)
            head_dim = (arch.attention_head_dim
                        or arch.hidden_size // arch.num_attention_heads)
            rotary_config = RotaryConfig(
                dimensions=max(2, int(head_dim * arch.rotary_percentage)),
                base=arch.rotary_embedding_base,
                max_seq_length=arch.sequence_length,
            )
        mup_attention_scale = None
        if arch.mup is not None:
            # muP rule: attention logits scale 1/d beyond the base width —
            # sqrt(base_head_dim)/head_dim equals 1/sqrt(head_dim) at the
            # base model and decays like 1/head_dim past it. base_head_dim
            # comes from the base model's own head count: width grown by
            # adding heads keeps head_dim (and this scale) constant
            head_dim = arch.hidden_size // arch.num_attention_heads
            base_heads = (
                arch.mup.base_num_attention_heads or arch.num_attention_heads
            )
            base_head_dim = arch.mup.base_hidden_size / base_heads
            mup_attention_scale = (base_head_dim**0.5) / head_dim
        self.attention = ParallelSelfAttention(
            hidden_size=arch.hidden_size,
            num_attention_heads=arch.num_attention_heads,
            scaling_factor=mup_attention_scale,
            masked_softmax_config=arch.masked_softmax,
            causal=arch.causal,
            num_local_attention_heads=arch.num_local_attention_heads,
            local_attention_window_size=arch.local_attention_window_size,
            dropout_attention_probs=arch.dropout_attention_probs,
            rotary_config=rotary_config,
            relative_position_embedding_type=arch.relative_position_embedding_type.value,
            bias=arch.attention_bias,
            dtype=dtype,
            bitfit_bias_name=bitfit,
            lora_config=arch.lora_config,
            norm_type=arch.norm_type,
            key_query_norm=arch.key_query_norm,
            key_query_norm_over_projection=(
                arch.key_query_norm_scope == KeyQueryNormScope.PROJECTION
            ),
            layernorm_config=arch.layernorm,
            qkv_in_one=arch.attention_qkv_in_one
            and arch.attention_num_kv_heads is None,
            num_kv_heads=arch.attention_num_kv_heads,
            head_dim=arch.attention_head_dim,
            key_multiplier=arch.multipliers.key,
        )
        # the second mixer of a parallel block, on the attention's input
        self.ssm: Optional[Mamba2Mixer] = (
            mamba_mixer(arch) if arch.parallel_ssm else None)
        self.post_attention_layernorm = get_norm(
            arch.norm_type, arch.hidden_size, arch.layernorm, dtype, bitfit
        )
        # sandwich norms: each sub-layer's OUTPUT is normed before it is
        # added to the residual stream (two further norms a layer)
        self.output_norms = {}
        if arch.sandwich_norm:
            self.output_norms = {
                name: get_norm(
                    arch.norm_type, arch.hidden_size, arch.layernorm, dtype, bitfit
                )
                for name in ("post_attention_output_layernorm",
                             "post_mlp_output_layernorm")
            }
        self.is_moe = arch.mlp_type == MLPType.MOE
        if self.is_moe:
            self.mlp: BaseLayer = routed_mlp(arch)
        else:
            self.mlp = dense_mlp(arch, bitfit)

        self.adapter_attention: Optional[Adapter] = None
        self.adapter_mlp: Optional[Adapter] = None
        self.adapter_name = None
        if arch.adapter_config is not None:
            cfg: AdapterConfig = arch.adapter_config
            self.adapter_name = cfg.name
            if cfg.attention_downsampling_factor:
                self.adapter_attention = Adapter(
                    arch.hidden_size, cfg.attention_downsampling_factor, cfg.init_std, dtype
                )
            if cfg.mlp_downsampling_factor:
                self.adapter_mlp = Adapter(
                    arch.hidden_size, cfg.mlp_downsampling_factor, cfg.init_std, dtype
                )

    @property
    def consumes(self):
        """What a walk of the stack reads off a trunk layer: the view of the
        serving state it keeps, attention's; of a parallel block the two
        mixers' views, attention's first (``nn.base_layer.state_views``)."""
        if self.ssm is None:
            return self.attention.STATE_VIEW
        return (self.attention.STATE_VIEW, self.ssm.STATE_VIEW)

    # ------------------------------------------------------------------ init
    def init(self, key: jax.Array) -> dict:
        keys = jax.random.split(key, 6)
        params = {
            "input_layernorm": self.input_layernorm.init(keys[0]),
            "attention": self.attention.init(keys[1]),
            "post_attention_layernorm": self.post_attention_layernorm.init(keys[2]),
            "mlp": self.mlp.init(keys[3]),
        }
        mult = self.architecture.multipliers
        # a matrix that a published multiplier follows starts at its usual
        # scale over that multiplier (the mixers do their own: the keys', the
        # MLP's two, in_proj's columns)
        dense = params["attention"]["dense"]
        dense["weight"] = multiplied(dense["weight"], 1.0 / mult.attention_out)
        if self.ssm is not None:
            params["ssm"] = self.ssm.init(jax.random.fold_in(key, 8))
            out = params["ssm"]["out_proj"]
            out["weight"] = multiplied(out["weight"], 1.0 / mult.ssm_out)
            # the depth scaling of a residual branch, as MixerLayer.init has
            # it, beside an embedding at unit variance: three branches a block
            scale = 0.5 * self.architecture.num_layers ** -0.5
            mlp_out = params["mlp"]["down_proj" if "down_proj" in params["mlp"]
                                    else "dense_out"]
            for branch in (dense, out, mlp_out):
                branch["weight"] = multiplied(branch["weight"], scale)
        for i, (name, norm) in enumerate(self.output_norms.items()):
            # keys of their own: the six above stay what they were
            params[name] = norm.init(jax.random.fold_in(key, 6 + i))
            # a norm of ones would add 2 x num_layers sub-layer outputs, each
            # as large as the stream it joins, every time the trunk is
            # walked: the depth scaling of a residual branch (GPT-2 gives its
            # output projections 1 / sqrt(2 L)) starts the branches' sum at
            # the size of the walk's input. At ones, fresh weights are a
            # chaotic map: on the chip the bf16 roundings of 192 layer
            # applications moved the logits by 0.4-0.6 (PERF.md, PR 40)
            scale = (2 * self.architecture.num_layers) ** -0.5
            weight = params[name]["weight"]
            params[name]["weight"] = (weight * scale).astype(weight.dtype)
        if self.adapter_attention is not None:
            params[f"adapter_attention_{self.adapter_name}"] = self.adapter_attention.init(keys[4])
        if self.adapter_mlp is not None:
            params[f"adapter_mlp_{self.adapter_name}"] = self.adapter_mlp.init(keys[5])
        return params

    def param_metas(self) -> dict:
        metas = {
            "input_layernorm": tree_prefix(self.input_layernorm.param_metas(), "input_layernorm"),
            "attention": tree_prefix(self.attention.param_metas(), "attention"),
            "post_attention_layernorm": tree_prefix(
                self.post_attention_layernorm.param_metas(), "post_attention_layernorm"
            ),
            "mlp": tree_prefix(self.mlp.param_metas(), "mlp"),
        }
        if self.ssm is not None:
            metas["ssm"] = tree_prefix(self.ssm.param_metas(), "ssm")
        for name, norm in self.output_norms.items():
            metas[name] = tree_prefix(norm.param_metas(), name)
        if self.adapter_attention is not None:
            name = f"adapter_attention_{self.adapter_name}"
            metas[name] = tree_prefix(self.adapter_attention.param_metas(), name)
        if self.adapter_mlp is not None:
            name = f"adapter_mlp_{self.adapter_name}"
            metas[name] = tree_prefix(self.adapter_mlp.param_metas(), name)
        return metas

    # ----------------------------------------------------------------- merge
    def merge_lora_weights(self, params: dict) -> dict:
        """Fold the attention block's LoRA deltas into its base weights."""
        params = dict(params)
        params["attention"] = self.attention.merge_lora_weights(params["attention"])
        return params

    # ----------------------------------------------------- token slicing
    def init_token_slice_cache(self, params: dict, x: dict,
                               ctx: ForwardContext, capacity: int):
        """Zeroed per-layer KV(+segment-id) cache for TeraPipe token
        slicing (parallel/pipeline.py): k/v buffers at full-sequence
        ``capacity`` on the slot axis, plus the cached slots' segment ids
        so the sliced attention keeps packed-document masking. The shapes
        come from an abstract probe of this layer on one slice, so GQA /
        head-dim / dtype choices never drift from the real attention."""
        import dataclasses as _dc

        probe_ctx = _dc.replace(ctx, dropout_key=None, deterministic=True)

        def probe(p, xx):
            return self(p, xx, probe_ctx, return_kv=True)[1]

        k, v = jax.eval_shape(probe, params, x)

        def grow(aval):
            return jnp.zeros(
                (aval.shape[0], capacity) + aval.shape[2:], aval.dtype
            )

        seg = jnp.zeros((k.shape[0], capacity), jnp.int32)
        return (grow(k), grow(v), seg)

    # --------------------------------------------------------------- forward
    def __call__(self, params: dict, x: dict, ctx: ForwardContext,
                 kv_cache=None, cache_offset=None, return_kv: bool = False):
        arch = self.architecture
        mult = arch.multipliers
        h = x["activations"]

        normed = self.input_layernorm(params["input_layernorm"], h, ctx)
        lines = None
        if self.ssm is not None and kv_cache is not None:
            # a parallel block's state: (attention's, the Mamba-2 mixer's)
            kv_cache, lines = kv_cache
            if not isinstance(lines, self.ssm.STATE_VIEW):
                raise ValueError(
                    "a parallel block takes a PagedKVCacheView and a "
                    "RecurrentStateView (the serving engine's state), not "
                    "dense caches: cached generate() is not built for "
                    "parallel_ssm; use use_cache=False or ServeEngine")
        with jax.named_scope("attn"):
            attn = self.attention(
                params["attention"],
                multiplied(normed, mult.attention_in),
                ctx,
                segment_ids=x["segment_ids"],
                position_ids=x["position_ids"],
                kv_cache=kv_cache,
                cache_offset=cache_offset,
                attention_scores_manipulation=x.get("attention_scores_manipulation"),
                # a STATIC python bool (threaded by inference.logits at trace
                # time); never a traced leaf
                attention_scores_manipulation_log_additive=x.get(
                    "attention_scores_manipulation_log_additive", True
                ),
                return_kv=return_kv,
            )
        new_kv = None
        if return_kv or kv_cache is not None:
            attn, new_kv = attn
        attn = multiplied(attn, mult.attention_out)
        if self.ssm is not None:
            y = self.ssm(params["ssm"], normed, ctx, state=lines,
                         return_state=return_kv)
            if new_kv is not None:
                y, new_lines = y
                new_kv = (new_kv, new_lines)
            attn = attn + multiplied(y, mult.ssm_out).astype(attn.dtype)
        attn = ctx.dropout(attn, arch.dropout_after_attention)
        if self.adapter_attention is not None:
            attn = attn + self.adapter_attention(
                params[f"adapter_attention_{self.adapter_name}"], attn, ctx
            )
        if self.output_norms:
            attn = self.output_norms["post_attention_output_layernorm"](
                params["post_attention_output_layernorm"], attn, ctx)
        h = h + attn.astype(h.dtype)

        normed = self.post_attention_layernorm(params["post_attention_layernorm"], h, ctx)
        aux_loss = moe_load = None
        if self.is_moe and ctx.serving:
            # nothing dropped, no auxiliary loss; on the engine's paged
            # path the row's real positions are known and their
            # assignments are counted (nn/moe.py, "Serving")
            real = None
            if isinstance(kv_cache, PagedKVCacheView):
                real = kv_cache.token_rows(h.shape[:2])[2]
            mlp_out, moe_load = self.mlp.serve(
                params["mlp"], normed, real, ctx.mesh)
        elif self.is_moe:
            mlp_out, aux_loss = self.mlp(params["mlp"], normed, ctx)
        else:
            with jax.named_scope("mlp"):
                mlp_out = self.mlp(params["mlp"], normed, ctx)
        mlp_out = ctx.dropout(mlp_out, arch.dropout_after_mlp)
        if self.adapter_mlp is not None:
            mlp_out = mlp_out + self.adapter_mlp(
                params[f"adapter_mlp_{self.adapter_name}"], mlp_out, ctx
            )
        if self.output_norms:
            mlp_out = self.output_norms["post_mlp_output_layernorm"](
                params["post_mlp_output_layernorm"], mlp_out, ctx)
        h = h + mlp_out.astype(h.dtype)

        out = dict(x)
        out["activations"] = h
        if aux_loss is not None:
            # router load-balance loss rides the IO dict to the loss function
            out["aux_loss"] = x.get("aux_loss", 0.0) + aux_loss
        if moe_load is not None:
            # (E,) assignments of real positions, summed over the layers
            out["moe_load"] = x.get("moe_load", 0) + moe_load
        if new_kv is not None:
            return out, new_kv
        return out
