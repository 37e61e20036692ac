"""Transformer model assembly.

(reference: src/scaling/transformer/model/model.py:43-408) — layer-spec
list, loss, parameter groups, init_model/init_optimizer. The reference's
``TransformerParallelModule`` subclass exists only to strip non-tensor
fields around pipe sends (model.py:96-119); under jit the IO dict is a
static-treedef pytree, so the plain ParallelModule works as-is.
"""

from __future__ import annotations

import re
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ...nn import LayerSpec, ParamMeta, PipelineBodySpec, TiedLayerSpec
from ...optimizer import Optimizer, OptimizerParamGroup
from ...parallel.parallel_module import ParallelModule
from ...topology import Topology
from .config import TransformerConfig, TransformerArchitectureConfig
from .layers.embedding import EmbeddingInput
from .layers.layer import MixerLayer, TransformerLayer
from .layers.lm_head import (
    LayerNormWrapper,
    LoopExitGate,
    TransformerEmbeddingHead,
    TransformerLMHead,
    TransformerLMHeadTied,
)

TIED_KEY = "embedding_lm_head"


def get_transformer_layer_specs(
    architecture: TransformerArchitectureConfig,
    topology: Optional[Topology] = None,
) -> List[LayerSpec]:
    """EmbeddingInput -> N x TransformerLayer -> final norm -> LM head
    [-> embedding head] (reference: model.py:122-216).

    A looped model (``loop_steps > 1``) has the same list, the trunk's layers
    ONCE: the steps share one set of parameters. Its exit gate's parameters
    lie after the final norm. Who walks the list runs trunk and final norm
    ``loop_steps`` times (inference.py ``_run_layers``).

    With pipe_parallel_size > 1 the homogeneous TransformerLayer run becomes
    one PipelineBodySpec executed as a stage-stacked spatial pipeline; edge
    layers stay replicated over the pipe axis."""
    has_embedding_head = architecture.embedding_head_config is not None
    if architecture.weight_tying:
        specs: List[LayerSpec] = [
            TiedLayerSpec(
                EmbeddingInput,
                architecture,
                key=TIED_KEY,
                tied_weight_attributes=["embedding.weight"],
            )
        ]
    else:
        specs = [LayerSpec(EmbeddingInput, architecture)]

    pp = topology.pipe_parallel_size if topology is not None else 1
    if pp > 1 and architecture.loop_steps > 1:
        raise ValueError(
            f"pipe_parallel_size {pp} with loop_steps "
            f"{architecture.loop_steps}: a looped trunk is not pipelined "
            "(every stage would be revisited each step); use "
            "pipe_parallel_size 1"
        )
    if architecture.layer_pattern is not None:
        # a kind a layer: one norm + one mixer each (TransformerConfig refuses
        # the pattern under pp > 1 or mp > 1)
        if pp > 1:
            raise ValueError(
                f"pipe_parallel_size {pp} with layer_pattern: layers of "
                "unequal kind are not stage-stacked; use pipe_parallel_size 1"
            )
        for layer_index in range(architecture.num_layers):
            specs.append(LayerSpec(MixerLayer, architecture, layer_index))
    elif pp > 1:
        if architecture.parallel_ssm:
            raise ValueError(
                f"pipe_parallel_size {pp} with parallel_ssm: a block's "
                "recurrent lines are not stage-stacked; use "
                "pipe_parallel_size 1")
        specs.append(
            PipelineBodySpec(TransformerLayer, architecture.num_layers, architecture)
        )
    else:
        for layer_index in range(architecture.num_layers):
            specs.append(LayerSpec(TransformerLayer, architecture, layer_index))

    specs.append(
        LayerSpec(LayerNormWrapper, architecture, record_embeddings=has_embedding_head)
    )
    if architecture.loop_exit_gate:
        specs.append(LayerSpec(LoopExitGate, architecture))

    if architecture.weight_tying:
        specs.append(
            TiedLayerSpec(
                TransformerLMHeadTied,
                architecture,
                key=TIED_KEY,
                tied_weight_attributes=["embedding.weight"],
            )
        )
    else:
        specs.append(LayerSpec(TransformerLMHead, architecture))

    if has_embedding_head:
        specs.append(LayerSpec(TransformerEmbeddingHead, architecture))
    return specs


def per_token_loss(logits, targets):
    """(token cross-entropy, correct-prediction flags) in fp32 — the one
    definition both the training loss and the standalone evaluator reduce
    (they differ only in mean-vs-sum aggregation).

    The cross entropy goes through the memory-lean custom VJP
    (ops/cross_entropy.py): same fp32 forward math, but no fp32
    ``(b, s, vocab)`` log-softmax residual held to the backward — ~2 GB
    less live memory at the bench shape, measured via compiled buffer
    assignment.

    Under tensor parallelism ``logits`` arrive ``(data, seq, model)`` from
    ``TransformerLMHead`` and stay so: the loss indexes nothing along the
    vocabulary, and the argmax is a reduction GSPMD splits into a shard's
    own (maximum, column) and a pick among the ``mp`` of them."""
    from ...ops.cross_entropy import cross_entropy_from_logits

    targets = targets.astype(jnp.int32)
    token_loss = cross_entropy_from_logits(logits, targets)
    # argmax is monotonic under the fp32 upcast, so comparing on the raw
    # logits keeps the old fp32-argmax semantics
    correct = (logits.argmax(-1) == targets).astype(jnp.float32)
    return token_loss, correct


def loss_function(output: Dict[str, Any], batch: Dict[str, Any]):
    """Cross entropy with per-token loss weights + accuracy
    (reference: model.py:43-76)."""
    targets = batch["target_token_ids"]
    loss_weights = batch.get("loss_weights")
    if loss_weights is None:
        loss_weights = jnp.ones(targets.shape, dtype=jnp.float32)
    loss_weights = loss_weights.astype(jnp.float32)

    token_loss, correct = per_token_loss(output["activations"], targets)
    denom = jnp.maximum(loss_weights.sum(), 1.0)
    loss = (token_loss * loss_weights).sum() / denom
    accuracy = (correct * loss_weights).sum() / denom
    metrics = {"accuracy": accuracy}
    aux = output.get("aux_loss")
    if aux is not None:
        # MoE load-balance term (already coefficient-scaled by the layers)
        aux = jnp.asarray(aux, jnp.float32).mean()
        loss = loss + aux
        metrics["moe_aux_loss"] = aux
    return loss, metrics


def metrics_aggregation_fn(metrics_list: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Mean over collected step metrics (reference: model.py:79-93; the DP
    mean happens inside the jitted step on TPU)."""
    if not metrics_list:
        return {}
    keys = metrics_list[0].keys()
    return {k: float(sum(m[k] for m in metrics_list) / len(metrics_list)) for k in keys}


NO_WEIGHT_DECAY_SUBSTRINGS = ("norm", "bias")


def get_parameter_groups(
    config: TransformerConfig, module: ParallelModule
) -> List[OptimizerParamGroup]:
    """weight-decay / no-decay / embedding groups + finetune filtering
    (reference: model.py:238-386)."""
    training = config.training
    metas = [
        m
        for m in jax.tree.leaves(
            module.param_metas(), is_leaf=lambda x: isinstance(x, ParamMeta)
        )
    ]

    include_patterns = [re.compile(p) for p in training.finetunable_parameters]
    exclude_patterns = [re.compile(p) for p in training.parameters_exclude]
    peft_names = config.transformer_architecture.peft_names

    def trainable(meta: ParamMeta) -> bool:
        name = meta.key
        if exclude_patterns and any(p.search(name) for p in exclude_patterns):
            return False
        if training.finetune:
            if any(p.search(name) for p in include_patterns):
                return True
            # PEFT params are always trainable in finetune mode
            # (reference: config.py:426-459 auto-separates them). Match the
            # naming convention `..._{name}.` / `...bias_{name}` exactly —
            # a bare substring test would let a short PEFT name like "ad"
            # claim unrelated params ("lm_head")
            return any(
                re.search(rf"(_|bias_){re.escape(n)}(\.|$)", name) for n in peft_names
            )
        return True

    decay_keys, no_decay_keys, embedding_keys = set(), set(), set()
    for meta in metas:
        if not trainable(meta):
            continue
        if (
            training.use_separate_lr_on_embeddings
            and meta.lr_group == "embedding"
        ):
            embedding_keys.add(meta.key)
        elif meta.no_weight_decay or any(
            s in meta.parameter_name.lower() for s in NO_WEIGHT_DECAY_SUBSTRINGS
        ) or meta.lr_group == "embedding":
            no_decay_keys.add(meta.key)
        else:
            decay_keys.add(meta.key)

    # muP (Adam rule): LR scales by 1/width-mult for matrices whose FAN-IN
    # grows with hidden_size — qkv/dense/mlp/expert weights, the readout,
    # adapter down-projections, lora_a, the first embedding-head
    # projection. Everything width-independent keeps the base LR: vectors,
    # the input-like embedding table and softprompts (in whichever decay
    # set they already lived — muP must not change decay membership),
    # adapter up, lora_b, later embedding-head projections, the whole
    # image encoder — their update scale never grew with width, so
    # shrinking it has no muP justification.
    mup_mult = config.transformer_architecture.mup_width_mult

    def fan_in_scales_with_width(meta: ParamMeta) -> bool:
        if len(meta.partition_spec) < 2:
            return False  # vectors (norms, biases)
        name = meta.parameter_name
        if meta.lr_group == "embedding" or "softprompt" in name:
            return False  # input-like: fan_in is vocab / prompt slots
        if "image_encoder" in name:
            return False
        if name.endswith(".up") or "lora_b" in name:
            return False
        m = re.search(r"proj_(\d+)_", name)
        if m:
            return int(m.group(1)) == 0
        return True

    if mup_mult == 1.0:
        group_spec = (
            (decay_keys, training.weight_decay, "weight_decay_params", 1.0),
            (no_decay_keys, 0.0, "no_weight_decay_params", 1.0),
        )
    else:
        by_key = {meta.key: meta for meta in metas}

        def split(keys: set) -> tuple[set, set]:
            scaled = {k for k in keys if fan_in_scales_with_width(by_key[k])}
            return scaled, keys - scaled

        decay_scaled, decay_fixed = split(decay_keys)
        no_decay_scaled, no_decay_fixed = split(no_decay_keys)
        group_spec = (
            (decay_scaled, training.weight_decay, "weight_decay_params",
             1.0 / mup_mult),
            (decay_fixed, training.weight_decay,
             "weight_decay_params_fixed_width", 1.0),
            (no_decay_scaled, 0.0, "no_weight_decay_params_width_scaled",
             1.0 / mup_mult),
            (no_decay_fixed, 0.0, "no_weight_decay_params", 1.0),
        )

    groups = []
    for keys, wd, name, lr_scale in group_spec:
        if keys:
            groups.append(
                OptimizerParamGroup(
                    keys=keys,
                    weight_decay=wd,
                    learning_rate_scheduler=config.learning_rate_scheduler,
                    name=name,
                    lr_scale=lr_scale,
                )
            )
    if embedding_keys:
        groups.append(
            OptimizerParamGroup(
                keys=embedding_keys,
                weight_decay=0.0,
                learning_rate_scheduler=config.embedding_learning_rate_scheduler,
                name="embedding_params",
            )
        )
    if not groups:
        raise ValueError("no trainable parameters selected")
    return groups


LOOPED_TRAINING_REFUSAL = (
    "a looped model (loop_steps > 1) is served, not trained: its objective "
    "(per-step losses weighted by the exit distribution, an entropy term) is "
    "not in the configuration, and the plain walk of the layer list would run "
    "the trunk once; run it through TransformerInferenceModule / ServeEngine"
)


PATTERN_TRAINING_REFUSAL = (
    "a layer_pattern stack is served, not trained: the chunked scan of the "
    "Mamba-2 mixer has no memory-lean backward, the routed layers' load "
    "balance has no objective in the configuration, and the optimizer's "
    "groups do not know the mixers' float32 leaves; run it through "
    "TransformerInferenceModule / ServeEngine"
)


PARALLEL_SSM_TRAINING_REFUSAL = (
    "a parallel_ssm stack is served, not trained: the chunked scan of the "
    "Mamba-2 mixer beside the attention has no memory-lean backward and the "
    "optimizer's groups do not know the mixer's float32 leaves; run it "
    "through TransformerInferenceModule / ServeEngine"
)


def init_model(config: TransformerConfig, topology: Optional[Topology] = None) -> ParallelModule:
    architecture = config.transformer_architecture
    specs = get_transformer_layer_specs(architecture, topology)
    refusal = None
    if architecture.loop_steps > 1:
        refusal = LOOPED_TRAINING_REFUSAL
    elif architecture.layer_pattern is not None:
        refusal = PATTERN_TRAINING_REFUSAL
    elif architecture.parallel_ssm:
        refusal = PARALLEL_SSM_TRAINING_REFUSAL
    return ParallelModule(
        specs,
        topology=topology,
        compute_dtype=architecture.dtype,
        forward_refusal=refusal,
    )


def init_optimizer(
    config: TransformerConfig, module: ParallelModule, topology: Optional[Topology] = None
) -> Optimizer:
    groups = get_parameter_groups(config, module)
    return Optimizer(config.optimizer, groups, module.param_metas(), topology=topology)
