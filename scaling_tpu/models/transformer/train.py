"""Transformer training entry.

(reference: src/scaling/transformer/train.py:80-304) — config -> topology
-> context -> model -> optimizer -> datasets -> trainer.run_training, with
the per-step TFLOPs/MFU instrumentation riding on the trainer's metric hook.
Runnable per host: ``python -m scaling_tpu.models.transformer.train
--payload=<b64 config>`` or programmatically via ``main(config)``.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax

from ...data.blended_dataset import BlendedDatasetConfig
from ...logging import logger
from ...runner import LaunchConfig, initialize_distributed
from ...topology import Topology
from ...trainer import BaseTrainer
from .config import TransformerConfig
from .context import TransformerContext
from .data.finetuning import (
    FinetuningChatBlendedDataset,
    FinetuningChatDataset,
    FinetuningTextBlendedDataset,
    FinetuningTextDataset,
)
from .data.text_dataset import LegacyBlendedDataset, TextBlendedDataset, TextDataset
from .model import init_model, init_optimizer, loss_function
from .utils.get_tflops import (
    detect_hardware,
    get_flops_per_token,
    get_model_parameter_count,
    get_palm_mfu,
    get_tflops_aleph_alpha,
    get_tflops_bloom,
    get_tflops_electra,
    get_tflops_megatron,
)


def batch_to_model_input(batch) -> dict:
    return batch.as_model_input()


def log_metrics_fn(trainer: BaseTrainer, output, metrics: dict) -> dict:
    """Adds tokens/s, the 4 TFLOPs estimators and PaLM MFU
    (reference: train.py:80-136)."""
    config: TransformerConfig = trainer.context.config
    arch = config.transformer_architecture
    topo = trainer.topology.config
    step_time = output.step_duration or 1e-9
    tokens = topo.global_batch_size * arch.sequence_length
    glu = arch.mlp_type.value == "swiglu"
    param_count = get_model_parameter_count(
        arch.hidden_size, arch.num_layers, arch.vocab_size, arch.mlp_factor, glu
    )
    metrics["tokens_per_second"] = tokens / step_time
    metrics["tflops_megatron"] = get_tflops_megatron(
        param_count, step_time, topo.global_batch_size, arch.sequence_length
    )
    metrics["tflops_bloom"] = get_tflops_bloom(
        arch.hidden_size, arch.num_layers, arch.vocab_size, step_time,
        topo.global_batch_size, arch.sequence_length,
        activation_checkpointing=topo.activation_checkpointing_type.value != "disabled",
    )
    metrics["tflops_electra"] = get_tflops_electra(
        arch.hidden_size, arch.num_layers, arch.num_attention_heads,
        arch.vocab_size, arch.sequence_length, step_time,
        topo.global_batch_size, arch.mlp_factor,
    )
    metrics["tflops_aleph_alpha"] = get_tflops_aleph_alpha(
        arch.hidden_size, arch.num_layers, arch.num_attention_heads,
        arch.vocab_size, arch.sequence_length, step_time,
        topo.global_batch_size, arch.mlp_factor,
    )
    hardware = detect_hardware()
    if hardware is not None:
        metrics["palm_mfu"] = get_palm_mfu(
            param_count, arch.num_layers, arch.hidden_size, arch.sequence_length,
            metrics["tokens_per_second"], topo.world_size, hardware=hardware,
        )
    return metrics


def _read_dataset(config: TransformerConfig, prefixes: Optional[List[Any]]):
    if not prefixes:
        return None
    arch = config.transformer_architecture
    data = config.data
    if data.finetuning_dataset or data.finetuning_chat_dataset:
        if arch.vocab_file is None:
            raise ValueError("finetuning datasets need transformer_architecture.vocab_file")
        if data.finetuning_chat_dataset:
            softprompt_chat = arch.softprompt_config
            datasets: List[Any] = [
                FinetuningChatDataset(
                    data_prefix=p,
                    sequence_length=arch.sequence_length,
                    vocab_file=arch.vocab_file,
                    seed=config.trainer.seed,
                    softprompt_n_tokens=(
                        softprompt_chat.n_tokens if softprompt_chat else 0
                    ),
                )
                for p in prefixes
            ]
            blended_cls: Any = FinetuningChatBlendedDataset
        else:
            softprompt = arch.softprompt_config
            datasets = [
                FinetuningTextDataset(
                    data_prefix=p,
                    sequence_length=arch.sequence_length,
                    vocab_file=arch.vocab_file,
                    seed=config.trainer.seed,
                    memory_map_dataset=data.finetuning_dataset_memory_map,
                    softprompt_n_tokens=softprompt.n_tokens if softprompt else 0,
                )
                for p in prefixes
            ]
            blended_cls = FinetuningTextBlendedDataset
    else:
        datasets = [
            TextDataset(
                data_prefix=p,
                sequence_length=arch.sequence_length,
                seed=config.trainer.seed,
                eod_token_id=data.eod_token_id,
                only_full_sequences=data.only_full_sequences,
                allow_incomplete_sequences_every_n=data.allow_incomplete_sequences_every_n,
                load_index_to_memory=data.load_mmap_index_to_memory,
                legacy_dataset=data.legacy_dataset,
            )
            for p in prefixes
        ]
        blended_cls = LegacyBlendedDataset if data.legacy_dataset else TextBlendedDataset
    if len(datasets) == 1:
        return datasets[0]
    blended_config = data.blended_dataset or BlendedDatasetConfig()
    return blended_cls(
        seed=config.trainer.seed, config=blended_config, datasets=datasets
    )


class TransformerTrainer(BaseTrainer):
    # accepts BOTH the legacy positional name and the BaseTrainer keyword
    # (run_with_resume and other generic wrappers call the base surface
    # `run_training(log_metrics_fn=...)` — it must not TypeError here)
    def run_training(self, log_metrics_fn_=None, *,
                     log_metrics_fn=None) -> None:  # noqa: D102
        fn = log_metrics_fn_ or log_metrics_fn or globals()["log_metrics_fn"]
        super().run_training(log_metrics_fn=fn)


def main(config: TransformerConfig) -> TransformerTrainer:
    if config.transformer_architecture.loop_steps > 1:
        from .model import LOOPED_TRAINING_REFUSAL

        raise NotImplementedError(f"train.main: {LOOPED_TRAINING_REFUSAL}")
    topology = Topology(config.topology)
    logger.configure(config.logger, name="transformer")
    logger.log_config(config)
    context = TransformerContext(config=config, topology=topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    dataset = _read_dataset(config, config.data.data_prefixes)
    dataset_evaluation = _read_dataset(config, config.data.validation_data_prefixes)
    from ...profiler import Profiler

    trainer = TransformerTrainer(
        config=config.trainer,
        context=context,
        parallel_module=module,
        optimizer=optimizer,
        loss_function=loss_function,
        dataset=dataset,
        dataset_evaluation=dataset_evaluation,
        batch_to_model_input=batch_to_model_input,
        profiler=Profiler(config.profiler),
    )
    # declare the model's FLOPs-per-token once so the trainer's telemetry
    # emits per-step achieved-TFLOPs/MFU gauges (docs/OBSERVABILITY.md)
    # alongside the per-step estimator metrics log_metrics_fn computes
    arch = config.transformer_architecture
    topo = config.topology
    param_count = get_model_parameter_count(
        arch.hidden_size, arch.num_layers, arch.vocab_size, arch.mlp_factor,
        glu=arch.mlp_type.value == "swiglu",
    )
    hardware = detect_hardware()
    trainer.telemetry.configure(
        flops_per_token=get_flops_per_token(
            param_count, arch.num_layers, arch.hidden_size,
            arch.sequence_length,
        ),
        tokens_per_step=topo.global_batch_size * arch.sequence_length,
        world_size=topo.world_size,
        peak_tflops=None if hardware is None else hardware.max_tflops,
    )
    from ...resilience import controlplane_from_env

    # under the multi-host supervisor every worker finds the control
    # plane in its environment (SCALING_TPU_CONTROL_DIR/_ADDR); joining
    # it turns on heartbeats (without which the supervisor would declare
    # a healthy host hung after the startup grace), the coordinated
    # preemption drain, and the cross-host commit barrier
    cp = controlplane_from_env()
    if cp is not None:
        trainer.attach_control_plane(
            cp, shared_save_dir=config.trainer.multihost_shared_save_dir
        )
        trainer.install_preemption_handler()
    from ...determined import DeterminedGlue

    glue = DeterminedGlue.detect()
    try:
        if glue is None:
            trainer.initialize(load_checkpoint=config.trainer.load_dir is not None)
        else:
            # under Determined the experiment's own latest checkpoint wins
            # over the configured load_dir (reference: trainer.py:416-428)
            glue.attach(trainer)
            with glue.latest_checkpoint() as det_ckpt:
                trainer.initialize(
                    load_checkpoint=(
                        det_ckpt is not None or config.trainer.load_dir is not None
                    ),
                    load_dir=det_ckpt,
                )
        clip_ckpt = config.transformer_architecture.image_encoder_clip_checkpoint
        if clip_ckpt is not None:
            _apply_pretrained_clip(trainer, module, clip_ckpt)
        trainer.run_training()
    finally:
        if glue is not None:
            glue.close()
    return trainer


def _apply_pretrained_clip(trainer, module, path) -> None:
    """Splice pretrained CLIP vision weights into the image-encoder trunk
    at startup (reference: clip.py constructs its trunk pretrained). Skipped
    whenever the loaded checkpoint already restored image-encoder weights
    (resume OR finetune-with-load_context=False — either way the trained
    trunk is in the checkpoint); applied on fresh runs and
    finetunes-from-LM-only-checkpoints. Optimizer masters for the spliced
    subtree re-derive so the first step can't revert it; moments loaded
    for the REST of the model are kept."""
    from pathlib import Path

    if trainer.context.iterations > 0:
        logger.info(f"resume at step {trainer.context.iterations}: "
                    "skipping pretrained CLIP splice (trunk is in the checkpoint)")
        return
    restored = trainer.restored_model_keys or set()
    # gate on the TRUNK specifically: a checkpoint restoring only the
    # shared non-trunk pieces (image_encoder.proj / final_norm) must not
    # suppress the splice the config explicitly asked for
    if any("image_encoder.clip" in k for k in restored):
        logger.info(
            "loaded checkpoint already restored the CLIP trunk; "
            "skipping pretrained CLIP splice"
        )
        return
    import torch

    p = Path(path)
    if p.is_dir():
        from transformers import CLIPVisionModel

        sd = CLIPVisionModel.from_pretrained(p).state_dict()
    else:
        sd = torch.load(p, map_location="cpu", weights_only=True)
        sd = sd.get("state_dict", sd)

    for i, layer in enumerate(module.layers):
        encoder = getattr(layer, "image_encoder", None)
        if encoder is None:
            continue
        name = module.layer_name(i)
        emb_params = trainer.params[name]
        fresh = encoder.load_clip_weights(emb_params["image_encoder"], sd)
        placed = jax.tree.map(
            lambda new, old: jax.device_put(new.astype(old.dtype), old.sharding)
            if hasattr(old, "sharding") else new.astype(old.dtype),
            fresh, emb_params["image_encoder"],
        )
        trainer.params = {
            **trainer.params, name: {**emb_params, "image_encoder": placed},
        }
        if trainer.optimizer_states_loaded:
            # the splice only replaced the clip TRUNK (load_clip_weights
            # leaves proj/final_norm untouched), so only that subtree gets
            # fresh masters/zero moments; loaded moments everywhere else —
            # including image_encoder.proj/final_norm — are kept. `only`
            # keeps the rest of the fresh tree at cheap placeholders, so
            # no full fp32 transient on big models.
            fresh = trainer.optimizer.init_state(
                trainer.params,
                only=lambda m: "image_encoder.clip" in m.parameter_name,
            )

            def graft(dst, src):
                enc = dst[name]["image_encoder"]
                fresh_enc = src[name]["image_encoder"]
                return {
                    **dst,
                    name: {
                        **dst[name],
                        "image_encoder": {**enc, "clip": fresh_enc["clip"]},
                    },
                }

            trainer.opt_state = trainer.opt_state._replace(
                master=graft(trainer.opt_state.master, fresh.master),
                exp_avg=graft(trainer.opt_state.exp_avg, fresh.exp_avg),
                exp_avg_sq=graft(trainer.opt_state.exp_avg_sq, fresh.exp_avg_sq),
            )
        else:
            trainer.opt_state = trainer.optimizer.init_state(trainer.params)
        logger.info(f"loaded pretrained CLIP vision weights from {path}")
        return
    raise ValueError(
        "image_encoder_clip_checkpoint set but the model has no image "
        "encoder (set image_encoder: true, image_encoder_backbone: clip)"
    )


if __name__ == "__main__":
    launch_config = LaunchConfig.from_launcher_args()
    initialize_distributed(launch_config)
    assert launch_config.payload is not None, "--payload required"
    config = TransformerConfig.from_dict(launch_config.payload)
    main(config)
