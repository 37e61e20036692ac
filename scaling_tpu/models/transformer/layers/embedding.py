"""Token embedding input layer.

(reference: src/scaling/transformer/model/layers/embedding.py:29-160) —
VocabParallelEmbedding + embedding dropout, optional softprompt splice.
The batch arrives as the dict the dataset collates
(token_ids/position_ids/segment_ids/loss_weights); this layer turns it into
the transformer IO dict. The image-encoder splice is gated off (config
raises), matching the TPU build's scope.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ....nn import (
    BaseLayer,
    ForwardContext,
    ParamMeta,
    VocabParallelEmbedding,
    normal_init,
    tree_prefix,
)
from ....nn.base_layer import multiplied
from ..config import SoftpromptConfig, TransformerArchitectureConfig
from .base import make_layer_io


class EmbeddingInput(BaseLayer):
    def __init__(self, architecture: TransformerArchitectureConfig):
        self.architecture = architecture
        extra = {}
        if architecture.layer_pattern is not None or architecture.parallel_ssm:
            # a stack of single-mixer layers starts its stream at unit
            # variance (N(0, 1), the plain default of an embedding table; a
            # table that is the head too at a head's Xavier deviation),
            # beside which each residual branch is small: layers/layer.py,
            # MixerLayer.init
            extra["init_method"] = normal_init(architecture.pattern_embedding_std)
        self.embedding = VocabParallelEmbedding(
            num_embeddings=architecture.vocab_size,
            embedding_dim=architecture.hidden_size,
            dtype=architecture.dtype,
            finetunable_token_ids=architecture.finetunable_token_ids or None,
            row_lookup=not architecture.weight_tying,
            **extra,
        )
        self.dropout_rate = architecture.dropout_embedding
        self.softprompt_config: Optional[SoftpromptConfig] = architecture.softprompt_config
        self.image_encoder = None
        if architecture.image_encoder:
            from ..image_encoder import ImageEncoder

            self.image_encoder = ImageEncoder(
                out_features=architecture.hidden_size,
                width=architecture.image_encoder_width,
                layers=architecture.image_encoder_layers,
                heads=architecture.image_encoder_heads,
                dropout_p=architecture.dropout_image_encoder,
                dtype=architecture.dtype,
                backbone=architecture.image_encoder_backbone,
                resnet_stages=architecture.image_encoder_resnet_stages,
                resnet_channels=architecture.image_encoder_resnet_channels,
            )

    def init(self, key: jax.Array) -> dict:
        params = {"embedding": self.embedding.init(key)}
        # under a published multiplier the table starts that much lower: the
        # stream starts where it would without one
        params["embedding"]["weight"] = multiplied(
            params["embedding"]["weight"],
            1.0 / self.architecture.multipliers.embedding)
        if self.image_encoder is not None:
            params["image_encoder"] = self.image_encoder.init(jax.random.fold_in(key, 2))
        if self.softprompt_config is not None:
            sp_key = jax.random.fold_in(key, 1)
            params[f"softprompt_{self.softprompt_config.name}"] = jax.random.normal(
                sp_key,
                (self.softprompt_config.n_tokens, self.architecture.hidden_size),
                dtype=self.architecture.dtype,
            ) * 0.5
        return params

    def param_metas(self) -> dict:
        metas = {"embedding": tree_prefix(self.embedding.param_metas(), "embedding")}
        if self.image_encoder is not None:
            metas["image_encoder"] = self.image_encoder.param_metas()
        if self.softprompt_config is not None:
            name = f"softprompt_{self.softprompt_config.name}"
            metas[name] = ParamMeta(
                parameter_name=name,
                partition_spec=(None, None),
                is_model_parallel_duplicate=True,
            )
        return metas

    def __call__(self, params: dict, batch: dict, ctx: ForwardContext) -> dict:
        token_ids = batch["token_ids"]
        embeddings = multiplied(
            self.embedding(params["embedding"], token_ids, ctx),
            self.architecture.multipliers.embedding)

        if self.image_encoder is not None and batch.get("input_images") is not None:
            # splice 144 encoded prefix tokens per image at its location
            # (reference: embedding.py:53-61,111-144 magma-style)
            imgs = batch["input_images"]  # (b, n_img, H, W, 3)
            locs = batch["input_image_locations"]  # (b, n_img) start positions
            b_, n_img = imgs.shape[:2]
            enc = self.image_encoder(
                params["image_encoder"], imgs.reshape((b_ * n_img,) + imgs.shape[2:]), ctx
            )
            enc = enc.reshape(b_, n_img, enc.shape[-2], enc.shape[-1])
            # (b, n_img) validity mask: collate pads items to the batch's max
            # image count; padded slots must not overwrite real embeddings
            img_mask = batch.get("input_image_mask")
            for j in range(n_img):
                spliced = jax.vmap(
                    lambda e, blk, st: jax.lax.dynamic_update_slice(
                        e, blk.astype(e.dtype), (st, 0)
                    )
                )(embeddings, enc[:, j], locs[:, j].astype(jnp.int32))
                if img_mask is not None:
                    spliced = jnp.where(img_mask[:, j, None, None], spliced, embeddings)
                embeddings = spliced

        if self.softprompt_config is not None:
            # overwrite the first n_tokens positions with the learned prompt
            # (reference: embedding.py:146-160 splices at placeholder ids)
            n = self.softprompt_config.n_tokens
            sp = params[f"softprompt_{self.softprompt_config.name}"]
            sp = jnp.broadcast_to(sp[None], (embeddings.shape[0], n, embeddings.shape[2]))
            embeddings = jax.lax.dynamic_update_slice_in_dim(
                embeddings, sp.astype(embeddings.dtype), 0, axis=1
            )

        embeddings = ctx.dropout(embeddings, self.dropout_rate)
        if self.architecture.hc_streams > 1:
            # every residual stream starts as the embedding: stream j is
            # lanes [j * hidden, (j + 1) * hidden) (nn/hyper_connection.py)
            embeddings = jnp.tile(
                embeddings, (1, 1, self.architecture.hc_streams))

        b, s = token_ids.shape
        position_ids = batch.get("position_ids")
        if position_ids is None:
            position_ids = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        segment_ids = batch.get("segment_ids")
        if segment_ids is None:
            segment_ids = jnp.zeros((b, s), dtype=jnp.int32)
        from ..config import MLPType

        aux_loss = (
            jnp.zeros((), jnp.float32)
            if self.architecture.mlp_type == MLPType.MOE
            else None
        )
        return make_layer_io(
            activations=embeddings,
            position_ids=position_ids,
            segment_ids=segment_ids,
            loss_weights=batch.get("loss_weights"),
            attention_scores_manipulation=batch.get("attention_scores_manipulation"),
            aux_loss=aux_loss,
        )
