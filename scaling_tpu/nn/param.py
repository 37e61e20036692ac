"""Parameter metadata.

Every parameter in the framework carries a ``ParamMeta`` describing how it
shards over the mesh, where it lives in the layer stack, and how the
optimizer/checkpoint machinery should treat it. This plays the role of the
reference's ``CoreParameterMeta``
(reference: src/scaling/core/nn/parameter_meta.py:17-151): the
layout-independent ``key`` makes checkpoints survive topology changes and
lets non-strict PEFT loading match parameters by name rather than position.

Parameters and metas live in *parallel pytrees* with identical structure:
layers return a nested-dict params tree from ``init`` and the same-shaped
meta tree from ``param_metas``.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Optional

import jax
from jax.sharding import PartitionSpec as P

from ..topology.topology import MODEL_AXIS


@dataclass(frozen=True)
class ParamMeta:
    parameter_name: str = ""
    layer_index: Optional[int] = None
    layer_class_name: str = ""
    # mesh sharding of the parameter itself; () = replicated
    partition_spec: tuple = ()
    is_model_parallel: bool = False
    model_parallel_dimension: Optional[int] = None
    # weight tying: parameters sharing a tied_key are the same array
    tied_key: Optional[str] = None
    # true for params replicated across mp that must stay bit-identical
    is_model_parallel_duplicate: bool = False
    no_weight_decay: bool = False
    # learning-rate group: "default" | "embedding"
    lr_group: str = "default"
    # marks norm params whose grads need mp-summing under sequence parallel
    is_sequence_parallel_norm: bool = False
    # every consumer of the leaf reads ROWS of it by index and none multiplies
    # by it (an untied embedding table): ZeRO-1 may leave it on the masters'
    # shard for the lookup (parallel/sharding.py, ``lookup_on_data_shard``)
    row_lookup: bool = False

    @property
    def key(self) -> str:
        """Layout-independent identity used for checkpoint matching."""
        return f"layer_{self.layer_index}_{self.layer_class_name}.{self.parameter_name}"

    def spec(self) -> P:
        return P(*self.partition_spec)

    def with_layer(self, layer_index: int, layer_class_name: str) -> "ParamMeta":
        return replace(self, layer_index=layer_index, layer_class_name=layer_class_name)

    def prefixed(self, prefix: str) -> "ParamMeta":
        name = f"{prefix}.{self.parameter_name}" if self.parameter_name else prefix
        return replace(self, parameter_name=name)


def model_parallel_meta(dim: int, **kwargs: Any) -> ParamMeta:
    """Meta for a weight sharded over the model axis along ``dim``."""
    spec: list = [None, None]
    spec[dim] = MODEL_AXIS
    return ParamMeta(
        partition_spec=tuple(spec),
        is_model_parallel=True,
        model_parallel_dimension=dim,
        **kwargs,
    )


def replicated_meta(ndim: int = 1, **kwargs: Any) -> ParamMeta:
    return ParamMeta(
        partition_spec=(None,) * ndim,
        is_model_parallel=False,
        is_model_parallel_duplicate=True,
        **kwargs,
    )


# ------------------------------------------------------------------ tree ops
def tree_prefix(metas: Any, prefix: str) -> Any:
    """Prefix every meta's parameter_name with ``prefix.``"""
    return jax.tree.map(
        lambda m: m.prefixed(prefix), metas, is_leaf=lambda x: isinstance(x, ParamMeta)
    )


def tree_with_layer(metas: Any, layer_index: int, layer_class_name: str) -> Any:
    return jax.tree.map(
        lambda m: m.with_layer(layer_index, layer_class_name),
        metas,
        is_leaf=lambda x: isinstance(x, ParamMeta),
    )


def named_parameters(params: Any, metas: Any) -> list[tuple[str, jax.Array, ParamMeta]]:
    """Flatten parallel trees into (key, array, meta) triples."""
    p_leaves, p_def = jax.tree.flatten(params)
    m_leaves, m_def = jax.tree.flatten(metas, is_leaf=lambda x: isinstance(x, ParamMeta))
    if len(p_leaves) != len(m_leaves):
        raise ValueError(
            f"params tree has {len(p_leaves)} leaves but metas tree has {len(m_leaves)}"
        )
    return [(m.key, p, m) for p, m in zip(p_leaves, m_leaves)]
