"""Layer contract and assembly specs.

The reference's ``BaseLayer`` needs tuple-conversion hooks because pipe
communication and activation checkpointing move opaque tuples between
processes (reference: src/scaling/core/nn/parallel_module/base_layer.py:16).
Under jit everything is a pytree with static treedef, so the contract
collapses to: ``init(key) -> params``, ``param_metas() -> metas``,
``__call__(params, x, ctx) -> y`` where x/y are pytrees.

``LayerSpec``/``TiedLayerSpec`` keep the reference's deferred-construction
API (reference: src/scaling/core/nn/parallel_module/layer_spec.py:8-29) so
model assembly code reads the same.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional, Type

import jax


@dataclass
class ForwardContext:
    """Per-call state threaded through layers (all jit-compatible)."""

    # dropout master key for this microbatch/step; None => deterministic
    dropout_key: Optional[jax.Array] = None
    # train vs eval; static under jit
    deterministic: bool = True
    # topology flags the layers need (static)
    sequence_parallel: bool = False
    model_parallel_size: int = 1
    context_parallel_size: int = 1
    # "ring" (K/V rotation) or "ulysses" (head all-to-all); see
    # topology.config.ContextParallelVariant
    context_parallel_variant: str = "ring"
    # mesh is needed for explicit collectives; None on single device
    mesh: Optional[Any] = None
    # a train step under ZeRO-1 over a data axis (static): the weights arrive
    # as the shards their masters live on and ``Optimizer.gather_params``
    # has gathered all but those a layer consumes there
    # (parallel/sharding.py, ``lookup_on_data_shard``). ``build_train_step``
    # sets it from the optimizer; False in every other pass
    zero_gathers_on_entry: bool = False
    # paged-decode attention back-end (static), decided HERE: 'pallas'
    # streams blocks through the flash-style kernel
    # (nn/paged_attention.py) and is what serves; 'xla' gathers each
    # row's block window, the formulation tests hold the kernel to
    # (TransformerInferenceModule._run_layers paged_kernel='xla').
    paged_kernel: str = "pallas"
    # an inference pass (static): TransformerInferenceModule sets it on
    # every context it makes (generate, logits, the serving engine's
    # programs). A routed MLP then drops no assignment and computes no
    # auxiliary loss (nn/moe.py, "Serving").
    serving: bool = False
    # the most tokens a row of a served tick brings (static): what the serving
    # pools' probe sizes a window layer's ring for (nn/window_attention.py);
    # 1 wherever nothing is probed
    serve_row_width: int = 1

    _key_counter: int = 0
    # the inputs of the TP regions this pass has entered through sequence
    # parallelism's explicit collectives (nn/linear.py,
    # ``column_parallel_matmul``), noted while tracing; kept, so that
    # siblings that gather one input count as the one region they enter
    _sp_region_inputs: list = field(default_factory=list)

    def note_sp_region(self, x: jax.Array) -> None:
        if not any(x is seen for seen in self._sp_region_inputs):
            self._sp_region_inputs.append(x)

    @property
    def sp_manual_boundaries(self) -> int:
        """How many TP regions this pass has entered by hand."""
        return len(self._sp_region_inputs)

    def next_key(self) -> Optional[jax.Array]:
        """Derive a fresh dropout key; deterministic given call order."""
        if self.dropout_key is None or self.deterministic:
            return None
        self._key_counter += 1
        return jax.random.fold_in(self.dropout_key, self._key_counter)

    def dropout(self, x: jax.Array, rate: float) -> jax.Array:
        if rate == 0.0 or self.deterministic:
            return x
        key = self.next_key()
        if key is None:
            return x
        keep = 1.0 - rate
        mask = jax.random.bernoulli(key, p=keep, shape=x.shape)
        return jax.numpy.where(mask, x / keep, 0).astype(x.dtype)


def state_views(layer) -> tuple:
    """The view classes of the serving state ``layer`` keeps, as its
    ``consumes`` declares them: none (no ``consumes``, or None: an MLP, an
    edge layer), one (the view its one mixer names), or, where two mixers run
    side by side in one block, a tuple of theirs. The order is that of the
    layer's state wherever a walk of the stack lists it: its caches, the
    finals of a probe, ``kinds`` of the serving pools."""
    consumes = getattr(layer, "consumes", None)
    if consumes is None:
        return ()
    return consumes if isinstance(consumes, tuple) else (consumes,)


def multiplied(x: jax.Array, by: float) -> jax.Array:
    """``x`` times a constant of the configuration, computed in float32 and
    given back in ``x``'s dtype (a constant such as 0.0110485 rounded to bf16
    first would be off by up to 0.4%); at 1 ``x`` itself, so that a model
    without multipliers lowers to what it did."""
    if by == 1.0:
        return x
    return (x.astype(jax.numpy.float32) * by).astype(x.dtype)


class BaseLayer:
    """Stateless layer: owns hyperparameters, emits params/metas trees."""

    def init(self, key: jax.Array) -> Any:
        raise NotImplementedError

    def param_metas(self) -> Any:
        raise NotImplementedError

    def __call__(self, params: Any, x: Any, ctx: ForwardContext) -> Any:
        raise NotImplementedError


@dataclass
class LayerSpec:
    """Deferred layer construction for pipeline assembly."""

    module_class: Type[BaseLayer]
    args: tuple = ()
    kwargs: dict = field(default_factory=dict)

    def __init__(self, module_class: Type[BaseLayer], *args: Any, **kwargs: Any):
        self.module_class = module_class
        self.args = args
        self.kwargs = kwargs

    def initialize(self) -> BaseLayer:
        return self.module_class(*self.args, **self.kwargs)


class PipelineBodySpec(LayerSpec):
    """A homogeneous run of ``num_layers`` identical layers, executed as one
    stage-stacked pipelined body (spatial GPipe over the ``pipe`` mesh axis).

    Replaces ``num_layers`` consecutive LayerSpecs of the same class; the
    constructed template layer supplies init/param_metas/__call__ for one
    layer. Checkpoints still see the individual layers (the ParallelModule
    un-stacks them into per-layer files), so a checkpoint written at one
    pipe_parallel_size loads at any other
    (reference partitioning: pipeline_partitioning.py:38-136).
    """

    def __init__(self, module_class: Type[BaseLayer], num_layers: int,
                 *args: Any, **kwargs: Any):
        super().__init__(module_class, *args, **kwargs)
        self.num_layers = num_layers


class TiedLayerSpec(LayerSpec):
    """LayerSpec whose named params are shared with other specs of same key.

    ``tied_weight_attributes`` lists param-tree paths (dot notation) tied
    across occurrences, e.g. embedding weight reused by the LM head.
    """

    def __init__(
        self,
        module_class: Type[BaseLayer],
        *args: Any,
        key: str,
        tied_weight_attributes: Optional[list[str]] = None,
        **kwargs: Any,
    ):
        super().__init__(module_class, *args, **kwargs)
        self.key = key
        self.tied_weight_attributes = tied_weight_attributes or ["weight"]
