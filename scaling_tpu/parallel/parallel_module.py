"""ParallelModule: layer-spec assembly + the jitted train/eval step.

The reference's ParallelModule interprets a precomputed 1F1B instruction
list per step, moving micro-batches through buffers and NCCL P2P
(reference: src/scaling/core/nn/parallel_module/parallel_module.py:89-747).
Under single-controller SPMD the entire train step — grad accumulation over
micro-batches, forward/backward, optimizer update, ZeRO collectives — is ONE
jitted program: the instruction loop becomes a ``lax.scan`` over stacked
micro-batches and XLA schedules the communication. Pipeline parallelism
(pp > 1) runs the layer stack through the pipelined executor in
``pipeline.py`` (collective-permute over the ``pipe`` axis) inside the same
step function.

Weight tying (reference: tied_layer_index.py:74-224) becomes structural:
tied attributes live once in the owner layer's params; consumer layers get
them injected at call time, so gradients flow to a single array and no
tied-grad all-reduce exists.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from typing import TYPE_CHECKING

from ..nn.base_layer import (
    BaseLayer,
    ForwardContext,
    LayerSpec,
    PipelineBodySpec,
    TiedLayerSpec,
)
from ..logging import logger
from ..nn.param import ParamMeta, named_parameters, tree_with_layer
from ..obs.registry import get_registry
from ..obs.spans import span
from ..topology import ActivationCheckpointingType, Topology
from ..topology.topology import MODEL_AXIS, PIPE_AXIS


def remat_policy(ckpt_type: ActivationCheckpointingType):
    """jax.checkpoint policy for a checkpointing mode (None = save nothing,
    recompute everything inside the checkpointed region)."""
    if ckpt_type == ActivationCheckpointingType.EVERY_LAYER_SAVE_DOTS:
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return None
from .pipeline import PipelinedBody

if TYPE_CHECKING:  # break the optimizer <-> parallel import cycle
    from ..optimizer.optimizer import Optimizer
from .sharding import shard_batch


class TrainStepOutput(NamedTuple):
    loss: Any
    metrics: Dict[str, Any]
    global_grad_norm: Optional[Any] = None
    learning_rates: Optional[Dict[str, Any]] = None
    overflow: Optional[Any] = None
    no_overflow_steps: Optional[Any] = None
    current_loss_scale: Optional[Any] = None
    debug_dict: Optional[Dict[str, Any]] = None
    step_duration: Optional[float] = None
    # False on steps where trainer.log_interval skipped the device->host
    # sync: numeric fields are still-in-flight jax arrays, not floats, and
    # the logging path must not touch them (that would reintroduce the sync)
    fetched: bool = True


class EvaluationStepOutput(NamedTuple):
    loss: Any
    metrics: Dict[str, Any]
    step_duration: Optional[float] = None


def _lift_edge_meta_over_pipe(meta: ParamMeta) -> ParamMeta:
    """Shard edge-layer model-parallel dims over (pipe, model) when pp > 1.

    Layers outside the pipelined body (embedding, lm head) would otherwise
    be replicated on every pipe stage — at a 7B/128k-vocab scale that wastes
    several GB of params + fp32 master/moments per stage. The reference
    instead places these on the first/last stage (partitioned_module.py);
    spatially, splitting their vocab dim across the pipe axis is the
    equivalent memory footprint, and GSPMD inserts the pipe-axis collectives.
    """
    if not getattr(meta, "is_model_parallel", False):
        return meta
    dim = meta.model_parallel_dimension or 0
    spec = list(meta.partition_spec)
    if dim >= len(spec) or spec[dim] != MODEL_AXIS:
        return meta
    spec[dim] = (PIPE_AXIS, MODEL_AXIS)
    return ParamMeta(**{**meta.__dict__, "partition_spec": tuple(spec)})


def _get_path(tree: dict, path: str):
    node = tree
    for part in path.split("."):
        node = node[part]
    return node


def _set_path(tree: dict, path: str, value) -> dict:
    parts = path.split(".")
    tree = dict(tree)
    node = tree
    for part in parts[:-1]:
        node[part] = dict(node[part])
        node = node[part]
    node[parts[-1]] = value
    return tree


def _del_path(tree: dict, path: str) -> dict:
    parts = path.split(".")
    tree = dict(tree)
    node = tree
    for part in parts[:-1]:
        node[part] = dict(node[part])
        node = node[part]
    del node[parts[-1]]
    return tree


def _jit_train_step(step: Callable, optimizer: Optimizer, donate: bool) -> Callable:
    """``jax.jit(step)`` behind ``optimizer.place_params``, called and lowered
    like the jitted function: ``step(params, opt_state, micro_batches, key)``
    and ``step.lower(...)``.

    Under ZeRO the step takes and returns the compute copy in the masters'
    placement. A caller may hold weights placed by their own specs alone (the
    first call after ``shard_params``, a checkpoint just loaded). ``jit`` would
    lower a second program for those, so the weights are placed here, before
    the ONE program: a local slice on the first call, a comparison of
    shardings a leaf on every later one."""
    jitted = jax.jit(step, donate_argnums=(0, 1) if donate else ())
    if optimizer.topology is None or not optimizer.config.zero:
        return jitted  # nothing to place: the jitted function itself

    def placed_step(params, *rest):
        return jitted(optimizer.place_params(params, donate=donate), *rest)

    placed_step.lower = lambda params, *rest: jitted.lower(
        optimizer.place_params(params), *rest)
    return placed_step


def _tied_meta(meta: ParamMeta, key: str) -> ParamMeta:
    if meta.row_lookup:
        # ZeRO-1 would leave the leaf on its shard for the owner's lookup,
        # and the consumer that multiplies by it would gather it at each use
        raise ValueError(
            f"{meta.parameter_name}: tied under {key!r} but declared "
            "row_lookup; build its layer without "
            "(VocabParallelEmbedding(row_lookup=False))")
    return type(meta)(**{**meta.__dict__, "tied_key": key})


@dataclass
class TiedInfo:
    key: str
    owner_layer: int
    attributes: List[str]
    consumers: List[int]


class ParallelModule:
    """Assembles a LayerSpec list into params/metas trees + step functions."""

    def __init__(
        self,
        layer_specs: List[LayerSpec],
        topology: Optional[Topology] = None,
        compute_dtype=jnp.float32,
        forward_refusal: Optional[str] = None,
    ):
        # why ``forward`` (so every train / eval step built on it) must not
        # walk this layer list once front to back; None: it may
        self.forward_refusal = forward_refusal
        self.layer_specs = layer_specs
        self.topology = topology
        self.compute_dtype = compute_dtype
        # body specs expand to PipelinedBody executors; logical layer indices
        # count through them so checkpoints name each inner layer like the
        # per-layer assembly would (reference: partitioned_module.py:249-257)
        self.layers: List[Any] = []
        self._logical_start: List[int] = []
        logical = 0
        for spec in layer_specs:
            self._logical_start.append(logical)
            if isinstance(spec, PipelineBodySpec):
                self.layers.append(
                    PipelinedBody(spec.initialize(), spec.num_layers, topology)
                )
                logical += spec.num_layers
            else:
                self.layers.append(spec.initialize())
                logical += 1
        self.num_logical_layers = logical
        self._has_spatial_pp = any(
            isinstance(l, PipelinedBody) and l.pp > 1 for l in self.layers
        )

        # tied-weight bookkeeping
        self.tied: Dict[str, TiedInfo] = {}
        for i, spec in enumerate(layer_specs):
            if isinstance(spec, TiedLayerSpec):
                if spec.key not in self.tied:
                    self.tied[spec.key] = TiedInfo(
                        key=spec.key, owner_layer=i,
                        attributes=spec.tied_weight_attributes, consumers=[],
                    )
                else:
                    assert self.tied[spec.key].attributes == spec.tied_weight_attributes
                    self.tied[spec.key].consumers.append(i)

    # ----------------------------------------------------------- params
    def layer_name(self, i: int) -> str:
        return f"layer_{self._logical_start[i]}"

    def _layer_class_name(self, i: int) -> str:
        layer = self.layers[i]
        if isinstance(layer, PipelinedBody):
            return type(layer.template).__name__
        return type(layer).__name__

    def init_params(self, key: jax.Array) -> dict:
        params = {}
        for i, layer in enumerate(self.layers):
            params[self.layer_name(i)] = layer.init(jax.random.fold_in(key, i))
        # drop tied attrs from consumers; owner holds the single copy
        for info in self.tied.values():
            for c in info.consumers:
                for attr in info.attributes:
                    params[self.layer_name(c)] = _del_path(params[self.layer_name(c)], attr)
        return params

    def param_metas(self) -> dict:
        metas = {}
        pp = self.topology.pipe_parallel_size if self.topology else 1
        for i, layer in enumerate(self.layers):
            m = layer.param_metas()
            if pp > 1 and not isinstance(layer, PipelinedBody):
                m = jax.tree.map(
                    _lift_edge_meta_over_pipe, m,
                    is_leaf=lambda x: isinstance(x, ParamMeta),
                )
            m = tree_with_layer(m, self._logical_start[i], self._layer_class_name(i))
            metas[self.layer_name(i)] = m
        for info in self.tied.values():
            owner_name = self.layer_name(info.owner_layer)
            for attr in info.attributes:
                meta = _get_path(metas[owner_name], attr)
                metas[owner_name] = _set_path(
                    metas[owner_name], attr,
                    _tied_meta(meta, info.key),
                )
            for c in info.consumers:
                for attr in info.attributes:
                    metas[self.layer_name(c)] = _del_path(metas[self.layer_name(c)], attr)
        return metas

    def named_parameters(self, params: dict) -> list:
        return named_parameters(params, self.param_metas())

    # ------------------------------------------------- checkpoint views
    # Stage-stacked body params are unstacked into per-logical-layer trees
    # before hitting disk, so checkpoint files are identical no matter the
    # pipe_parallel_size they were written under (the reference gets the
    # same property from merged layer files, partitioned_module.py:197-257).
    def ckpt_view(self, tree: dict) -> dict:
        view: dict = {}
        for i, layer in enumerate(self.layers):
            name = self.layer_name(i)
            sub = tree[name]
            if isinstance(layer, PipelinedBody):
                start = self._logical_start[i]
                L = layer.num_layers

                def to_layer_major(x, _layer=layer, _L=L):
                    # empty (0,) leaves are frozen-param placeholders in
                    # optimizer-state trees: not stacked, pass through
                    if not x.size:
                        return x
                    if _layer.vpp > 1:
                        # (pp, v, lpv, ...): stage s's virtual index r is
                        # the round-robin chunk r*pp + s — undo via
                        # (v, pp, lpv) flattening
                        x = jnp.moveaxis(x, 0, 1)
                        return x.reshape(_L, *x.shape[3:])
                    return x.reshape(_L, *x.shape[2:])

                flat = jax.tree.map(to_layer_major, sub)
                for j in range(L):
                    view[f"layer_{start + j}"] = jax.tree.map(
                        lambda x, _j=j: x[_j] if x.size else x, flat
                    )
            else:
                view[name] = sub
        return view

    def ckpt_unview(self, view: dict, like: dict) -> dict:
        """Inverse of ckpt_view; ``like`` supplies sharding/placement."""
        out: dict = {}
        for i, layer in enumerate(self.layers):
            name = self.layer_name(i)
            if isinstance(layer, PipelinedBody):
                start = self._logical_start[i]
                L, pp = layer.num_layers, max(layer.pp, 1)
                vpp = max(layer.vpp, 1)
                per_layer = [view[f"layer_{start + j}"] for j in range(L)]

                def restack(old, *xs, _vpp=vpp):
                    if old.size == 0:  # frozen-param placeholder
                        return old
                    new = jnp.stack(xs, axis=0)
                    if _vpp > 1:
                        # layer-major -> (v, pp, lpv, ...) -> interleaved
                        # (pp, v, lpv, ...) chunk layout (chunk r*pp + s
                        # lives at stage s, virtual index r)
                        new = jnp.moveaxis(
                            new.reshape(_vpp, pp, L // (pp * _vpp), *xs[0].shape),
                            0, 1,
                        )
                    else:
                        new = new.reshape(pp, L // pp, *xs[0].shape)
                    return (
                        jax.device_put(new, old.sharding)
                        if hasattr(old, "sharding")
                        else new
                    )

                out[name] = jax.tree.map(restack, like[name], *per_layer)
            else:
                out[name] = view[name]
        return out

    def ckpt_metas(self) -> dict:
        metas: dict = {}
        for i, layer in enumerate(self.layers):
            name = self.layer_name(i)
            start = self._logical_start[i]
            if isinstance(layer, PipelinedBody):
                template_metas = layer.template.param_metas()
                cls = self._layer_class_name(i)
                for j in range(layer.num_layers):
                    metas[f"layer_{start + j}"] = tree_with_layer(
                        template_metas, start + j, cls
                    )
            else:
                m = tree_with_layer(
                    layer.param_metas(), start, self._layer_class_name(i)
                )
                metas[name] = m
        # mirror the tied-attribute dropping of param_metas()
        for info in self.tied.values():
            owner_name = self.layer_name(info.owner_layer)
            for attr in info.attributes:
                meta = _get_path(metas[owner_name], attr)
                metas[owner_name] = _set_path(
                    metas[owner_name], attr,
                    _tied_meta(meta, info.key),
                )
            for c in info.consumers:
                for attr in info.attributes:
                    metas[self.layer_name(c)] = _del_path(metas[self.layer_name(c)], attr)
        return metas

    def parameter_count(self, params: dict) -> int:
        return sum(int(p.size) for p in jax.tree.leaves(params))

    def merge_lora_weights(self, params: dict) -> dict:
        """Fold LoRA deltas into base weights on every layer that has them.

        Backs ``trainer.merge_lora_after_loading_checkpoint`` (reference:
        attention.py:766-797 via trainer config). Stage-stacked pipeline
        bodies are merged per layer via nested vmap over the (pp,
        layers_per_stage) leading dims.
        """
        params = dict(params)
        for i, layer in enumerate(self.layers):
            name = self.layer_name(i)
            if isinstance(layer, PipelinedBody):
                template = layer.template
                if hasattr(template, "merge_lora_weights"):
                    merge = jax.vmap(jax.vmap(template.merge_lora_weights))
                    if layer.vpp > 1:  # extra (v) leading dim to map over
                        merge = jax.vmap(merge)
                    params[name] = merge(params[name])
            elif hasattr(layer, "merge_lora_weights"):
                params[name] = layer.merge_lora_weights(params[name])
        return params

    # ---------------------------------------------------------- forward
    def _layer_params(self, params: dict, i: int) -> dict:
        p = params[self.layer_name(i)]
        for info in self.tied.values():
            if i in info.consumers:
                for attr in info.attributes:
                    owner_p = _get_path(params[self.layer_name(info.owner_layer)], attr)
                    p = _set_path(p, attr, owner_p)
        return p

    def forward(self, params: dict, x: Any, ctx: ForwardContext) -> Any:
        if self.forward_refusal is not None:
            raise NotImplementedError(self.forward_refusal)
        ckpt_type = (
            self.topology.activation_checkpointing_type
            if self.topology is not None
            else ActivationCheckpointingType.DISABLED
        )
        policy = remat_policy(ckpt_type)
        for i, layer in enumerate(self.layers):
            layer_p = self._layer_params(params, i)
            if isinstance(layer, PipelinedBody):
                # the body remats its own stage/layer scans
                x = layer(
                    layer_p, x, ctx, stacked=False,
                    remat=ckpt_type != ActivationCheckpointingType.DISABLED,
                    remat_policy=policy,
                )
            elif ckpt_type in (
                ActivationCheckpointingType.EVERY_LAYER,
                ActivationCheckpointingType.EVERY_LAYER_SAVE_DOTS,
            ):
                x = jax.checkpoint(
                    lambda p, xx, _layer=layer: _layer(p, xx, ctx),
                    policy=policy,
                )(layer_p, x)
            else:
                x = layer(layer_p, x, ctx)
        return x

    def _make_ctx(self, deterministic: bool, dropout_key,
                  zero_gathers_on_entry: bool = False) -> ForwardContext:
        topo = self.topology
        return ForwardContext(
            dropout_key=dropout_key,
            deterministic=deterministic,
            zero_gathers_on_entry=zero_gathers_on_entry,
            sequence_parallel=bool(topo and topo.sequence_parallel),
            model_parallel_size=topo.model_parallel_size if topo else 1,
            context_parallel_size=topo.context_parallel_size if topo else 1,
            context_parallel_variant=(
                topo.context_parallel_variant if topo else "ring"
            ),
            mesh=topo.mesh if topo else None,
        )

    def loss_vocab_shards(self) -> int:
        """Over how many devices the vocabulary of the logits is split
        between the head and the loss: what the last layer that declares
        ``vocab_shards`` (``TransformerLMHead``) says of this mesh, 1 (whole
        rows on every device) where none does."""
        mesh = self.topology.mesh if self.topology else None
        declaring = [l for l in self.layers if hasattr(l, "vocab_shards")]
        return declaring[-1].vocab_shards(mesh) if declaring else 1

    # ------------------------------------------------------- train step
    def build_train_step(
        self,
        optimizer: Optimizer,
        loss_function: Callable[[Any, Any], tuple],
        donate: bool = True,
    ) -> Callable:
        """Returns jitted ``step(params, opt_state, micro_batches, dropout_key)``.

        ``micro_batches``: pytree whose leaves are stacked
        (grad_accumulation_steps, dp * micro_batch_size, ...) arrays.
        Output loss/metrics are means over micro batches (reference:
        parallel_module.py:288, optimizer.py:99-105).

        ``train.build_step`` is the Python that assembles the step, once a
        process; the step's first CALL traces, lowers and compiles it, which
        is the ``compile.*`` rows named ``jit(step)`` (obs/compile_events.py).
        """
        with span("train.build_step"):
            return self._assemble_train_step(optimizer, loss_function, donate)

    def _assemble_train_step(self, optimizer: Optimizer,
                             loss_function: Callable, donate: bool) -> Callable:
        if self.forward_refusal is not None:
            raise NotImplementedError(self.forward_refusal)
        gas = self.topology.gradient_accumulation_steps if self.topology else 1
        # a property of the program being built, read here and never in the
        # step: 1 = the loss sees whole rows, mp (pp * mp under stages) = it
        # runs on that many shards of the vocabulary
        shards = self.loss_vocab_shards()
        get_registry().gauge("train_loss_vocab_shards").set(shards)
        logger.info(f"train step: the loss runs over {shards} vocabulary shard(s)")
        # how many TP regions the traced step enters through sequence
        # parallelism's explicit collectives (nn/linear.py); 0 until a trace
        # says otherwise, and where SP is off or a constraint was kept
        manual_boundaries = get_registry().gauge("train_sp_manual_boundaries")
        manual_boundaries.set(0)
        # ZeRO-1's data-axis traffic (optimizer.py): leaves gathered once on
        # entry, gradients reduce-scattered onto the masters' placement, and
        # leaves neither gathered nor scattered because their layer looks
        # rows up on the shard; set when the step is traced, 0 where ZeRO is
        # off or the data axis is 1
        entry_gathers = get_registry().gauge("train_zero_entry_gathers")
        scattered_grads = get_registry().gauge("train_zero_scattered_grads")
        shard_lookups = get_registry().gauge("train_zero_shard_lookups")
        entry_gathers.set(0)
        scattered_grads.set(0)
        shard_lookups.set(0)

        scaler_enabled = optimizer.config.loss_scaler.enable

        def log_traced(zero_leaves: int, looked_up: int):
            """Called while a step is traced, after the forward: what the
            traced program does, on one line."""
            entry_gathers.set(zero_leaves)
            scattered_grads.set(zero_leaves)
            shard_lookups.set(looked_up)
            logger.info(
                f"train step: {int(manual_boundaries.value)} tensor-parallel "
                "region(s) entered through explicit collectives; ZeRO-1 over "
                f"the data axis: {zero_leaves} leaves gathered on entry "
                f"(train_zero_entry_gathers), {zero_leaves} gradients "
                "reduce-scattered onto the masters' placement "
                f"(train_zero_scattered_grads), {looked_up} looked up on "
                "their shard (train_zero_shard_lookups)")

        if self._has_spatial_pp:
            return self._build_spatial_train_step(
                optimizer, loss_function, donate, log_traced)

        def microbatch_loss(params, mb, dropout_key, loss_scale):
            # PEFT: frozen leaves produce constant-zero grads, so XLA drops
            # their weight-grad matmuls and DP syncs (optimizer.py)
            params = optimizer.freeze_frozen_params(params)
            ctx = self._make_ctx(
                deterministic=False, dropout_key=dropout_key,
                zero_gathers_on_entry=optimizer.gathers_on_entry())
            out = self.forward(params, mb, ctx)
            manual_boundaries.set(ctx.sp_manual_boundaries)
            loss, metrics = loss_function(out, mb)
            scaled = loss.astype(jnp.float32) / gas
            if scaler_enabled:
                scaled = scaled * loss_scale
            return scaled, (loss, metrics)

        def step(params, opt_state, micro_batches, dropout_key):
            loss_scale = opt_state.loss_scaler.current_scale
            params, zero_leaves, looked_up = optimizer.gather_params(params)

            grad_fn = jax.value_and_grad(microbatch_loss, has_aux=True)

            def body(carry, mb_and_idx):
                grads_acc, loss_acc, metrics_acc = carry
                mb, idx = mb_and_idx
                mb_key = jax.random.fold_in(dropout_key, idx)
                (_, (loss, metrics)), grads = grad_fn(params, mb, mb_key, loss_scale)
                grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                loss_acc = loss_acc + loss.astype(jnp.float32)
                metrics_acc = jax.tree.map(
                    lambda a, b: a + jnp.asarray(b, jnp.float32), metrics_acc, metrics
                )
                return (grads_acc, loss_acc, metrics_acc), None

            zero_grads = jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
            first_mb = jax.tree.map(lambda x: x[0], micro_batches)
            # learn the metrics structure without burning flops
            metrics0 = jax.eval_shape(
                lambda p, mb, k, s: microbatch_loss(p, mb, k, s)[1][1],
                params,
                first_mb,
                dropout_key,
                loss_scale,
            )
            zero_metrics = jax.tree.map(lambda m: jnp.zeros((), jnp.float32), metrics0)
            log_traced(zero_leaves, looked_up)

            if gas == 1:
                (grads, loss_sum, metrics_sum), _ = body(
                    (zero_grads, jnp.float32(0), zero_metrics),
                    (first_mb, jnp.int32(0)),
                )
            else:
                idxs = jnp.arange(gas)
                (grads, loss_sum, metrics_sum), _ = jax.lax.scan(
                    body, (zero_grads, jnp.float32(0), zero_metrics), (micro_batches, idxs)
                )

            new_params, new_opt_state, opt_out = optimizer.step(
                params, grads, opt_state, compute_dtype=self.compute_dtype
            )
            loss = loss_sum / gas
            metrics = jax.tree.map(lambda m: m / gas, metrics_sum)
            return new_params, new_opt_state, loss, metrics, opt_out

        return _jit_train_step(step, optimizer, donate)

    def _build_spatial_train_step(
        self, optimizer, loss_function: Callable, donate: bool,
        log_traced: Callable,
    ) -> Callable:
        """Train step for pipe_parallel_size > 1: all micro-batches flow
        through the stage-stacked body at once (spatial GPipe); edge layers
        and the loss run per micro-batch under vmap/scan. Gradients come
        from ONE backward over the whole pipelined program — XLA schedules
        the collective-permutes, matching the reference's 1F1B+grad-accum
        semantics (reference: pipeline_schedule/train.py:33-174) without the
        instruction interpreter.
        """
        topo = self.topology
        gas = topo.gradient_accumulation_steps
        scaler_enabled = optimizer.config.loss_scaler.enable
        remat = (
            topo.activation_checkpointing_type != ActivationCheckpointingType.DISABLED
        )
        policy = remat_policy(topo.activation_checkpointing_type)
        body_ids = [
            i for i, l in enumerate(self.layers) if isinstance(l, PipelinedBody)
        ]
        if len(body_ids) != 1:
            raise NotImplementedError(
                f"spatial pipelining expects exactly one PipelineBodySpec, got {len(body_ids)}"
            )
        body_idx = body_ids[0]
        pre_ids = list(range(body_idx))
        post_ids = list(range(body_idx + 1, len(self.layers)))

        def spatial_loss(params, micro_batches, dropout_key, loss_scale):
            params = optimizer.freeze_frozen_params(params)
            mb_keys = jax.vmap(
                lambda m: jax.random.fold_in(dropout_key, m)
            )(jnp.arange(gas))

            def run_pre(mb, k):
                ctx = self._make_ctx(deterministic=False, dropout_key=k)
                x = mb
                for i in pre_ids:
                    x = self.layers[i](self._layer_params(params, i), x, ctx)
                return x

            xs = jax.vmap(run_pre)(micro_batches, mb_keys)

            body_ctx = self._make_ctx(
                deterministic=False,
                dropout_key=jax.random.fold_in(dropout_key, 0x0B0D),
            )
            xs = self.layers[body_idx](
                self._layer_params(params, body_idx), xs, body_ctx, remat=remat,
                remat_policy=policy,
            )

            def run_post(x, mb, k):
                ctx = self._make_ctx(
                    deterministic=False, dropout_key=jax.random.fold_in(k, 1)
                )
                for i in post_ids:
                    x = self.layers[i](self._layer_params(params, i), x, ctx)
                loss, metrics = loss_function(x, mb)
                return (
                    loss.astype(jnp.float32),
                    jax.tree.map(lambda v: jnp.asarray(v, jnp.float32), metrics),
                )

            # scan (not vmap) over micro-batches + remat: only one
            # micro-batch worth of vocab-sized logits is ever live
            run_post_ck = jax.checkpoint(run_post, policy=policy)

            def post_scan(_, inp):
                x, mb, k = inp
                return None, run_post_ck(x, mb, k)

            _, (losses, metrics) = jax.lax.scan(
                post_scan, None, (xs, micro_batches, mb_keys)
            )
            loss = losses.mean()
            metrics = jax.tree.map(lambda v: v.mean(axis=0), metrics)
            scaled = loss * loss_scale if scaler_enabled else loss
            return scaled, (loss, metrics)

        def step(params, opt_state, micro_batches, dropout_key):
            loss_scale = opt_state.loss_scaler.current_scale
            # under stages no leaf is looked up on its shard
            # (``lookup_on_data_shard``): the contexts below say nothing
            params, zero_leaves, looked_up = optimizer.gather_params(params)
            (_, (loss, metrics)), grads = jax.value_and_grad(
                spatial_loss, has_aux=True
            )(params, micro_batches, dropout_key, loss_scale)
            log_traced(zero_leaves, looked_up)
            new_params, new_opt_state, opt_out = optimizer.step(
                params, grads, opt_state, compute_dtype=self.compute_dtype
            )
            return new_params, new_opt_state, loss, metrics, opt_out

        return _jit_train_step(step, optimizer, donate)

    def build_eval_step(self, loss_function: Callable) -> Callable:
        def eval_step(params, micro_batch):
            ctx = self._make_ctx(deterministic=True, dropout_key=None)
            out = self.forward(params, micro_batch, ctx)
            loss, metrics = loss_function(out, micro_batch)
            return loss, metrics

        return jax.jit(eval_step)

    # ------------------------------------------------ inference forward
    def build_forward(self, deterministic: bool = True) -> Callable:
        def fwd(params, x):
            ctx = self._make_ctx(deterministic=deterministic, dropout_key=None)
            return self.forward(params, x, ctx)

        return jax.jit(fwd)

    def shard_params(self, params: dict, fsdp_data_axis: bool = False) -> dict:
        """Place params on the mesh according to their metas.

        ``fsdp_data_axis`` (ZeRO stage 3) additionally shards every param
        over the data axis on its last free divisible dim — GSPMD inserts
        the per-use all-gather in forward/backward and the transposed
        reduce-scatter for the grads, so per-device parameter memory drops
        by ~dp while the step math is unchanged."""
        if self.topology is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .sharding import spec_with_data_axis

        metas = self.param_metas()
        dp = self.topology.data_parallel_size if fsdp_data_axis else 1

        def place(p, m):
            spec = m.partition_spec
            if fsdp_data_axis:
                spec = spec_with_data_axis(spec, p.shape, dp)
            return jax.device_put(p, NamedSharding(self.topology.mesh, P(*spec)))

        return jax.tree.map(
            place, params, metas, is_leaf=lambda x: isinstance(x, ParamMeta)
        )

    def shard_batch(self, batch: Any, stacked: bool = True) -> Any:
        """Place a batch on the mesh: the batch axis shards over ``data``.

        ``stacked=True`` for train batches with a leading grad-accum axis
        (gas, dp*mbs, ...); False for single micro batches (dp*mbs, ...).

        Multi-host: every process passes the same full global batch (the
        loader stream is a pure function of seed + consumed samples, so
        identical on all hosts) and each host materializes only the slices
        its own devices hold — the JAX equivalent of the reference's
        broadcast_data + DP-strided loader split (broadcast_data.py:103,
        dataloader.py:69-80).
        """
        if self.topology is None:
            return batch
        from jax.sharding import NamedSharding, PartitionSpec as P

        # batch dims shard over data; the sequence dim (first after batch)
        # shards over the context axis for ring attention (no-op at cp=1)
        lead = (None, "data", "context") if stacked else ("data", "context")
        multiprocess = jax.process_count() > 1
        batch_axis = 1 if stacked else 0
        global_batch = (
            self.topology.micro_batch_size * self.topology.data_parallel_size
        )

        def put(x):
            if not hasattr(x, "ndim") or x.ndim < len(lead) - 1:
                return x
            spec = lead[: x.ndim] + (None,) * (x.ndim - len(lead))
            sharding = NamedSharding(self.topology.mesh, P(*spec))
            if multiprocess:
                # every host must pass the same FULL global batch: a
                # per-rank slice has a locally-consistent shape too, so
                # without this guard each host would silently train on
                # different data under one "global" array
                if x.ndim > batch_axis and x.shape[batch_axis] != global_batch:
                    raise ValueError(
                        f"multi-host shard_batch needs the full global batch "
                        f"(dim {batch_axis} == micro_batch_size * dp = "
                        f"{global_batch}), got shape {x.shape}; do not feed "
                        "per-dp_rank slices here"
                    )
                # device_put cannot target non-addressable devices; the
                # callback is invoked only for this host's shard indices
                x_np = np.asarray(x)
                return jax.make_array_from_callback(
                    x_np.shape, sharding, lambda idx: x_np[idx]
                )
            return jax.device_put(x, sharding)

        return jax.tree.map(put, batch)
