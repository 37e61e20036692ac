"""Mixed-precision AdamW with ZeRO-1 state sharding.

Capability parity with the reference's optimizer stack
(reference: src/scaling/core/optimizer/optimizer.py:37-734,
parameter_group.py:81-667): AdamW (torch semantics incl. bias correction and
decoupled weight decay), fp32 master weights with low-precision compute
params, per-group weight decay + LR schedules (separate embedding LR),
global-grad-norm clipping, dynamic loss scaling with overflow step-skip.

TPU-native re-design: the whole step is one pure function inside jit. The
reference's ZeRO-1 machinery — NCCL-aligned flat buffers, DP partitions,
grad copy prequel, all-gather sequel (parameter_group.py:26-472) — is
replaced by sharding the fp32 master + moment trees over the ``data`` mesh
axis with ``NamedSharding``. The compute copy lives in the same placement
between steps: ``step`` constrains each gradient to it (a reduce-scatter) and
returns the new copy as the shard it was cast from; the NEXT step gathers it
once on entry (``gather_params``), under the forward, where the reference
gathers at the end of ``step()`` with nothing left to hide it. Overflow skip
uses ``jnp.where`` on the whole state instead of aborting the step.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field, model_validator

from ..config import BaseConfig
from ..nn.param import ParamMeta
from ..topology.topology import DATA_AXIS, Topology
from .learning_rate_scheduler import LearningRateScheduler, LearningRateSchedulerConfig
from .loss_scaler import (
    LossScaler,
    LossScalerConfig,
    LossScalerState,
    has_inf_or_nan_tree,
)


class OptimizerConfig(BaseConfig):
    beta1: float = Field(
        0.9,
        description="First coefficient used for computing running averages of "
        "gradient and its square",
    )
    beta2: float = Field(
        0.95,
        description="Second coefficient used for computing running averages of "
        "gradient and its square",
    )
    eps: float = Field(
        1e-8,
        description="term added to the denominator to improve numerical stability",
    )
    gradient_clipping: float = Field(
        0.0, description="clip global l2 grads to this value, deactivate if 0.0"
    )
    allreduce_bucket_size: int = Field(
        500000000,
        description="number of floating points to allreduce in one go "
        "(kept for config parity; XLA schedules collectives itself)",
    )
    loss_scaler: LossScalerConfig = Field(
        LossScalerConfig(), description="Configuration of the loss scaler"
    )
    zero: bool = Field(
        False,
        description="enable zero stage 1: shard fp32 master weights and moments "
        "over the data axis",
    )
    zero_stage: int = Field(
        1,
        description="with zero enabled: 1 shards only optimizer state "
        "(reference surface); 3 additionally shards the COMPUTE params over "
        "the data axis (FSDP — beyond the reference), with GSPMD inserting "
        "the per-use all-gather and the grad reduce-scatter. Stage 2 is "
        "implicit in SPMD (grads never materialize unsharded) and is "
        "rejected.",
        ge=1,
        le=3,
    )
    zero_save_static: bool = Field(
        False,
        description="kept for config parity (reference optimizer_config.py:36): "
        "checkpoints here always save per-layer unsharded arrays, so there is "
        "no merge step to skip",
    )
    debug_log: bool = Field(False, description="per-parameter grad/weight norms")

    @model_validator(mode="after")
    def _validate_zero_stage(self):
        if self.zero_stage == 2:
            raise ValueError(
                "zero_stage 2 is implicit under GSPMD (gradients are "
                "reduce-scattered, never materialized unsharded); use 1 or 3"
            )
        if self.zero_stage != 1 and not self.zero:
            raise ValueError(
                f"zero_stage {self.zero_stage} requires zero: true — "
                "without it the stage setting would silently no-op"
            )
        return self


AdamWOptimizerConfig = OptimizerConfig  # reference alias


class OptimizerParamGroup:
    """Named parameter subset with its own weight decay and LR schedule.

    Membership is by ``ParamMeta.key``; ``parameters`` may be a sub-tree
    mask produced by the model's ``get_parameter_groups``.
    """

    def __init__(
        self,
        keys: set[str],
        weight_decay: float = 0.0,
        learning_rate_scheduler: Optional[LearningRateSchedulerConfig] = None,
        name: str = "param_group",
        lr_scale: float = 1.0,
    ):
        self.keys = set(keys)
        self.weight_decay = weight_decay
        self.lr_config = learning_rate_scheduler or LearningRateSchedulerConfig()
        self.scheduler = LearningRateScheduler(self.lr_config)
        self.name = name
        # constant multiplier on the scheduled LR; muP width scaling rides
        # here (models/transformer/model.py get_parameter_groups)
        self.lr_scale = lr_scale


class OptimizerState(NamedTuple):
    step: jax.Array  # i32, number of completed optimizer steps
    master: Any  # fp32 master params pytree
    exp_avg: Any
    exp_avg_sq: Any
    loss_scaler: LossScalerState


class OptimizerStepOutput(NamedTuple):
    global_grad_norm: Optional[jax.Array] = None
    global_grad_norm_clipped: Optional[jax.Array] = None
    learning_rates: Optional[dict] = None
    overflow: Optional[jax.Array] = None
    no_overflow_steps: Optional[jax.Array] = None
    current_loss_scale: Optional[jax.Array] = None
    debug_dict: Optional[dict] = None


class Optimizer:
    """AdamW over (params, metas) trees, grouped by ParamMeta.key."""

    def __init__(
        self,
        config: OptimizerConfig,
        parameter_groups: list[OptimizerParamGroup],
        metas: Any,
        topology: Optional[Topology] = None,
    ):
        self.config = config
        self.parameter_groups = parameter_groups
        self.metas = metas
        self.topology = topology
        self.loss_scaler = LossScaler(config.loss_scaler)

        # leaf -> group index (-1 = frozen / not optimized)
        meta_leaves = jax.tree.leaves(
            metas, is_leaf=lambda x: isinstance(x, ParamMeta)
        )
        self._group_index: list[int] = []
        claimed: set[str] = set()
        for m in meta_leaves:
            gi = -1
            for i, g in enumerate(parameter_groups):
                if m.key in g.keys:
                    gi = i
                    claimed.add(m.key)
                    break
            self._group_index.append(gi)
        all_keys = {k for g in parameter_groups for k in g.keys}
        missing = all_keys - claimed
        if missing:
            raise ValueError(f"parameter group keys not found in model: {sorted(missing)[:5]}")
        self._meta_leaves = meta_leaves
        self._treedef = jax.tree.structure(
            metas, is_leaf=lambda x: isinstance(x, ParamMeta)
        )

    # --------------------------------------------------------------- state
    def _master_sharding(self, meta: ParamMeta, shape: tuple):
        """ZeRO: additionally shard the master/moments over the data axis
        (the rule shared with stage-3 param sharding — aligned placements
        mean the master->param cast needs no resharding)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import spec_with_data_axis

        if self.topology is None:
            return None
        spec = meta.partition_spec
        if self.config.zero:
            spec = spec_with_data_axis(
                spec, shape, self.topology.data_parallel_size
            )
        return NamedSharding(self.topology.mesh, P(*spec))

    def gathers_on_entry(self) -> bool:
        """ZeRO stage 1 over a data axis wider than 1: the compute copy
        crosses the step boundary as the data shard its master lives on and
        the step gathers it once, on entry. (Stage 3 keeps it so as well,
        and lets GSPMD gather it at each use.)"""
        return (
            self.topology is not None
            and self.config.zero
            and self.config.zero_stage == 1
            and self.topology.data_parallel_size > 1
        )

    def _between_steps_sharding(self, leaf, meta: ParamMeta, gi: int):
        """Where an optimized leaf's compute copy lives between steps under
        ZeRO (the masters' placement); None for a leaf that stays where it
        is: no mesh, no ZeRO, frozen (no master)."""
        if self.topology is None or not self.config.zero or gi < 0:
            return None
        return self._master_sharding(meta, leaf.shape)

    def place_params(self, params: Any, donate: bool = False) -> Any:
        """``params`` as a ZeRO step takes and returns them. A leaf already
        in the masters' placement passes untouched; those placed by their
        own spec are put there together, by ONE jitted identity (every chip
        keeps a slice of what it holds: no traffic; leaf by leaf through
        ``device_put`` the 116 leaves of a 7B cell took 11 s on four chips),
        so that a caller who placed the weights without the data axis still
        meets the ONE program the step lowers. ``donate`` deletes the leaves
        that were moved, as the step's donation would have: both copies and
        a step's temporaries do not fit beside a 7B cell's state. Shapes
        (``ShapeDtypeStruct``) are re-labelled, for ``lower``."""
        leaves, td = jax.tree.flatten(params)
        move, targets = [], []
        for i, (p, m, gi) in enumerate(
            zip(leaves, self._meta_leaves, self._group_index)
        ):
            sh = self._between_steps_sharding(p, m, gi)
            if sh is None:
                continue
            if isinstance(p, jax.ShapeDtypeStruct):
                leaves[i] = jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=sh)
            elif not (
                isinstance(p, jax.Array) and p.sharding.is_equivalent_to(sh, p.ndim)
            ):
                move.append(i)
                targets.append(sh)
        if move:
            sources = [leaves[i] for i in move]
            moved = jax.jit(lambda xs: xs, out_shardings=targets)(sources)
            for i, new, old in zip(move, moved, sources):
                leaves[i] = new
                if donate and isinstance(old, jax.Array):
                    old.delete()
        return jax.tree.unflatten(td, leaves)

    def gather_params(self, params: Any) -> tuple[Any, int, int]:
        """Inside a jitted step, before anything consumes ``params``: ZeRO-1's
        ONE gather of each optimized leaf from the masters' placement to its
        own spec. Said outside ``value_and_grad`` it happens once a step (the
        backward reads the gathered copy), bf16 on the wire, and depends on
        nothing but its consumer, so the compiler can run a layer's gather
        under the layers before it. A leaf that its layer consumes ON the
        shard (``lookup_on_data_shard``: an untied embedding table, of which
        a step reads a few thousand rows) stays where it is, and its gradient
        comes back data-reduced in the same placement. Returns the tree, how
        many leaves it gathered (``step`` scatters as many gradients back)
        and how many it left for a lookup on the shard: 0 and 0 without
        ZeRO-1 over a data axis."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.sharding import lookup_on_data_shard

        if not self.gathers_on_entry():
            return params, 0, 0
        leaves, td = jax.tree.flatten(params)
        gathered = looked_up = 0
        for i, (p, m, gi) in enumerate(
            zip(leaves, self._meta_leaves, self._group_index)
        ):
            own = NamedSharding(self.topology.mesh, P(*m.partition_spec))
            # frozen, or no dimension the data axis divides: nothing to move
            if gi < 0 or self._master_sharding(m, p.shape).is_equivalent_to(
                own, p.ndim
            ):
                continue
            if lookup_on_data_shard(
                m, p.shape, self.topology.mesh, self.gathers_on_entry()
            ):
                looked_up += 1
                continue
            leaves[i] = jax.lax.with_sharding_constraint(p, own)
            gathered += 1
        return jax.tree.unflatten(td, leaves), gathered, looked_up

    def abstract_state(self, params: Any) -> OptimizerState:
        """``init_state``'s output as ShapeDtypeStructs with the ZeRO
        master shardings attached.

        ``jax.eval_shape(init_state, ...)`` drops shardings, which would
        let an AOT compile place the fp32 masters replicated — hiding
        exactly the per-chip memory ZeRO-1 exists to shard. This keeps the
        placement so huge layouts (the BASELINE #4 7B at TP×PP×DP) can be
        ``step.lower(...)``-compiled and cost/memory-pinned without
        materializing 12 bytes/param."""
        empty = jax.ShapeDtypeStruct((0,), jnp.float32)
        masters = []
        for p, m, gi in zip(
            jax.tree.leaves(params), self._meta_leaves, self._group_index
        ):
            if gi < 0:
                masters.append(empty)
                continue
            sh = self._master_sharding(m, p.shape)
            masters.append(
                jax.ShapeDtypeStruct(p.shape, jnp.float32, sharding=sh)
                if sh is not None
                else jax.ShapeDtypeStruct(p.shape, jnp.float32)
            )
        tree = jax.tree.unflatten(self._treedef, masters)
        return OptimizerState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            master=tree,
            exp_avg=tree,
            exp_avg_sq=tree,
            loss_scaler=jax.eval_shape(self.loss_scaler.init_state),
        )

    def init_state(self, params: Any, only=None) -> OptimizerState:
        """Fresh state (fp32 masters from ``params``, zero moments).

        ``only`` (an optional ``ParamMeta -> bool`` predicate) limits real
        allocation to matching leaves; the rest get the same cheap ``(0,)``
        placeholders as frozen params. Callers that graft a fresh SUBTREE
        into loaded state (the pretrained-CLIP splice) use it to avoid
        transiently materializing 12 bytes/param for the whole model."""

        def make_master(p, m, gi):
            # explicit copy: astype is a no-op for fp32 params and the master
            # must not alias the compute params (donation would double-free)
            x = jnp.array(p, dtype=jnp.float32, copy=True)
            sh = self._master_sharding(m, x.shape)
            return jax.device_put(x, sh) if sh is not None else x

        p_leaves = jax.tree.leaves(params)
        masters, avgs, avg_sqs = [], [], []
        # one fresh (0,) buffer per slot: a shared placeholder would be the
        # same buffer donated many times in the jitted step (XLA rejects it)
        empty = lambda: jnp.zeros((0,), dtype=jnp.float32)  # noqa: E731
        for p, m, gi in zip(p_leaves, self._meta_leaves, self._group_index):
            if gi < 0 or (only is not None and not only(m)):
                # frozen (or outside the requested subtree): no fp32 master
                # or moments — a 7B frozen backbone would otherwise burn
                # 12 bytes/param of device memory
                masters.append(empty())
                avgs.append(empty())
                avg_sqs.append(empty())
                continue
            masters.append(make_master(p, m, gi))
            sh = self._master_sharding(m, p.shape)

            def zeros():
                z = jnp.zeros(p.shape, dtype=jnp.float32)
                return jax.device_put(z, sh) if sh is not None else z

            avgs.append(zeros())
            avg_sqs.append(zeros())
        unflatten = lambda ls: jax.tree.unflatten(self._treedef, ls)  # noqa: E731
        scalars = (jnp.asarray(0, jnp.int32), self.loss_scaler.init_state())
        if self.topology is not None:
            # replicate the scalars on the mesh like every step's outputs
            # will be: left off the mesh they key a second trace and a
            # second full compile of the train step at step 2 (27 s of the
            # first on-chip run, PERF.md "First on-chip run")
            from jax.sharding import NamedSharding, PartitionSpec as P

            scalars = jax.device_put(
                scalars, NamedSharding(self.topology.mesh, P())
            )
        return OptimizerState(
            step=scalars[0],
            master=unflatten(masters),
            exp_avg=unflatten(avgs),
            exp_avg_sq=unflatten(avg_sqs),
            loss_scaler=scalars[1],
        )

    # ---------------------------------------------------------------- step
    def scale_loss(self, loss: jax.Array, state: OptimizerState) -> jax.Array:
        return self.loss_scaler.scale_loss(loss, state.loss_scaler)

    def freeze_frozen_params(self, params: Any) -> Any:
        """stop_gradient every leaf that belongs to no parameter group.

        A PEFT step would otherwise compute, DP-sync and overflow-check
        full model-sized gradients that ``step`` then drops on the floor:
        the frozen weight-grad matmuls stay live because
        ``has_inf_or_nan_tree`` consumes every grad leaf, and GSPMD's
        gradient psum over the data axis rides along with them (measured
        at TP=2 × DP=4: LoRA's collective bytes *exceeded* full
        finetuning's). With frozen leaves stopped inside the loss, their
        gradients are constant zeros and XLA deletes the matmuls and
        collectives outright — backward cost scales with the adapters,
        which is the point of BASELINE #5's PEFT layout.

        Deliberate loss-scaling consequence: under fp16 dynamic scaling,
        a non-finite value confined to a FROZEN leaf's gradient no longer
        trips ``has_inf_or_nan_tree`` (the leaf's grad is now a constant
        zero rather than inf/nan), so it causes neither a skipped step nor
        a scale backoff. That is correct — those gradients were discarded
        anyway, and an overflow that only a dropped tensor would have seen
        should not perturb the training of the live adapters. Covered by
        ``test_frozen_leaf_overflow_invisible_to_scaler``."""
        if all(gi >= 0 for gi in self._group_index):
            return params
        leaves, td = jax.tree.flatten(params)
        return jax.tree.unflatten(
            td,
            [
                leaf if gi >= 0 else jax.lax.stop_gradient(leaf)
                for leaf, gi in zip(leaves, self._group_index)
            ],
        )

    def step(
        self,
        params: Any,
        grads: Any,
        state: OptimizerState,
        compute_dtype=None,
    ) -> tuple[Any, OptimizerState, OptimizerStepOutput]:
        c = self.config
        g_leaves = jax.tree.leaves(grads)
        if self.gathers_on_entry():
            # each gradient onto the shard that consumes it, BEFORE the
            # overflow check and the norm read it: a reduce-scatter over the
            # data axis, then a sum over the shard and a scalar all-reduce,
            # never an all-reduce onto every data rank that then uses its
            # 1/dp. (The chip's compiler derived as much from the masters'
            # placement, fused as ``all-reduce-scatter``; said here it does
            # not hang on what propagation finds.) The gradient of a leaf
            # looked up on its shard (``gather_params``) is there already:
            # for it this is the identity.
            g_leaves = [
                jax.lax.with_sharding_constraint(g, self._master_sharding(m, g.shape))
                if gi >= 0
                else g
                for g, m, gi in zip(g_leaves, self._meta_leaves, self._group_index)
            ]
        p_leaves = jax.tree.leaves(params)
        m_leaves = jax.tree.leaves(state.master)
        a_leaves = jax.tree.leaves(state.exp_avg)
        s_leaves = jax.tree.leaves(state.exp_avg_sq)

        # ---- overflow check on the raw (scaled) grads. The step-skip only
        # applies under dynamic loss scaling (reference semantics: without a
        # scaler a non-finite grad propagates loudly instead of freezing the
        # run); the raw flag is always surfaced in the output.
        raw_overflow = has_inf_or_nan_tree(g_leaves)
        overflow = raw_overflow if c.loss_scaler.enable else jnp.asarray(False)
        scaler_state, scaler_out = self.loss_scaler.step(state.loss_scaler, overflow)

        # ---- unscale
        inv_scale = jnp.where(
            jnp.asarray(c.loss_scaler.enable),
            1.0 / state.loss_scaler.current_scale,
            1.0,
        ).astype(jnp.float32)
        g32 = [g.astype(jnp.float32) * inv_scale for g in g_leaves]

        # ---- global grad norm over optimized leaves
        sq = [
            jnp.sum(jnp.square(g))
            for g, gi in zip(g32, self._group_index)
            if gi >= 0
        ]
        global_norm = jnp.sqrt(jnp.sum(jnp.stack(sq))) if sq else jnp.asarray(0.0)
        if c.gradient_clipping > 0.0:
            clip_coeff = jnp.minimum(
                1.0, c.gradient_clipping / (global_norm + 1e-6)
            )
            g32 = [g * clip_coeff for g in g32]
            clipped_norm = jnp.minimum(global_norm, c.gradient_clipping)
        else:
            clipped_norm = global_norm

        # ---- per-group learning rates at step+1 (reference steps then logs)
        step_index = state.step + 1
        group_lrs = [
            g.scheduler.get_lr(step_index) * g.lr_scale
            for g in self.parameter_groups
        ]

        beta1, beta2 = c.beta1, c.beta2
        t = step_index.astype(jnp.float32)
        bc1 = 1.0 - beta1**t
        bc2 = 1.0 - beta2**t

        new_p, new_m, new_a, new_s = [], [], [], []
        for p, g, master, avg, avg_sq, gi, m in zip(
            p_leaves, g32, m_leaves, a_leaves, s_leaves, self._group_index,
            self._meta_leaves,
        ):
            if gi < 0:  # frozen
                new_p.append(p)
                new_m.append(master)
                new_a.append(avg)
                new_s.append(avg_sq)
                continue
            lr = group_lrs[gi].astype(jnp.float32)
            # decoupled decay uses lr*wd, so an lr_scale (muP width rule)
            # would silently rescale regularization too; dividing wd by the
            # scale keeps lr*wd — the decay actually applied — exactly as
            # tuned at the base width ("independent weight decay")
            grp = self.parameter_groups[gi]
            wd = grp.weight_decay / grp.lr_scale
            m2 = master * (1.0 - lr * wd) if wd else master
            a2 = beta1 * avg + (1.0 - beta1) * g
            s2 = beta2 * avg_sq + (1.0 - beta2) * jnp.square(g)
            denom = jnp.sqrt(s2) / jnp.sqrt(bc2) + c.eps
            m2 = m2 - (lr / bc1) * a2 / denom
            # overflow => keep everything unchanged (step skip)
            m2 = jnp.where(overflow, master, m2)
            a2 = jnp.where(overflow, avg, a2)
            s2 = jnp.where(overflow, avg_sq, s2)
            new_m.append(m2)
            new_a.append(a2)
            new_s.append(s2)
            p2 = m2.astype(compute_dtype or p.dtype)
            between_steps = self._between_steps_sharding(p2, m, gi)
            if between_steps is not None:
                # the new compute copy stays on the shard it was cast from
                # (no traffic) and crosses the step boundary there: the next
                # step's entry gathers it (``gather_params``), where there is
                # compute to hide the gather under. Said out loud so that the
                # step's outputs are placed as ``place_params`` places its
                # inputs, whatever the compiler would have chosen: another
                # placement is a second executable at step 2
                p2 = jax.lax.with_sharding_constraint(p2, between_steps)
            new_p.append(p2)

        unflatten = lambda ls: jax.tree.unflatten(jax.tree.structure(params), ls)  # noqa: E731
        new_state = OptimizerState(
            step=jnp.where(overflow, state.step, state.step + 1),
            master=unflatten(new_m),
            exp_avg=unflatten(new_a),
            exp_avg_sq=unflatten(new_s),
            loss_scaler=scaler_state,
        )
        debug = None
        if c.debug_log:
            debug = {
                m.key: jnp.sqrt(jnp.sum(jnp.square(g)))
                for m, g in zip(self._meta_leaves, g32)
            }
        output = OptimizerStepOutput(
            global_grad_norm=global_norm,
            global_grad_norm_clipped=clipped_norm,
            learning_rates={
                g.name: lr for g, lr in zip(self.parameter_groups, group_lrs)
            },
            overflow=scaler_out.overflow if c.loss_scaler.enable else raw_overflow,
            no_overflow_steps=scaler_out.no_overflow_steps if c.loss_scaler.enable else None,
            current_loss_scale=scaler_out.current_loss_scale if c.loss_scaler.enable else None,
            debug_dict=debug,
        )
        return unflatten(new_p), new_state, output
