"""Generic training loop + checkpoint orchestration.

(reference: src/scaling/core/trainer/trainer.py:33-558). ``run_training``
drives: jitted train step -> periodic save -> periodic eval -> rank-0 metric
logging. Checkpoint directories follow the reference layout:
``save_dir/global_step{N}/`` with model/optimizer/context artifacts plus a
``latest`` pointer file, so tooling built around reference checkpoints keeps
working.
"""

from __future__ import annotations

import json
import os
import shutil
import time
import zipfile
from enum import Enum
from pathlib import Path
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from pydantic import Field, model_validator

from ..checkpoint import (
    AsyncCheckpointWriter,
    load_model_checkpoint,
    load_optimizer_checkpoint,
    save_model_checkpoint,
    save_optimizer_checkpoint,
)
from ..config import BaseConfig
from ..context import BaseContext
from ..data import DataLoader
from ..logging import logger
from ..obs import StepTelemetry, get_registry, process_start_s, span
from ..optimizer.optimizer import Optimizer, OptimizerState
from ..parallel.parallel_module import (
    EvaluationStepOutput,
    ParallelModule,
    TrainStepOutput,
)
from ..resilience import (
    CheckpointCommit,
    NonFiniteGuard,
    NonFiniteLossError,
    StepStallWatchdog,
    get_fault_plan,
    retry_io,
)
from ..resilience.controlplane import (
    ABORT_FLAG,
    ENV_NUM_HOSTS,
    PREEMPT_FLAG,
    STALL_FLAG,
    ControlPlane,
    JobAborted,
    straggler_table,
)
from ..resilience.manifest import CheckpointCorruptionError, read_manifest
from ..resilience.meshmeta import (
    build_mesh_meta,
    param_record,
    read_mesh_meta,
    write_mesh_meta,
)
from ..resilience.reshard import (
    fire_reshard_point,
    rescale_consumed_samples,
    reshard_plan,
)
from ..resilience.restore import checkpoint_candidates, verify_checkpoint

# disk-corruption error types the load fallback may skip past; everything
# else (shape/config mismatches, OOMs, assertion errors) aborts the resume
_CORRUPT_LOAD_ERRORS = (
    zipfile.BadZipFile,
    EOFError,
    OSError,
    CheckpointCorruptionError,
    json.JSONDecodeError,
)


class CheckpointBackend(Enum):
    NPZ = "npz"
    ORBAX = "orbax"


class TrainerConfig(BaseConfig):
    save_dir: Optional[str] = Field(None, description="directory for saving checkpoints")
    save_interval: Optional[int] = Field(
        None,
        description="save a checkpoint every 'save_interval' steps to save_dir, "
        "iff save_dir is defined",
    )
    load_dir: Optional[str] = Field(None, description="directory for loading checkpoints")
    train_iterations: Optional[int] = Field(None, description="train for this number of iterations")
    assert_checkpoint_loaded: bool = Field(
        True, description="error out if a checkpoint could not be loaded"
    )
    load_optimizer_states: bool = Field(
        True, description="load optimizer states on checkpoint load"
    )
    delete_past_optimizer_states: bool = Field(
        True,
        description="Deletes optimizer states on the last n-1 checkpoints right "
        "after saving the nth checkpoint",
    )
    load_context: bool = Field(
        True,
        description="load context state, i.e. train iterations, consumed train "
        "and eval samples on checkpoint load",
    )
    allowed_missing_keys_in_checkpoint: Optional[List[str]] = Field(
        None,
        description="list of parameter name regexes that may not be present in an "
        "existing checkpoint (e.g. fresh adapters)",
    )
    allowed_unexpected_keys_in_checkpoint: Optional[List[str]] = Field(
        None,
        description="list of parameter name regexes that may be present in an "
        "existing checkpoint but not be loaded",
    )
    ignore_keys_in_checkpoint: Optional[List[str]] = Field(
        None,
        description="list of parameter name regexes for which pretrained weights "
        "are not loaded (reinitialise parts of a model)",
    )
    merge_lora_after_loading_checkpoint: bool = Field(
        False, description="merge LoRa weights after loading"
    )
    seed: int = Field(42, description="")
    log_interval: int = Field(
        1,
        description="fetch and log step metrics every n steps. Intermediate "
        "steps skip the device-to-host sync entirely, so consecutive steps "
        "chain on-device and host latency leaves the critical path "
        "(the reference logs every step; 1 keeps that behavior). Steps "
        "inside an active profiler window always sync so recorded step "
        "times stay honest",
        ge=1,
    )
    eval_iterations: int = Field(0, description="number of eval micro batches per eval pass")
    eval_interval: Optional[int] = Field(None, description="evaluate every n train steps")
    dataloader_num_workers: int = Field(0, description="kept for config parity")
    dataloader_pin_memory: bool = Field(True, description="kept for config parity")
    dataloader_prefetch_factor: Optional[int] = Field(
        None,
        description="prefetch up to this many micro-batch stacks on a "
        "background thread, overlapping host-side batch assembly with the "
        "device step; None/0 loads synchronously. Resume exactness is "
        "unaffected: the stream is a pure function of (seed, "
        "consumed_samples) and prefetched-but-unconsumed batches are "
        "rebuilt on restart",
        ge=0,
    )
    save_checkpoint_async: bool = Field(
        False,
        description="write checkpoint files on a background thread; the train "
        "loop only blocks for the device-to-host gather",
    )
    strict_checkpoint_load: bool = Field(
        False,
        description="fail on the FIRST checkpoint that flunks integrity "
        "verification instead of falling back to the newest older valid "
        "one — for runs where silently resuming from an earlier step "
        "would invalidate the experiment",
    )
    multihost_shared_save_dir: bool = Field(
        False,
        description="multi-host supervision: save_dir is ONE tree shared "
        "by every host (orbax on shared storage) — only host 0 advances "
        "`latest`, after the cross-host commit barrier. False means "
        "per-host shard dirs where every host owns its own pointer. "
        "Only read when a control plane is attached",
    )
    max_consecutive_nonfinite: Optional[int] = Field(
        None,
        description="non-finite policy budget: tolerate up to this many "
        "CONSECUTIVE overflow/NaN steps (the loss scaler already turns "
        "each into a no-op update), then save a checkpoint and abort "
        "with a diagnosis. None disables. Only fetched steps are "
        "observed — with log_interval > 1 the streak is counted at "
        "fetch granularity",
        ge=0,
    )
    step_timeout_seconds: Optional[float] = Field(
        None,
        description="step-stall watchdog: if a train-loop iteration "
        "makes no progress for this long, dump every thread's stack "
        "(hung collective / wedged storage forensics) and flag "
        "preemption so the loop saves-and-exits at the next safe "
        "point. None disables",
        gt=0,
    )
    io_retry_attempts: int = Field(
        3,
        description="bounded retry for transient dataloader read "
        "failures (exponential backoff; checkpoint writes retry with "
        "the same default independently)",
        ge=1,
    )
    io_retry_backoff_seconds: float = Field(
        0.05, description="base backoff delay for dataloader read retries",
        ge=0,
    )
    deep_checkpoint_verification: bool = Field(
        True,
        description="verify crc32 digests of every manifest-listed file "
        "before restoring (catches bit rot / torn writes). False checks "
        "existence+size only — for very large checkpoints on slow "
        "shared storage where a full read per restore is prohibitive",
    )
    checkpoint_backend: CheckpointBackend = Field(
        CheckpointBackend.NPZ,
        description="'npz': layout-independent per-layer files, host-gathered "
        "(the golden format; supports non-strict PEFT loading). 'orbax': "
        "tensorstore-backed sharded save/restore — every host writes only "
        "its own shards and restore re-shards to the current mesh, the "
        "multi-host-scale path (requires exact key match; checkpoints keep "
        "the same per-layer canonical tree, so pp/mp relayouts still load)",
    )

    @model_validator(mode="after")
    def _validate_backend(self):
        if (
            self.checkpoint_backend == CheckpointBackend.ORBAX
            and self.save_checkpoint_async
        ):
            raise ValueError(
                "save_checkpoint_async is not supported with the orbax "
                "backend yet: its tensorstore write is synchronous, which "
                "would silently break the async contract — disable one"
            )
        return self


class BaseTrainer:
    """Wires module/optimizer/datasets; owns the train loop."""

    def __init__(
        self,
        config: TrainerConfig,
        context: BaseContext,
        parallel_module: ParallelModule,
        optimizer: Optimizer,
        loss_function: Callable,
        dataset: Any = None,
        dataset_evaluation: Any = None,
        metrics_aggregation_fn: Optional[Callable] = None,
        batch_to_model_input: Callable = lambda b: b,
        profiler: Any = None,
    ):
        self.profiler = profiler
        self.config = config
        self.context = context
        self.module = parallel_module
        self.optimizer = optimizer
        self.loss_function = loss_function
        self.dataset = dataset
        self.dataset_evaluation = dataset_evaluation
        self.batch_to_model_input = batch_to_model_input
        self.topology = context.topology

        self.params: Any = None
        self.opt_state: Optional[OptimizerState] = None
        # log_interval bookkeeping: steps dispatched since the last
        # device->host fetch, and the wall clock of that fetch (for
        # amortized per-step durations)
        self._unfetched_steps = 0
        self._last_fetch_wall: Optional[float] = None
        # bookkeeping from the last load_checkpoint: which model keys were
        # actually taken from the checkpoint (None = no checkpoint loaded)
        # and whether optimizer moments survived the load — startup splices
        # (pretrained CLIP) gate on these
        self.restored_model_keys: Optional[set] = None
        self.optimizer_states_loaded: bool = False
        self._ckpt_writer: Optional[AsyncCheckpointWriter] = None
        self._prefetch_queue: Any = None
        self._prefetch_thread: Any = None
        self._prefetch_stop: Any = None
        self._train_step = None
        self._eval_step = None
        self.dataloader: Optional[DataLoader] = None
        self.dataloader_evaluation: Optional[DataLoader] = None
        # generic cluster hook points (Determined glue attaches here; any
        # scheduler integration can): an extra preemption predicate polled
        # every step, metric sinks called after logging, and checkpoint
        # sinks called with each finished step dir
        self.external_preemption: Optional[Callable[[], bool]] = None
        self.metrics_hooks: List[Callable[[dict, int], None]] = []
        self.checkpoint_hooks: List[Callable[[Path, int], None]] = []
        self._preempted = False
        # per-step telemetry (docs/OBSERVABILITY.md): hardware gauges,
        # step-time EMA, and — once configure() declared the model's
        # FLOPs-per-token — achieved-TFLOPs/MFU; flushed to the metrics
        # JSONL sink on every fetched step. Host-side only by contract.
        self.telemetry = StepTelemetry()
        # multi-host supervision (attach_control_plane): out-of-band
        # heartbeats/barriers/flags beside the XLA collectives
        self._control_plane: Optional[ControlPlane] = None
        self._cp_first_checkin = True
        self._cp_step_barrier = True
        self._cp_barrier_timeout = 300.0
        self._cp_peer_stale = 60.0
        self._cp_latest_leader = True
        self._cp_prev_commit_step: Optional[int] = None
        self._last_saved_step: Optional[int] = None
        self._nonfinite_guard: Optional[NonFiniteGuard] = (
            NonFiniteGuard(config.max_consecutive_nonfinite)
            if config.max_consecutive_nonfinite is not None
            else None
        )

    # ------------------------------------------------------------ lifecycle
    def initialize(
        self, load_checkpoint: bool = True, load_dir: Optional[Path | str] = None
    ) -> None:
        self.context.initialize(self.config.seed)
        key = self.context.rng.key("model_init")
        params = self.module.init_params(key)
        params = jax.tree.map(
            lambda p: p.astype(self.module.compute_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating)
            else p,
            params,
        )
        opt_cfg = self.optimizer.config
        fsdp = opt_cfg.zero and opt_cfg.zero_stage == 3
        # under ZeRO the compute copy lives between steps where its master
        # does (stage 1: the step gathers it on entry; stage 3: at each use),
        # so a checkpoint loaded below lands there too (``ckpt_unview``)
        self.params = self.optimizer.place_params(
            self.module.shard_params(params, fsdp_data_axis=fsdp))
        self.opt_state = self.optimizer.init_state(self.params)

        loaded = False
        load_dir = load_dir or self.config.load_dir
        if load_checkpoint and load_dir is not None:
            loaded = self.load_checkpoint(load_dir)
            if self.config.assert_checkpoint_loaded and not loaded:
                raise AssertionError(
                    f"could not load checkpoint from {load_dir}"
                )

        self._build_dataloaders()
        self._train_step = self.module.build_train_step(self.optimizer, self.loss_function)
        self._eval_step = self.module.build_eval_step(self.loss_function)
        if (self.config.dataloader_prefetch_factor or 0) > 0 and self.dataloader is not None:
            self._start_prefetch(self.config.dataloader_prefetch_factor)

    def _start_prefetch(self, depth: int) -> None:
        """Fill a bounded queue of ready micro-batch stacks off-thread.

        The worker runs for the trainer's lifetime (daemon thread): stopping
        mid-stream would desynchronize the dataloader's internal cursor from
        ``consumed_samples`` by discarding already-assembled batches. Every
        already-queued batch is consumed in order by later steps, so
        back-to-back run_training calls see the exact synchronous stream.
        """
        import queue
        import threading

        q = queue.Queue(maxsize=depth)
        stop = threading.Event()
        self._prefetch_queue = q
        self._prefetch_stop = stop

        def worker():
            # closure locals: stop_prefetch may null the attributes while a
            # slow assemble is still in flight
            while not stop.is_set():
                try:
                    item = self._assemble_micro_batches()
                except BaseException as e:  # surfaced on the consumer side
                    item = e
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.2)
                        break
                    except queue.Full:
                        continue
                if isinstance(item, BaseException):
                    if stop.is_set():
                        logger.warning(
                            f"batch prefetch error during shutdown: {item!r}"
                        )
                    return

        self._prefetch_thread = threading.Thread(
            target=worker, name="batch-prefetch", daemon=True
        )
        self._prefetch_thread.start()

    def stop_prefetch(self) -> None:
        """Explicit shutdown (tests / trainer teardown); discards any
        batches still in the queue, so only call when this trainer object
        will not train further."""
        if self._prefetch_stop is not None:
            self._prefetch_stop.set()
        if self._prefetch_thread is not None:
            self._prefetch_thread.join(timeout=5)
        self._prefetch_queue = None
        self._prefetch_thread = None
        self._prefetch_stop = None

    # Deliberately lock-free: ``dataloader`` is assigned here BEFORE
    # ``_start_prefetch`` spawns the worker (thread-start happens-before
    # publishes it) and never reassigned while the worker is live
    # (``stop_prefetch`` joins the thread first).
    # sta: lock(dataloader)
    def _build_dataloaders(self) -> None:
        if self.dataset is not None:
            self.dataloader = DataLoader(
                seed=self.config.seed,
                consumed_samples=self.context.consumed_samples,
                dataset=self.dataset,
                topology=self.topology,
                retry_attempts=self.config.io_retry_attempts,
                retry_backoff=self.config.io_retry_backoff_seconds,
            )
        if self.dataset_evaluation is not None:
            self.dataloader_evaluation = DataLoader(
                seed=self.config.seed,
                consumed_samples=self.context.consumed_eval_samples,
                dataset=self.dataset_evaluation,
                topology=self.topology,
                retry_attempts=self.config.io_retry_attempts,
                retry_backoff=self.config.io_retry_backoff_seconds,
            )

    # ----------------------------------------------------------- train step
    def _assemble_micro_batches(self):
        """Stack grad-accum micro batches along a new leading axis."""
        gas = self.topology.gradient_accumulation_steps
        batches = [
            self.batch_to_model_input(next(self.dataloader)) for _ in range(gas)
        ]
        stacked = jax.tree.map(lambda *xs: np.stack(xs, axis=0), *batches)
        return self.module.shard_batch(stacked)

    def _next_micro_batches(self):
        if self._prefetch_queue is not None:
            item = self._prefetch_queue.get()
            if isinstance(item, BaseException):
                self.stop_prefetch()
                raise item
            return item
        return self._assemble_micro_batches()

    def train_step(self) -> TrainStepOutput:
        step_idx = self.context.iterations
        if (
            self.profiler is not None
            and self.profiler.enabled_at(step_idx)
            and self._unfetched_steps
        ):
            # the profiled window must open with a drained device queue or
            # its first step_time absorbs the unfetched backlog
            jax.block_until_ready(self.opt_state.step)  # sta: disable=STA010
            self._unfetched_steps = 0
            self._last_fetch_wall = time.time()
        if self.profiler is not None:
            self.profiler.begin_step(step_idx)
        start = time.time()
        with span("step.data", step=step_idx):
            micro_batches = self._next_micro_batches()
        t_data = time.time() - start
        dropout_key = self.context.rng.key("dropout", self.context.iterations)
        # dispatch-only span: without a drain it measures how long the
        # host took to hand XLA the fused step, the device work itself
        # shows up in step.sync — adding a drain here is exactly the
        # per-step sync log_interval exists to remove
        with span("step.fwdbwd", step=step_idx):
            self.params, self.opt_state, loss, metrics, opt_out = self._train_step(
                self.params, self.opt_state, micro_batches, dropout_key
            )
        if get_fault_plan().fire("step.nan_grads") == "nan":
            # emulate a transient hardware NaN burst for the non-finite
            # policy: poison only the OBSERVED loss (params stay clean,
            # so "skip and continue" semantics hold exactly)
            loss = jnp.asarray(float("nan"), jnp.float32)
        self.context.step()
        # profiler windows always sync (recorded step times must cover the
        # device work); otherwise log_interval decides whether this step
        # fetches or stays in flight so the next dispatch isn't gated on
        # host latency
        profiling = self.profiler is not None and self.profiler.enabled_at(step_idx)
        # the run's last step always fetches: otherwise a train_iterations
        # that isn't a log_interval multiple ends with the tail steps'
        # metrics (including the final loss) never logged, their device
        # work drained only implicitly by checkpointing
        last_step = (
            self.config.train_iterations is not None
            and self.context.iterations >= self.config.train_iterations
        )
        fetch = profiling or last_step or (
            self.context.iterations % self.config.log_interval == 0
        )
        if not fetch:
            self._unfetched_steps += 1
            return TrainStepOutput(
                loss=loss,
                metrics=metrics,
                global_grad_norm=opt_out.global_grad_norm,
                learning_rates=opt_out.learning_rates,
                overflow=opt_out.overflow,
                no_overflow_steps=opt_out.no_overflow_steps,
                current_loss_scale=opt_out.current_loss_scale,
                step_duration=None,  # dispatch time would masquerade as step time
                fetched=False,
            )
        with span("step.sync", step=step_idx):
            # THE deliberate per-log-interval host sync, inside its own
            # measured span (docs/OBSERVABILITY.md step.sync)
            loss = float(loss)  # sta: disable=STA010
        # a fetch after unfetched steps drains their whole device backlog,
        # so this step's wall time covers several steps of device work;
        # report the amortized per-step time (what tokens/s and the TFLOPs
        # estimators divide by) instead of the ~interval-x drain time
        backlog = self._unfetched_steps
        self._unfetched_steps = 0
        now = time.time()
        if backlog and self._last_fetch_wall is not None:
            step_duration = (now - self._last_fetch_wall) / (backlog + 1)
        else:
            step_duration = now - start
        self._last_fetch_wall = now
        if self.profiler is not None:
            self.profiler.record(
                step_idx,
                {"data_load": t_data, "step_time": step_duration - t_data},
            )
            self.profiler.end_step(step_idx)
        return TrainStepOutput(
            loss=loss,
            metrics={k: float(v) for k, v in metrics.items()},
            global_grad_norm=_maybe_float(opt_out.global_grad_norm),
            learning_rates={k: float(v) for k, v in (opt_out.learning_rates or {}).items()},
            overflow=_maybe_bool(opt_out.overflow),
            no_overflow_steps=_maybe_int(opt_out.no_overflow_steps),
            current_loss_scale=_maybe_float(opt_out.current_loss_scale),
            step_duration=step_duration,
        )

    def eval_step(self) -> EvaluationStepOutput:
        with span("trainer.eval", step=self.context.iterations):
            return self._eval_step_inner()

    def _eval_step_inner(self) -> EvaluationStepOutput:
        start = time.time()
        assert self.dataloader_evaluation is not None, "no evaluation dataset"
        losses, metric_list = [], []
        for _ in range(max(self.config.eval_iterations, 1)):
            batch = self.batch_to_model_input(next(self.dataloader_evaluation))
            batch = self.module.shard_batch(batch, stacked=False)
            loss, metrics = self._eval_step(self.params, batch)
            losses.append(float(loss))
            metric_list.append({k: float(v) for k, v in metrics.items()})
            self.context.consumed_eval_samples += (
                self.topology.config.micro_batch_size
                * self.topology.config.data_parallel_size
            )
        mean_metrics = {
            k: float(np.mean([m[k] for m in metric_list])) for k in metric_list[0]
        } if metric_list else {}
        return EvaluationStepOutput(
            loss=float(np.mean(losses)),
            metrics=mean_metrics,
            step_duration=time.time() - start,
        )

    # ------------------------------------------------------- control plane
    def attach_control_plane(
        self,
        cp: ControlPlane,
        *,
        step_barrier: bool = True,
        barrier_timeout_s: float = 300.0,
        peer_stale_s: float = 60.0,
        shared_save_dir: bool = False,
    ) -> None:
        """Join a multi-host supervision control plane (docs/RESILIENCE.md).

        Per loop iteration this host then: publishes a heartbeat, obeys
        the supervisor's ``abort`` flag (exit fast instead of hanging in
        a collective whose peer is gone), broadcasts/observes the
        ``preempt`` flag (one host's SIGTERM becomes everyone's
        save-and-exit at the SAME step boundary), and — with
        ``step_barrier`` — rendezvouses at ``step-N`` so the preemption
        decision is taken in lockstep even when the step program itself
        would tolerate skew. ``save_checkpoint`` additionally enters the
        ``commit:step-N`` barrier between shard commit and the ``latest``
        advance. ``shared_save_dir=True`` means all hosts write one
        shared checkpoint tree (orbax on shared storage): only host 0
        advances ``latest``; with per-host shard dirs every host owns
        its own pointer, still gated on the same barrier."""
        if not step_barrier and cp.num_hosts > 1:
            # without the lockstep rendezvous nothing bounds step skew,
            # so a drain can end with hosts saving at different steps
            # and parking in commit barriers that never fill
            logger.warning(
                "attach_control_plane(step_barrier=False) on a "
                f"{cp.num_hosts}-host plane: coordinated preemption "
                "cannot guarantee a same-step boundary and commit "
                "barriers may time out during a drain"
            )
        self._control_plane = cp
        self._cp_step_barrier = step_barrier
        self._cp_barrier_timeout = barrier_timeout_s
        self._cp_peer_stale = peer_stale_s
        self._cp_latest_leader = (not shared_save_dir) or cp.host_id == 0

    def _control_plane_checkin(self) -> bool:
        """Top-of-iteration supervision protocol (see attach_control_plane).

        Returns True when this host must exit at the CURRENT boundary
        (its own preemption decided before arriving at the step barrier,
        or a peer's broadcast observed pre- or post-barrier). The
        boundary decision is only ever taken at those points: a local
        SIGTERM that lands while we are INSIDE the barrier wait comes
        too late — we already rendezvoused for the next step, and
        peers may already be parked at ITS barrier — so that host runs
        one more step and exits through the post-step path instead,
        where the broadcast-plus-arrival releases peers at the matching
        boundary. Flag-before-arrival ordering makes the released
        peer's post-barrier flag check reliable."""
        cp = self._control_plane
        if cp is None:
            return self._preempted
        step = self.context.iterations
        # the first iteration's step still pays the cold jit compile —
        # report "starting" so the supervisor applies the startup grace,
        # not the steady-state heartbeat timeout
        cp.heartbeat(step, status="starting" if self._cp_first_checkin
                     else "running")
        self._cp_first_checkin = False
        if cp.get_flag(ABORT_FLAG) is not None:
            logger.log_event("abort-observed", host=cp.host_id, step=step)
            raise JobAborted(
                "supervisor raised the abort flag: a peer host is gone, "
                "so barriers/collectives can never complete — exiting "
                "without a save (the last committed checkpoint stands)"
            )
        if not self._preempted and cp.get_flag(PREEMPT_FLAG) is not None:
            self._preempted = True
        if self._preempted:
            # exiting at THIS boundary: flag + arrival (idempotent, via
            # _broadcast_preempt) release any peer already parked inside
            # this step's barrier; skipping the wait ourselves is safe —
            # the save's commit barrier is the real rendezvous
            self._broadcast_preempt(step)
            return True
        if self._cp_step_barrier and cp.num_hosts > 1:
            cp.barrier(f"step-{step}", self._cp_barrier_timeout)
            if step >= 2 and cp.host_id == 0:
                # every host arrived at step-{step} for us to be here, so
                # none can ever wait on step-{step-2} again — unbounded
                # arrival state on long runs otherwise. One prune suffices;
                # all N hosts issuing it is N-1 wasted coordinator round
                # trips per step on the TCP backend
                cp.prune_barrier(f"step-{step - 2}")
            if cp.get_flag(PREEMPT_FLAG) is not None:
                # the broadcaster arrived at THIS barrier, so its exit
                # boundary is this one — join it
                self._preempted = True
                return True
        # a local signal that landed during the barrier wait is handled
        # post-step (see docstring), never here
        return False

    def _broadcast_preempt(self, step: int) -> None:
        """Make this host's preemption everyone's, without stranding a
        peer: set the preempt flag (once), then register arrival at this
        boundary's step barrier. Exit paths never re-enter the loop top,
        so a peer already parked inside ``step-N`` would otherwise wait
        out the full barrier timeout for an arrival that never comes.
        Flag-before-arrival ordering means a peer released by our
        arrival always observes the flag on its post-barrier check."""
        cp = self._control_plane
        if cp is None:
            return
        if cp.get_flag(PREEMPT_FLAG) is None:
            cp.set_flag(PREEMPT_FLAG, str(step))
            logger.log_event("preempt-broadcast", host=cp.host_id, step=step)
        if self._cp_step_barrier and cp.num_hosts > 1:
            cp.arrive(f"step-{step}")

    def _commit_barrier_and_latest(self, commit: CheckpointCommit) -> None:
        """Cross-host commit barrier: this host's shard is committed
        (manifest + rename done); ``latest`` may only advance once EVERY
        host has committed its shard for this step. A host killed in
        this window leaves peers timing out at the barrier — ``latest``
        stays at the previous step on every host, so restore can never
        assemble a mixed-step checkpoint."""
        cp = self._control_plane
        if cp is not None and cp.num_hosts > 1:
            get_fault_plan().fire("ckpt.commit_barrier", path=commit.final_dir)
            # the commit-barrier wait IS the per-host straggler signal:
            # the host that waits longest committed first, the one that
            # waits ~0 made everyone else wait (analyzer attributes this
            # offline from the span stream). Every host derives the SAME
            # trace id from the commit identity — no context crosses the
            # wire, yet obs trace reassembles one commit:step-N trace
            # spanning all hosts (per coordination epoch: a post-relaunch
            # re-save of the same step is a different incident)
            from ..obs import derive_trace_id, trace_context

            commit_trace = derive_trace_id(
                "ckpt-commit", commit.step,
                os.environ.get("SCALING_TPU_COORD_EPOCH", "0"),
            )
            with trace_context(commit_trace):
                with span("ckpt.commit_barrier", step=commit.step,
                          host=cp.host_id):
                    cp.barrier(
                        f"commit:step-{commit.step}",
                        self._cp_barrier_timeout,
                    )
            prev = self._cp_prev_commit_step
            if prev is not None and prev != commit.step and cp.host_id == 0:
                # every host passed THIS commit barrier, so none can ever
                # wait on the previous step's again; keep the current
                # one's arrivals sticky (a preemption re-save of the same
                # step must re-enter it instantly). Host 0 only — one
                # prune suffices
                cp.prune_barrier(f"commit:step-{prev}")
            self._cp_prev_commit_step = commit.step
        if self._cp_latest_leader:
            with span("ckpt.latest", step=commit.step):
                commit.update_latest()

    # ----------------------------------------------------------- preemption
    def install_preemption_handler(self) -> None:
        """Save-and-exit on SIGTERM — the TPU-pod equivalent of the
        reference's Determined preemption hook (reference:
        trainer.py:449-456): GKE spot/preemptible nodes deliver SIGTERM
        ahead of reclaim; the next run resumes from the saved step.

        Chains to any previously installed SIGTERM handler (launchers,
        log flushers, cluster agents) instead of silently discarding it.
        """
        import signal

        prev = signal.getsignal(signal.SIGTERM)

        def handler(signum, frame):
            self._preempted = True
            if callable(prev):  # SIG_DFL/SIG_IGN are enum ints, skipped
                prev(signum, frame)

        self._preempted = False
        signal.signal(signal.SIGTERM, handler)

    # ----------------------------------------------------------- preemption
    def _preemption_requested(self) -> bool:
        if not self._preempted and self._control_plane is not None:
            # another host broadcast preemption since our last check
            if self._control_plane.get_flag(PREEMPT_FLAG) is not None:
                self._preempted = True
        return self._preempted or (
            self.external_preemption is not None and self.external_preemption()
        )

    def _preemption_exit(self) -> None:
        # a mid-step SIGTERM lands here WITHOUT passing another checkin:
        # broadcast (and release any peer parked at this boundary's step
        # barrier) before saving, or the commit barrier below would wait
        # on peers that never learned they must save
        self._broadcast_preempt(self.context.iterations)
        if (
            self.config.save_dir is not None
            and self._last_saved_step == self.context.iterations
        ):
            # the will_save path just saved this exact boundary (lockstep
            # peers all did the same, so no commit-barrier mismatch);
            # re-staging an identical checkpoint on the preemption
            # critical path can overrun a tight reclaim grace. Still
            # drain the async writer so that save is durably committed.
            self.finalize_checkpoints()
            logger.info(
                "preemption: boundary already checkpointed, exiting cleanly"
            )
        elif self.config.save_dir is not None:
            if self._control_plane is not None:
                # same head-of-window refresh as the regular will_save
                # path: the last heartbeat was at the loop-top checkin,
                # a whole step ago — without this, heartbeat_timeout
                # must budget step+save and the supervisor can declare
                # us hung (and SIGKILL us) mid-final-save
                self._control_plane.heartbeat(
                    self.context.iterations, status="running"
                )
            step_dir = self.save_checkpoint()
            self.finalize_checkpoints()
            self._run_checkpoint_hooks(step_dir)
            logger.info("preemption: checkpoint saved, exiting cleanly")
        if self._control_plane is not None:
            self._control_plane.heartbeat(
                self.context.iterations, status="preempted"
            )

    def _on_step_stall(self, step: int, elapsed: float) -> None:
        """Watchdog callback: the watchdog thread must not host-gather
        donated device buffers mid-step, so it requests a save at the
        next safe point — if the stalled step ever completes, the loop
        saves-and-exits via the preemption path.

        With a control plane attached, peer heartbeats turn the blind
        "no progress for Ns" into a verdict: a peer that stopped
        publishing is dead (the collective will never complete — the
        supervisor is about to tear us down), otherwise the stall is
        local (wedged storage, stuck data worker)."""
        verdict = "local-stall"
        dead: List[int] = []
        cp = self._control_plane
        if cp is not None:
            try:
                report = straggler_table(
                    cp.peer_heartbeats(), cp.num_hosts, self._cp_peer_stale
                )
            # ValueError included: a truncated TCP reply surfaces as
            # json.JSONDecodeError, and this watchdog-thread callback
            # must reach the save-and-exit request below no matter what
            except (OSError, RuntimeError, ValueError) as e:
                logger.warning(f"peer heartbeat read failed mid-stall: {e!r}")
            else:
                # our own heartbeat is necessarily stale mid-stall (the
                # main thread is stuck inside the step, not publishing),
                # so counting ourselves would turn every local stall
                # into a false "peer-host-dead"
                dead = [h for h in report.dead_hosts if h != cp.host_id]
                if dead:
                    verdict = "peer-host-dead"
                logger.error(
                    f"stall straggler table (stale after "
                    f"{self._cp_peer_stale}s):\n{report.render()}"
                )
        logger.log_event(
            "step-stall", step=step, elapsed_s=round(elapsed, 1),
            verdict=verdict, dead_hosts=dead,
            host=cp.host_id if cp is not None else 0,
        )
        logger.error(
            f"step stall after step {step} ({elapsed:.1f}s, {verdict}): "
            "requesting save-and-exit at the next loop boundary"
        )
        if cp is not None:
            try:
                # the drain below exits every host with code 0 — the
                # stall flag is what tells the supervisor this was NOT a
                # finished run, so it relaunches instead of reporting
                # success mid-training
                cp.set_flag(STALL_FLAG, str(step))
            except (OSError, RuntimeError, ValueError) as e:
                logger.warning(f"stall flag broadcast failed: {e!r}")
        self._preempted = True

    # ----------------------------------------------------------- train loop
    def run_training(self, log_metrics_fn: Optional[Callable] = None) -> None:
        assert self.config.train_iterations is not None
        topo = self.topology
        if topo is not None and topo.pipe_parallel_size > 1:
            # the obs report's pipeline section needs the schedule shape to
            # attribute span-measured step time against the predicted
            # bubble (docs/PIPELINE.md); one lifecycle event carries it
            logger.log_event(
                "pipeline-config",
                pp=topo.pipe_parallel_size,
                virtual=topo.pipe_virtual_size,
                token_slices=topo.pipe_token_slices,
                gas=topo.gradient_accumulation_steps,
            )
        # the auto-sharding tuner's predicted step time for this run's
        # layout (exported by `python -m scaling_tpu.tune` as
        # SCALING_TPU_TUNER_PREDICTION): logged into the SAME events
        # stream so `obs report` can score prediction vs span-measured
        # step time — the tuner's calibration loop (docs/TUNING.md)
        from ..tune import prediction_from_env

        prediction = prediction_from_env()
        if prediction is not None:
            logger.log_event("tuner-prediction", **prediction)
        watchdog = None
        if self.config.step_timeout_seconds is not None:
            # created here, ARMED by the loop after the first step
            # completes: the cold jit compile (minutes on big models)
            # must not read as a stall
            watchdog = StepStallWatchdog(
                self.config.step_timeout_seconds, on_stall=self._on_step_stall
            )
        try:
            self._run_training_loop(log_metrics_fn, watchdog)
        finally:
            if watchdog is not None:
                watchdog.stop()
            if self.profiler is not None:
                # abort paths (NonFiniteLossError, SIGTERM drain, stall)
                # must not lose a partially collected window or leave an
                # XLA trace running
                self.profiler.close()

    def _emit_step_metrics(
        self, output: TrainStepOutput, log_metrics_fn: Optional[Callable]
    ) -> None:
        if not output.fetched:
            # unfetched steps (log_interval > 1) carry in-flight device
            # arrays; touching them here would reintroduce the per-step
            # sync the knob exists to remove
            return
        metrics = {
            "loss": output.loss,
            **output.metrics,
            **(output.learning_rates or {}),
        }
        if output.global_grad_norm is not None:
            metrics["global_grad_norm"] = output.global_grad_norm
        if output.current_loss_scale is not None:
            metrics["loss_scale"] = output.current_loss_scale
        metrics["step_duration"] = output.step_duration
        if log_metrics_fn is not None:
            metrics = log_metrics_fn(self, output, metrics)
        try:
            # host-side gauges only (memory stats, EMA, MFU): adds no
            # device syncs — see tests/core/test_obs/test_step_path.py
            metrics.update(self.telemetry.on_step(
                self.context.iterations, output.step_duration
            ))
        except Exception as e:
            # telemetry must never abort a training step
            logger.warning(f"step telemetry update failed: {e!r}")
        logger.log_metrics(metrics, self.context.iterations)
        self.telemetry.flush(self.context.iterations)
        for hook in self.metrics_hooks:
            try:
                hook(metrics, self.context.iterations)
            except Exception as e:
                # reporting must never abort a training step
                logger.warning(f"metrics hook failed: {e}")

    def _run_training_loop(
        self, log_metrics_fn: Optional[Callable],
        watchdog: Optional[StepStallWatchdog] = None,
    ) -> None:
        watchdog_armed = False
        first_step_done = False
        while self.context.iterations < self.config.train_iterations:
            if watchdog is not None and watchdog_armed:
                watchdog.beat(self.context.iterations)
            get_fault_plan().fire("signal.sigterm")
            get_fault_plan().fire("host.kill")
            get_fault_plan().fire("host.hang")
            # heartbeat + abort/preempt flags + lockstep barrier; raises
            # JobAborted when the supervisor is tearing this epoch down.
            # True = exit at this boundary: a SIGTERM that arrived during
            # the checkpoint/eval window (or a stall flag) must exit
            # without burning another full step. The external predicate
            # is NOT polled here — cluster glue (Determined) counts one
            # poll per completed step
            if self._control_plane_checkin():
                self._preemption_exit()
                return
            output = self.train_step()
            if not first_step_done:
                # the first step holds the step's trace, lowering and
                # compile (the ``compile.*`` rows named ``jit(step)``): what
                # a restart costs before it trains again
                first_step_done = True
                get_registry().gauge("train_first_step_seconds").set(
                    time.monotonic() - process_start_s())
            if watchdog is not None and not watchdog_armed:
                watchdog_armed = True
                watchdog.start()  # steady-state steps from here on
            if (
                self._preemption_requested()
                and self.context.iterations < self.config.train_iterations
            ):
                # the step that just completed is about to be saved by
                # the preemption exit — its metrics must reach the sinks
                # too (same contract as the non-finite abort below).
                # NOT at the final boundary: the run is complete, and a
                # drain here would save + enter a commit barrier that
                # peers who missed the flag (they exit 'done' without
                # another checkin) never arrive at — every host must
                # take the identical normal exit path instead
                self._emit_step_metrics(output, log_metrics_fn)
                self._preemption_exit()
                return
            will_save = (
                self.config.save_dir is not None
                and self.config.save_interval is not None
                and self.context.iterations % self.config.save_interval == 0
            )
            will_eval = (
                self.config.eval_interval is not None
                and self.dataset_evaluation is not None
                and self.context.iterations % self.config.eval_interval == 0
            )
            if (will_save or will_eval) and self._unfetched_steps:
                # checkpoint/eval sync the device anyway; draining FIRST
                # pins the unfetched backlog's device work inside the train
                # window, so the aux-time exclusion below can't swallow
                # real step time that would have drained during the aux work
                jax.block_until_ready(self.opt_state.step)  # sta: disable=STA010
            if (will_save or will_eval) and self._control_plane is not None:
                # the save/eval window publishes no step heartbeats (a
                # long eval can exceed heartbeat_timeout on its own);
                # restart the staleness clock here so the timeout only
                # has to budget for the window itself, not step+window
                self._control_plane.heartbeat(
                    self.context.iterations, status="running"
                )
            aux_start = time.time()
            if will_save:
                step_dir = self.save_checkpoint()
                self._run_checkpoint_hooks(step_dir)
            if will_eval:
                eval_out = self.eval_step()
                logger.log_metrics(
                    {"eval_loss": eval_out.loss, **{f"eval_{k}": v for k, v in eval_out.metrics.items()}},
                    self.context.iterations,
                )
            if (will_save or will_eval) and self._last_fetch_wall is not None:
                # the amortized step_duration divides (next fetch - last
                # fetch) by the backlog; checkpoint/eval wall time between
                # fetches is not train-step work and would inflate it
                self._last_fetch_wall += time.time() - aux_start
            self._emit_step_metrics(output, log_metrics_fn)
            if self._nonfinite_guard is not None and output.fetched:
                # after logging, so the aborting step's metrics still
                # reach the sinks. Fetched outputs only: unfetched steps
                # carry in-flight device arrays whose inspection would
                # force the sync log_interval exists to remove
                try:
                    self._nonfinite_guard.observe(
                        self.context.iterations, output.loss,
                        output.overflow, output.current_loss_scale,
                    )
                except NonFiniteLossError:
                    # budget exhausted: leave a resumable checkpoint
                    # behind, then surface the diagnosis
                    if self.config.save_dir is not None:
                        step_dir = self.save_checkpoint()
                        self.finalize_checkpoints()
                        self._run_checkpoint_hooks(step_dir)
                        logger.error(
                            f"non-finite abort: state saved to {step_dir}"
                        )
                    raise
        self.finalize_checkpoints()
        if self._control_plane is not None:
            # the supervisor's straggler table should read "done", not a
            # stale "running" that looks like a hang at shutdown
            self._control_plane.heartbeat(self.context.iterations, status="done")

    def _run_checkpoint_hooks(self, step_dir: Path) -> None:
        if not self.checkpoint_hooks:
            return
        if self._ckpt_writer is not None:
            # hooks must see a durable checkpoint, not an in-flight async
            # write — a torn copy must never leave the machine
            self._ckpt_writer.wait()
        for hook in self.checkpoint_hooks:
            try:
                hook(step_dir, self.context.iterations)
            except Exception as e:
                logger.warning(f"checkpoint hook failed: {e}")

    # ----------------------------------------------------------- checkpoint
    def finalize_checkpoints(self) -> None:
        """Block until pending async checkpoint writes are durable.

        Deliberately leaves the prefetch thread running: the trainer may
        train again (queued batches continue the exact stream); the daemon
        thread dies with the process."""
        if self._ckpt_writer is not None:
            self._ckpt_writer.wait()

    def _config_fingerprint(self) -> Optional[str]:
        """Stable digest of the run config, stamped into the checkpoint
        manifest (restore logs a warning when it changes across a
        resume — legitimate for finetunes, suspicious otherwise)."""
        cfg = getattr(self.context, "config", None)
        if cfg is None or not hasattr(cfg, "model_dump"):
            return None
        import hashlib
        import json as _json

        blob = _json.dumps(cfg.model_dump(mode="json"), sort_keys=True, default=str)
        return hashlib.sha256(blob.encode()).hexdigest()[:16]

    # ------------------------------------------------- mesh metadata (elastic)
    def _num_hosts(self) -> int:
        """Host count of the pod writing/reading this checkpoint: the
        control plane when attached (supervised runs), the supervisor's
        env contract otherwise, falling back to the jax process count."""
        if self._control_plane is not None:
            return int(self._control_plane.num_hosts)
        env = os.environ.get(ENV_NUM_HOSTS)
        if env is not None:
            return int(env)
        return int(jax.process_count())

    def _current_topology_dict(self) -> dict:
        cfg = self.topology.config
        return {
            "world_size": cfg.world_size,
            "pipe_parallel_size": cfg.pipe_parallel_size,
            "data_parallel_size": cfg.data_parallel_size,
            "context_parallel_size": cfg.context_parallel_size,
            "model_parallel_size": cfg.model_parallel_size,
            "pipe_virtual_size": cfg.pipe_virtual_size,
            "pipe_token_slices": cfg.pipe_token_slices,
            "micro_batch_size": cfg.micro_batch_size,
            "gradient_accumulation_steps": cfg.gradient_accumulation_steps,
            "global_batch_size": cfg.global_batch_size,
            "num_hosts": self._num_hosts(),
        }

    def _param_records(self, params_view, metas) -> dict:
        """meta key -> global shape/dtype/sharding-spec record for
        MESH.json. The ckpt-view tree holds GLOBAL logical arrays (the
        stage stacking is already undone), so .shape here is the
        mesh-independent shape any reader reconstructs — no device sync
        (shape/dtype are host-side metadata)."""
        from ..nn.param import ParamMeta

        p_leaves = jax.tree.leaves(params_view)
        m_leaves = jax.tree.leaves(
            metas, is_leaf=lambda x: isinstance(x, ParamMeta)
        )
        return {
            m.key: param_record(
                p.shape, p.dtype, getattr(m, "partition_spec", ())
            )
            for p, m in zip(p_leaves, m_leaves)
        }

    def _mesh_meta(self, params_view, metas) -> dict:
        opt_cfg = self.optimizer.config
        zero_stage = (
            int(getattr(opt_cfg, "zero_stage", 1))
            if getattr(opt_cfg, "zero", False)
            else 0
        )
        return build_mesh_meta(
            topology=self._current_topology_dict(),
            params=self._param_records(params_view, metas),
            optimizer={
                "zero_stage": zero_stage,
                "fields": ["master", "exp_avg", "exp_avg_sq"],
                # on-disk optimizer leaves mirror the param tree as
                # GLOBAL arrays (ckpt_view gathers zero-partitioned
                # state), so a resharder re-slices them the same way
                "layout": "global-per-layer",
            },
            step=self.context.iterations,
        )

    def save_checkpoint(self, dir: Optional[Path | str] = None) -> Path:
        """Atomic commit protocol (docs/RESILIENCE.md): everything is
        written into a ``.tmp-global_stepN`` staging dir, checksummed
        into ``MANIFEST.json``, fsynced and atomically renamed onto
        ``global_stepN`` before ``latest`` moves — a kill at any instant
        leaves the previous committed checkpoint intact and loadable.

        Traced as ``trainer.save`` (on the async path this covers only
        the host gather + submit; the writer thread's own ``ckpt.*``
        spans carry the durable-write cost)."""
        with span("trainer.save", step=self.context.iterations):
            return self._save_checkpoint_inner(dir)

    def _save_checkpoint_inner(self, dir: Optional[Path | str] = None) -> Path:
        base = Path(dir or self.config.save_dir)
        base.mkdir(parents=True, exist_ok=True)
        writer = None
        if self.config.save_checkpoint_async:
            if self._ckpt_writer is None:
                self._ckpt_writer = AsyncCheckpointWriter()
            else:
                self._ckpt_writer.wait()  # never interleave two saves
            writer = self._ckpt_writer
        # AFTER the writer barrier: creating the commit sweeps stale
        # .tmp-* staging debris, which must never race a previous async
        # save's still-pending finalize
        commit = CheckpointCommit(
            base, self.context.iterations,
            config_fingerprint=self._config_fingerprint(),
        )
        stage_dir = commit.tmp_dir
        # checkpoint-view trees: stage-stacked pipeline bodies un-stack into
        # per-layer files so checkpoints are pipe-layout independent
        viewed_opt = self.opt_state._replace(
            master=self.module.ckpt_view(self.opt_state.master),
            exp_avg=self.module.ckpt_view(self.opt_state.exp_avg),
            exp_avg_sq=self.module.ckpt_view(self.opt_state.exp_avg_sq),
        )
        metas = self.module.ckpt_metas()
        params_view = self.module.ckpt_view(self.params)
        with span("ckpt.stage", step=self.context.iterations,
                  backend=self.config.checkpoint_backend.value):
            if self.config.checkpoint_backend == CheckpointBackend.ORBAX:
                self._save_orbax(stage_dir, viewed_opt, params_view)
            else:
                # checked here, not in config validation: jax.process_count()
                # initializes the backend as a side effect, which would break a
                # later jax.distributed.initialize() for configs built early
                if jax.process_count() > 1:
                    raise RuntimeError(
                        "the npz checkpoint backend host-gathers every array "
                        "and cannot run multi-process; set "
                        "trainer.checkpoint_backend: orbax for multi-host runs"
                    )
                save_model_checkpoint(
                    stage_dir, params_view, metas,
                    separate_file_for_parameters=getattr(
                        self.module, "separate_file_for_parameters", None
                    ),
                    writer=writer,
                    recorder=commit.record,
                )
                save_optimizer_checkpoint(
                    stage_dir, viewed_opt, metas, writer=writer,
                    recorder=commit.record,
                )
            # MESH.json (docs/RESILIENCE.md "Elastic resharding"): the
            # logical param tree + saving topology, staged with the rest
            # so the commit's manifest scan digests it — restore at a
            # different mesh shape verifies against it instead of
            # assuming the disk layout matches the current mesh
            write_mesh_meta(stage_dir, self._mesh_meta(params_view, metas))
            self.context.save_checkpoint(stage_dir)
            # full config travels with the weights so inference can rebuild
            # the architecture (reference: context.py:113-125 config.yml copy)
            cfg = getattr(self.context, "config", None)
            if cfg is not None and hasattr(cfg, "model_dump"):
                import yaml as _yaml

                (stage_dir / "config.yml").write_text(
                    _yaml.safe_dump(cfg.model_dump(mode="json"), sort_keys=False)
                )
                # tokenizer travels with the weights so inference needs
                # nothing else (reference: inference_model.py:70 vocab.json)
                vocab = getattr(
                    getattr(cfg, "transformer_architecture", None), "vocab_file", None
                )
                if vocab and Path(vocab).is_file():
                    shutil.copyfile(vocab, stage_dir / "vocab.json")
        step_dir = commit.final_dir
        if writer is None:
            commit.finalize()
            self._commit_barrier_and_latest(commit)
        else:
            # the single writer thread is FIFO: the manifest+rename, the
            # cross-host commit barrier, and then "latest" land only
            # after every npz of this save is durable
            writer.submit(commit.finalize)
            writer.submit(self._commit_barrier_and_latest, commit)
        logger.info(f"saved checkpoint {step_dir}")
        if self.config.delete_past_optimizer_states:
            if writer is None:
                self._prune_past_optimizer_states(base, step_dir)
            else:
                # AFTER the queued finalize+latest: pruning the previous
                # checkpoint's optimizer state before the new save is
                # committed would open a crash window with no optimizer
                # state anywhere on disk
                writer.submit(self._prune_past_optimizer_states, base, step_dir)
        self._last_saved_step = self.context.iterations
        return step_dir

    def _prune_past_optimizer_states(self, base: Path, step_dir: Path) -> None:
        for old in sorted(base.glob("global_step*")):
            if old == step_dir:
                continue
            removed = []
            for f in old.glob("optimizer_state_*"):
                f.unlink()
                removed.append(f.name)
            old_orbax_opt = old / "orbax" / "optimizer"
            if old_orbax_opt.is_dir():
                removed.extend(
                    p.relative_to(old).as_posix()
                    for p in old_orbax_opt.rglob("*") if p.is_file()
                )
                shutil.rmtree(old_orbax_opt)
            if removed:
                # keep the pruned checkpoint valid in the eyes of the
                # fallback scanner: its manifest must not list files
                # this deliberate pruning removed
                from ..resilience import prune_manifest_entries

                prune_manifest_entries(old, removed)

    def _save_orbax(self, step_dir: Path, viewed_opt: OptimizerState,
                    params_view=None) -> None:
        """Tensorstore-backed sharded save: every host writes only its own
        shards — no host gather, unlike the npz path (save trees are the
        same per-layer canonical views, so pp/mp relayouts still restore)."""
        from ..checkpoint.orbax_backend import save_orbax

        save_orbax(
            step_dir,
            params_view if params_view is not None
            else self.module.ckpt_view(self.params),
            {
                "step": viewed_opt.step,
                "master": viewed_opt.master,
                "exp_avg": viewed_opt.exp_avg,
                "exp_avg_sq": viewed_opt.exp_avg_sq,
                "loss_scaler": viewed_opt.loss_scaler._asdict(),
            },
        )

    def _restore_orbax_params(self, step_dir: Path, metas, restored_keys=None,
                              params_view=None):
        """Restore the param view tree, re-sharded to the CURRENT mesh
        layout (orbax reads each shard from tensorstore). Non-strict under
        the same allow-list regexes as the npz loader, so PEFT/LoRA loads
        work against orbax base checkpoints too."""
        from ..checkpoint.orbax_backend import restore_orbax_params

        return restore_orbax_params(
            step_dir,
            params_view if params_view is not None
            else self.module.ckpt_view(self.params),
            metas,
            allowed_missing_keys=self.config.allowed_missing_keys_in_checkpoint,
            allowed_unexpected_keys=self.config.allowed_unexpected_keys_in_checkpoint,
            ignore_keys=self.config.ignore_keys_in_checkpoint,
            restored_keys=restored_keys,
        )

    def _restore_orbax_opt(self, step_dir: Path) -> OptimizerState:
        """Restore the optimizer view trees (call only when the caller wants
        optimizer states — missing/mismatched trees raise and the caller
        re-derives fresh state, like the npz path)."""
        from ..checkpoint.orbax_backend import restore_orbax_opt

        restored = restore_orbax_opt(
            step_dir,
            {
                "step": self.opt_state.step,
                "master": self.module.ckpt_view(self.opt_state.master),
                "exp_avg": self.module.ckpt_view(self.opt_state.exp_avg),
                "exp_avg_sq": self.module.ckpt_view(self.opt_state.exp_avg_sq),
                "loss_scaler": self.opt_state.loss_scaler._asdict(),
            },
        )
        # scalars come back COMMITTED to whatever single device orbax used;
        # jit refuses to relocate committed arrays across the mesh, so hand
        # them back as host values (uncommitted — jit places them freely)
        return self.opt_state._replace(
            step=np.asarray(restored["step"]),
            master=restored["master"],
            exp_avg=restored["exp_avg"],
            exp_avg_sq=restored["exp_avg_sq"],
            loss_scaler=type(self.opt_state.loss_scaler)(
                **jax.tree.map(np.asarray, restored["loss_scaler"])
            ),
        )

    def load_checkpoint(self, dir: Optional[Path | str] = None) -> bool:
        """Verified restore with fallback: candidates are tried in
        preference order (a valid ``latest`` pointer first, then every
        ``global_step*`` newest-first); each must pass manifest
        verification and actually load — corrupt or torn ones are
        skipped with an exact reason, so a run resumes from the most
        recent VALID state instead of crashing on a rotten one.
        ``trainer.strict_checkpoint_load`` turns any skip into an error.
        """
        base = Path(dir or self.config.load_dir)
        strict = self.config.strict_checkpoint_load
        candidates = checkpoint_candidates(base)
        if not candidates:
            logger.warning(f"no checkpoint found at {base}")
            return False
        skipped: List[str] = []
        for step_dir in candidates:
            problems = verify_checkpoint(
                step_dir, deep=self.config.deep_checkpoint_verification
            )
            if problems:
                line = f"{step_dir.name}: {'; '.join(problems)}"
                if strict:
                    raise CheckpointCorruptionError(
                        f"checkpoint verification failed (strict mode): {line}"
                    )
                logger.warning(f"skipping invalid checkpoint {line}")
                skipped.append(line)
                continue
            try:
                # a TRANSIENT read error must not demote a checkpoint
                # that just passed verification — retry the (idempotent)
                # load before treating the OSError as corruption
                retry_io(
                    lambda d=step_dir: self._load_step_dir(d),
                    attempts=self.config.io_retry_attempts,
                    base_delay=self.config.io_retry_backoff_seconds,
                    retry_on=(OSError,),
                    what=f"checkpoint load {step_dir.name}",
                )
            except _CORRUPT_LOAD_ERRORS as e:
                # disk-level corruption the manifest could not vouch
                # against (legacy manifest-less checkpoints, torn orbax
                # trees). Config/shape mismatches, OOMs and assertion
                # errors are NOT in this tuple — those abort, falling
                # back would silently load the wrong science.
                line = f"{step_dir.name}: load failed ({type(e).__name__}: {e})"
                if strict:
                    raise
                logger.warning(f"skipping unreadable checkpoint {line}")
                skipped.append(line)
                continue
            if skipped:
                logger.warning(
                    f"resumed from {step_dir.name} after skipping "
                    f"{len(skipped)} checkpoint(s): " + " | ".join(skipped)
                )
            return True
        logger.warning(
            f"no valid checkpoint under {base}; skipped: " + " | ".join(skipped)
        )
        return False

    def _load_step_dir(self, step_dir: Path) -> None:
        manifest = read_manifest(step_dir)
        if manifest is not None and manifest.get("config_fingerprint"):
            current = self._config_fingerprint()
            if current is not None and current != manifest["config_fingerprint"]:
                logger.warning(
                    f"config fingerprint changed since {step_dir.name} was "
                    f"saved ({manifest['config_fingerprint']} -> {current}); "
                    "expected for finetunes/topology changes, suspicious "
                    "for a plain resume"
                )
        from ..checkpoint.orbax_backend import orbax_model_valid

        orbax_dir_present = (step_dir / "orbax").is_dir()
        orbax_backend = orbax_dir_present and orbax_model_valid(step_dir)
        if orbax_dir_present and not orbax_backend:
            # a crashed orbax save must not shadow valid npz files in the
            # same step dir (and must fail loudly when nothing else exists)
            if not list(step_dir.glob("model_state_layer_*.npz")):
                raise CheckpointCorruptionError(
                    f"{step_dir / 'orbax'} exists but holds no committed orbax "
                    "checkpoint (torn save?) and no npz files are present"
                )
            logger.warning(
                f"{step_dir / 'orbax'} is not a committed orbax checkpoint; "
                "falling back to the npz files in the same step dir"
            )
        metas = self.module.ckpt_metas()
        current_view = self.module.ckpt_view(self.params)
        # reshard-on-restore (docs/RESILIENCE.md "Elastic resharding"):
        # when the checkpoint's MESH.json topology differs from the
        # restoring one, pre-flight the logical param tree (a global-
        # shape disagreement is a different model — abort, never "fall
        # back"), then take the SAME per-layer global-array load below:
        # device_put against the current metas re-slices every leaf
        # (params AND zero-partitioned optimizer state) onto the new
        # mesh, with ckpt_view/ckpt_unview handling the vpp stacking.
        # Legacy checkpoints without MESH.json restore at the same
        # shape exactly as before (plan is None).
        plan = reshard_plan(
            read_mesh_meta(step_dir),
            self._current_topology_dict(),
            self._param_records(current_view, metas),
        )
        if plan is not None:
            fire_reshard_point(step_dir, plan)
            logger.log_event(
                "ckpt-reshard", step=manifest.get("step")
                if manifest is not None else None,
                **plan.event_fields(),
            )
        self.restored_model_keys = set()
        if orbax_backend:
            params_view = self._restore_orbax_params(
                step_dir, metas, restored_keys=self.restored_model_keys,
                params_view=current_view,
            )
        else:
            params_view = load_model_checkpoint(
                step_dir,
                current_view,
                metas,
                allowed_missing_keys=self.config.allowed_missing_keys_in_checkpoint,
                allowed_unexpected_keys=self.config.allowed_unexpected_keys_in_checkpoint,
                ignore_keys=self.config.ignore_keys_in_checkpoint,
                restored_keys=self.restored_model_keys,
            )
        self.params = self.module.ckpt_unview(params_view, self.params)
        merged_lora = False
        if self.config.merge_lora_after_loading_checkpoint:
            self.params = self.module.merge_lora_weights(self.params)
            merged_lora = True
            logger.info("merged LoRA deltas into base weights after load")
        optimizer_states_loaded = False
        # after a merge the checkpoint's fp32 masters are stale (they hold the
        # unmerged weights and nonzero lora_b — the first step would resurrect
        # the folded delta); re-derive instead, like the reference's
        # refresh_optimizer_after_model_change (trainer.py:87-92)
        if self.config.load_optimizer_states and not merged_lora:
            try:
                if orbax_backend:
                    loaded = self._restore_orbax_opt(step_dir)
                else:
                    viewed_current = self.opt_state._replace(
                        master=self.module.ckpt_view(self.opt_state.master),
                        exp_avg=self.module.ckpt_view(self.opt_state.exp_avg),
                        exp_avg_sq=self.module.ckpt_view(self.opt_state.exp_avg_sq),
                    )
                    loaded = load_optimizer_checkpoint(step_dir, viewed_current, metas)
                self.opt_state = loaded._replace(
                    master=self.module.ckpt_unview(loaded.master, self.opt_state.master),
                    exp_avg=self.module.ckpt_unview(loaded.exp_avg, self.opt_state.exp_avg),
                    exp_avg_sq=self.module.ckpt_unview(
                        loaded.exp_avg_sq, self.opt_state.exp_avg_sq
                    ),
                )
                optimizer_states_loaded = True
            except FileNotFoundError:
                logger.warning(f"optimizer states absent in {step_dir}")
            except Exception as e:
                # an orbax TREE MISMATCH (architecture/PEFT change) is the
                # same situation as absent npz files: fall back to fresh
                # state. Orbax surfaces mismatches through a zoo of types
                # (KeyError/ValueError/TypeError, AssertionError, its own
                # classes), so the orbax branch treats every non-I/O error
                # as a mismatch. I/O, memory and runtime errors are NOT
                # caught — a corrupt checkpoint or an HBM OOM mid-restore
                # (XLA's RESOURCE_EXHAUSTED is a RuntimeError subclass)
                # must abort, not silently reset Adam moments. The npz
                # path aborts on EVERY error, as before this fallback
                # existed.
                if isinstance(e, (OSError, MemoryError, RuntimeError)):
                    raise
                if not orbax_backend:
                    raise
                logger.warning(
                    f"orbax optimizer tree mismatch ({type(e).__name__}: {e}); "
                    "re-deriving fresh optimizer state"
                )
        self.optimizer_states_loaded = optimizer_states_loaded
        if not optimizer_states_loaded:
            # fp32 masters were copied from the random init; re-derive them
            # from the loaded params or the first step would revert the model
            self.opt_state = self.optimizer.init_state(self.params)
            logger.info("re-derived fresh optimizer state from loaded parameters")
        if self.config.load_context:
            self.context.load_checkpoint(step_dir)
            # the data cursor is a GLOBAL sample count, mesh-independent
            # by construction — but the new batch hierarchy's sampler
            # grid must divide it or micro-batch strides would split
            # mid-step (samples skipped/repeated). Validate at restore
            # time, where the error is actionable, not steps later.
            cfg = self.topology.config
            self.context.consumed_samples = rescale_consumed_samples(
                self.context.consumed_samples,
                micro_batch_size=cfg.micro_batch_size,
                data_parallel_size=cfg.data_parallel_size,
            )
            # the eval cursor advances by the OLD mbs*dp per eval
            # micro-batch, so it is legitimately not aligned to the new
            # grid after a reshard — floor-align it (a few re-seen eval
            # samples are harmless; hard-failing here would kill every
            # downsized relaunch at startup)
            self.context.consumed_eval_samples = rescale_consumed_samples(
                self.context.consumed_eval_samples,
                micro_batch_size=cfg.micro_batch_size,
                data_parallel_size=cfg.data_parallel_size,
                what="consumed_eval_samples",
                on_misaligned="floor",
            )
        logger.info(f"loaded checkpoint {step_dir}")


def _maybe_float(v):
    return None if v is None else float(v)


def _maybe_int(v):
    return None if v is None else int(v)


def _maybe_bool(v):
    return None if v is None else bool(v)
