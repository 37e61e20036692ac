"""The serving engine: the fused per-tick program + the tick loop.

Per tick the scheduler mixes prompt prefill work with decode work for
every running sequence; the engine runs it all as **ONE fused
Sarathi-style mixed program**: every slot row is either a prefill CHUNK
(prompts stream into the paged pool in fixed-size chunks) or a decode
row bringing its ONE last token — tagged purely by traced per-row
lengths, so a tick with 4 prefilling prompts dispatches 1 executable,
not 5. A row samples one position, its last real one. The engine does
not speculate: no row carries drafted tokens.

Shared-prefix block reuse rides the tick (docs/SERVING.md "Raw speed"):
the scheduler's prefix trie maps cached prompt blocks straight into new
sequences' tables (prefill skipped for the shared prefix; copy-on-write
forks applied by ``_apply_cow`` before programs run).

Paged attention streams KV blocks through the Pallas paged-decode
kernel (nn/paged_attention.py — interpreted off-TPU so the CPU mesh
runs the real kernel body). Layers that keep a line a slot instead (a
window layer's ring, a recurrent, convolution or delta-rule state) are
advanced, never indexed by position, so the prefix cache is refused
beside them by name (``__init__``).

The program's batch is TOKEN-MAJOR: the tick's real tokens, packed back
to back in slot order into one of (at most) two token widths that follow
from the configuration (``EngineConfig.mixed_widths``), so the trunk
prices the tokens a tick holds and not ``num_slots x mixed_width`` padded
positions.

No per-request recompiles, by construction: the mixed program compiles
once per token width, every width at the engine's first tick — its
shapes are the fixed ``(width,)`` tokens and ``(num_slots,
max_blocks_per_seq)`` tables, and sequence raggedness (prompt lengths,
prefill offsets) lives in block tables / context lengths / new_lens,
never in shapes. All of that host state travels as ONE int32
operand a tick (``TickLayout``), sliced apart on the device: a tick costs
one host-to-device transfer.

The engine runs ONE TICK AHEAD of the tokens the host has read
(``ServeEngine.tick``): it issues program N+1, then reads program N, so the
chip never waits for the host's emit / schedule / build. The one thing the
host needs of tick N to build tick N+1, a decoding row's last token, is fed
from program N's samples on the device (``prev``); the scheduler works on
the projected state (``Sequence.in_flight``), and where it needs a token's
value (a possible preemption, a deadline) the read comes first
(``_read_first``, ``SYNC_REASONS``).
All signatures are pinned in the ``serve_decode`` HLO-audit section
(analysis/goldens/serve_decode.json): a scheduler shape-bucketing or
kernel change that would trigger a recompile storm on the chip shows up
as golden drift in CI instead.

Sampling is per-request (``inference.sample_rows``): temperature /
top-k / top-p ride the jitted programs as traced per-row arrays, greedy
is the ``temperature=0`` default, and a tick in which no row samples
runs the sampler's argmax branch alone (one ``cond`` on the tick's
temperatures; no sort, no draw). Sample keys derive from (request id,
token position) — ``inference.request_sample_key`` — so a preempted-
and-resumed sequence redraws the SAME tokens and recompute-style
preemption (scheduler.py) stays invisible in the output even for
sampled rows.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import statistics
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from .. import obs
from ..logging import logger
from ..obs import compile_events
from ..nn.base_layer import state_views
from ..nn.latent_paged_attention import latent_tile_tokens
from ..nn.sparse_latent_attention import index_tile_tokens
from ..nn.mamba import RecurrentStateView, split_capacity
from ..nn.paged_attention import (
    kernel_sub_tokens, kernel_tile_tokens, kv_block_layout,
)
from ..resilience.faults import get_fault_plan
from .kvcache import (
    PagedKVPools,
    build_layer_views,
    init_pools,
    line_layers,
    serving_mesh,
    state_from_views,
)
from .scheduler import (
    Backpressure,
    ContinuousBatchingScheduler,
    Request,
    SchedulerConfig,
    Sequence,
    SequenceState,
    Tick,
)

# the minor dimension of a TPU vector register: the small token width is a
# whole multiple of it
LANES = 128
# prompts streaming a chunk each that the small token bucket has room for
# beside a decode row in every slot (EngineConfig.mixed_widths)
SMALL_BUCKET_CHUNKS = 3
# in ``TickLayout``'s tokens, a decode row's last token while the host has not
# read it: the mixed program takes it from the program before it
IN_FLIGHT = -1
# why a tick was not issued ahead of the read of the one before it
# (``serve_ticks_synchronous_total``'s ``reason``)
SYNC_REASONS = ("preempt", "deadline", "first", "drained")
# ticks whose spans ``stats_snapshot()["tick_phases_ms"]`` takes its medians over
TICK_PHASES_TICKS = 512
# the spans that tile a tick: ``serve.tick`` and ``serve.mixed`` only hold
# them, and ``serve.preempt`` / ``.cow`` nest in ``serve.schedule``
LEAF_PHASES = frozenset((
    "serve.schedule", "serve.mixed.build", "serve.mixed.dispatch",
    "serve.mixed.wait", "serve.emit", "serve.retire"))


def packed_batch_shape(width: int, row_width: int) -> Tuple[int, int]:
    """The ``(g, s)`` a token width runs the trunk at: groups of
    ``row_width`` tokens, the most one row brings (or of the widest
    divisor of the width under it). The dense trunk does not care for
    the shape; a routed MLP's one-hot dispatch is ``(g, s, E, C)`` with
    ``C = s`` when serving (nn/moe.py), linear in the width while ``s``
    stays fixed; and at this ``s`` the full width ``num_slots *
    row_width`` is, shape for shape, the row-major batch it replaced."""
    s = next(d for d in range(min(width, row_width), 0, -1) if width % d == 0)
    return width // s, s


class TickFields(NamedTuple):
    """The mixed program's per-tick host state, field by field."""

    tables: object    # (num_slots, max_blocks_per_seq) int32 block tables
    ctx_lens: object  # (num_slots,) int32 tokens already in the pool
    new_lens: object  # (num_slots,) int32 real tokens the row brings
    topks: object     # (num_slots,) int32
    reqids: object    # (num_slots,) int32
    gen0: object      # (num_slots,) int32 key-fold base of the row
    temps: object     # (num_slots,) float32
    topps: object     # (num_slots,) float32
    tokens: object    # (width,) int32 the tick's tokens, packed by slot


@dataclasses.dataclass(frozen=True)
class TickLayout:
    """Where each field of a tick lies in the ONE int32 vector the host
    hands the mixed program: the tables flattened, the seven per-slot
    rows in ``TickFields`` order (the two float32 rows as their bits),
    the tokens LAST, so that every offset but the vector's length is the
    same at every token width. The one definition: the host writes
    through ``split`` of a numpy vector (views), the program reads
    through ``split`` of the traced one (static slices)."""

    num_slots: int
    max_blocks_per_seq: int

    @property
    def head(self) -> int:
        """Elements before the tokens."""
        rows = len(TickFields._fields) - 2  # but the tables and the tokens
        return self.num_slots * (self.max_blocks_per_seq + rows)

    def size(self, width: int) -> int:
        return self.head + width

    def split(self, packed) -> TickFields:
        """The fields of a packed vector of any width. A numpy vector
        gives writable views of itself; a traced one static slices, the
        float32 rows bitcast back (the same bits either way)."""
        import numpy as np

        n, m = self.num_slots, self.max_blocks_per_seq
        on_host = isinstance(packed, np.ndarray)
        fields = {"tables": packed[:n * m].reshape(n, m),
                  "tokens": packed[self.head:]}
        for i, name in enumerate(TickFields._fields[1:-1]):
            row = packed[n * (m + i):n * (m + i + 1)]
            if name in ("temps", "topps"):
                if on_host:
                    row = row.view(np.float32)
                else:
                    from jax import lax

                    row = lax.bitcast_convert_type(row, np.float32)
            fields[name] = row
        return TickFields(**fields)

    def host(self, width: int) -> Tuple[object, TickFields]:
        """An all-zero host vector at ``width`` (an empty tick: no row
        brings a token, every table points at the trash block) and its
        fields to write through."""
        import numpy as np

        packed = np.zeros((self.size(width),), np.int32)
        return packed, self.split(packed)


@dataclasses.dataclass
class IssuedTick:
    """A tick whose program is issued and whose samples the host has not
    read: what ``ServeEngine._read`` needs to emit and retire it."""

    step: int
    tick: Tick
    width: int
    sampled: object  # the program's samples, on the device
    # rows that will have produced a token, each with the slot it ran in:
    # chunk rows that complete their prompt, decode rows
    firsts: List[Tuple[Sequence, int]]
    decodes: List[Tuple[Sequence, int]]
    prefilled: int  # prompt tokens its chunk rows brought
    positions: int  # sampled positions that hold a token
    # serve.mixed's links to the traced requests it advanced: the wait for
    # its samples carries them too (obs/trace.py counts both)
    traces: dict


@dataclasses.dataclass
class EngineConfig:
    num_slots: int = 8
    block_size: int = 16
    num_blocks: int = 128
    max_blocks_per_seq: int = 16
    token_budget: int = 512
    kv_dtype: str = "native"  # 'native' | 'int8'
    # Sarathi-style chunked prefill: prompt tokens per chunk row
    prefill_chunk: int = 32
    # shared-prefix KV block reuse (RadixAttention-style trie admission;
    # see SchedulerConfig.prefix_cache)
    enable_prefix_cache: bool = True
    # prompts streaming a chunk each that the SMALL token width has room for
    # beside a decode row in every slot (``mixed_widths``). An engine of many
    # slots and short chunks raises it so that the common tick, whose prompt
    # tokens keep its slots full, still runs at the small width
    small_bucket_chunks: int = SMALL_BUCKET_CHUNKS
    sample_seed: int = 0  # base key for per-request sampling
    flush_interval: int = 50  # registry flush cadence (ticks)
    # ---- resilience (docs/SERVING.md "Resilience") ----
    # per-request deadline defaults (milliseconds from arrival; None =
    # unbounded). A request may carry its own; expiry is checked at
    # every tick boundary and retires the request with terminal status
    # 'timeout', recycling its slot and blocks immediately.
    default_deadline_ms: Optional[float] = None
    default_ttft_deadline_ms: Optional[float] = None
    # overload shedding: watermark admission control over pool pressure
    # (with hysteresis) and waiting-queue depth — above the high
    # watermark `submit` returns a structured Backpressure instead of
    # queueing. None disables (the seed behavior).
    shed_high_watermark: Optional[float] = None
    shed_low_watermark: Optional[float] = None
    max_waiting: Optional[int] = None
    # fleet identity (docs/SERVING.md "The fleet"): set by the router /
    # fleet bench so this replica's metrics carry a ``replica`` label,
    # its serve-request events a ``replica`` field, and its journal a
    # per-replica namespace. None = the single-engine deployment (all
    # telemetry names unchanged).
    replica_id: Optional[int] = None

    def __post_init__(self):
        # the scheduler's checks (prefill_chunk, the watermarks)
        # where the value was written, not at the engine's first tick
        self.scheduler_config()

    @property
    def mixed_width(self) -> int:
        """The most tokens one row brings to a tick: a chunk row up to
        ``prefill_chunk``, a decode row one. The width of the per-row
        query blocks the paged kernel attends over."""
        return self.prefill_chunk

    @property
    def mixed_widths(self) -> Tuple[int, ...]:
        """Token widths ``T`` the engine builds its mixed program at, in
        rising order; a tick runs at the smallest that holds its real
        tokens (``sum(new_len)``). At most two, fixed by the
        configuration: the full width ``num_slots * mixed_width`` holds
        whatever the scheduler admits; the small one holds the common
        tick, a decode row (one token) in every slot and
        ``small_bucket_chunks`` prompts streaming a chunk each, rounded
        up to whole ``LANES`` (below the chip's ridge a tick costs one
        read of the weights whatever it holds, so a finer bucket buys
        nothing and a third program costs its warm-up). Engines whose
        full width is no larger build the one program."""
        full = self.num_slots * self.mixed_width
        small = (self.num_slots
                 + self.small_bucket_chunks * self.prefill_chunk)
        small = -(-small // LANES) * LANES
        return (small, full) if small < full else (full,)

    def scheduler_config(self) -> SchedulerConfig:
        return SchedulerConfig(
            num_slots=self.num_slots, block_size=self.block_size,
            num_blocks=self.num_blocks,
            max_blocks_per_seq=self.max_blocks_per_seq,
            token_budget=self.token_budget,
            prefill_chunk=self.prefill_chunk,
            prefix_cache=self.enable_prefix_cache,
            shed_high_watermark=self.shed_high_watermark,
            shed_low_watermark=self.shed_low_watermark,
            max_waiting=self.max_waiting,
        )


def _spanned_init(init):
    """``serve.init`` around the engine's construction (pools, tables,
    scheduler, layout): once an engine, and closed as a failed span where a
    configuration is refused."""

    @functools.wraps(init)
    def spanned(self, *args, **kwargs):
        with obs.span("serve.init") as init_span:
            init(self, *args, **kwargs)
            init_span.annotate(num_slots=self.config.num_slots,
                               kv_lines=self.pools.kv_lines,
                               pool_bytes=self.pools.device_bytes())

    return spanned


class ServeEngine:
    """Continuous-batching engine over a ``TransformerInferenceModule``."""

    @_spanned_init
    def __init__(self, inference_module, config: Optional[EngineConfig] = None):
        import jax

        self.inf = inference_module
        self.config = config or EngineConfig()
        self.scheduler = ContinuousBatchingScheduler(
            self.config.scheduler_config()
        )
        # mp>1 sharded serving: the pools shard over the model axis and
        # every program runs SPMD over the serving mesh (one mixed
        # program, now partitioned; activation all-reduces come from the
        # same GSPMD constraints training's model axis uses)
        self.mesh = serving_mesh(inference_module)
        self.model_parallel = (
            1 if self.mesh is None
            else int(self.mesh.shape.get("model", 1))
        )
        self._replicated = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._replicated = NamedSharding(self.mesh, P())
        arch = inference_module.architecture
        arch.refuse_paged_serving(self.config.kv_dtype)
        # window attention layers (nn/window_attention.py): each keeps a RING
        # of lines a slot, whatever the context; the pools, the block tables
        # and the scheduler's block count are the full layers' alone
        self.window_layers = arch.window_layers
        self.window_size = arch.window_size
        # windowed LATENT layers (nn/window_latent_attention.py): a ring of
        # latent lines a slot, beside the sparse latent layers' pages
        self.window_latent_layers = arch.window_latent_layers
        if ((self.window_layers or self.window_latent_layers)
                and self.config.kv_dtype != "native"):
            raise ValueError(
                f"kv_dtype {self.config.kv_dtype!r} with window attention "
                "layers: a ring's lines are kept in the model's dtype and "
                "their rounding in int8 is not measured; use "
                "kv_dtype='native'")
        self.pools: PagedKVPools = init_pools(
            inference_module, self.config.num_blocks, self.config.block_size,
            kv_dtype=self.config.kv_dtype, num_slots=self.config.num_slots,
            row_width=self.config.mixed_width,
        )
        # the layers that keep a line a slot, by the name their kind's spans
        # and counters carry: a state that is advanced, never indexed by
        # position, so what skips or rewinds positions is refused, not
        # approximated
        self.line_layers = {kind.NAME: layers for kind, layers
                            in line_layers(self.pools.kinds).items()}
        if self.line_layers and self.config.enable_prefix_cache:
            raise ValueError(
                "enable_prefix_cache with layers that keep a line a slot "
                f"({self.line_layers}): a prefix hit starts a row "
                "past tokens its lines never saw (only the KV of a shared "
                "prefix is kept, no snapshot of the lines); set "
                "enable_prefix_cache=False")
        # recurrent lines advance in a form of their own, a step or a chunk
        # by what a row brings (nn/mamba.py, nn/gated_delta.py: the kinds whose
        # view says ``SPLITS``), by the name their spans and counters carry
        self.split_lines = {
            kind.NAME: layers
            for kind, layers in line_layers(self.pools.kinds).items()
            if getattr(kind, "SPLITS", False)}
        self.ssm_lines = self.split_lines.get(RecurrentStateView.NAME, 0)
        # layers whose two mixers run side by side and keep state under both
        # rules, a paged line and a line a slot (parallel_ssm)
        self.par_lines = sum(
            len(state_views(layer)) > 1
            for layer in inference_module.module.layers)
        import numpy as np

        self._np = np
        self._jax = jax
        # latent attention layers (nn/latent_attention.py): their lines are
        # paged like K and V, written once a token and never rewound
        self.latent_layers = inference_module.architecture.latent_layers
        # residual streams a token carries (nn/hyper_connection.py; 1: the
        # plain residual) and the sub-layers that have a mapping of their
        # own: the streams are activations and are never cached
        self.hc_streams = inference_module.architecture.hc_streams
        self.hc_sublayers = sum(
            getattr(layer, "hc", None) is not None
            for layer in inference_module.module.layers)
        # the SPARSE layers (latent: nn/sparse_latent_attention.py;
        # grouped-query: nn/sparse_attention.py): a query attends over its
        # index_topk best lines, chosen from index keys that are a leaf of a
        # line (a latent line's second, a K / V line's third)
        self.sparse_layers = inference_module.architecture.sparse_layers
        self.index_topk = inference_module.architecture.index_topk
        if self.sparse_layers and self.config.enable_prefix_cache:
            kind = "latent " if self.latent_layers else ""
            raise ValueError(
                f"enable_prefix_cache with sparse {kind}attention layers: a "
                "prefix hit and a copy-on-write fork over a line that holds "
                "the indexer's keys have not been held to the reference; set "
                "enable_prefix_cache=False")
        # KV tokens a tile of the paged kernel holds, at a shard's heads (a
        # sparse latent layer: index keys one step of a row's score loop
        # multiplies), and a SUB-TILE of it, the unit the paged kernel waits
        # for and folds (the other two fold whole tiles)
        if self.sparse_layers:
            self._kv_tile = self._kv_sub = index_tile_tokens(
                self.config.block_size, self.config.max_blocks_per_seq)
        elif self.pools.pool_k[0].ndim == 3:   # a line without a head axis
            self._kv_tile = self._kv_sub = latent_tile_tokens(
                self.config.block_size, self.config.max_blocks_per_seq)
        else:
            pool = self.pools.pool_k[0]
            _, n_kv, head_major = kv_block_layout(
                pool, math.prod(pool.shape[1:]) // self.config.block_size)
            shapes = (
                self.config.block_size, self.config.max_blocks_per_seq,
                n_kv // self.model_parallel, pool.shape[3],
                pool.dtype.itemsize, head_major,
            )
            self._kv_tile = kernel_tile_tokens(*shapes)
            self._kv_sub = kernel_sub_tokens(*shapes)
        n = self.config.num_slots
        # the ONE host operand of a tick and where its fields lie
        self._layout = TickLayout(n, self.config.max_blocks_per_seq)
        # per-slot sampler state, set at admission and copied into every
        # tick's operand (traced per-row arrays in the program)
        self._temp = np.zeros((n,), np.float32)
        self._topk = np.zeros((n,), np.int32)
        self._topp = np.zeros((n,), np.float32)
        self._reqid = np.zeros((n,), np.int32)
        # host arrays handed to the device by _dev, and those of them the
        # counted (non-warm-up) ticks moved: one a tick
        self.host_puts = 0
        self.tick_operands = 0
        self._base_key = self._dev(
            jax.random.PRNGKey(self.config.sample_seed)
        )
        # the last program's (num_slots, 1) samples, on the device: the
        # next program's ``prev`` (zeros before the first)
        self._prev = self._first_prev()
        # the tick whose program is issued and not yet read, if any, and
        # the thread that issued it
        self._issued: Optional[IssuedTick] = None
        self._tick_thread: Optional[int] = None
        # a capture holds whole ticks: the one in flight is read before a
        # capture starts and before it stops
        obs.settle_at_capture_edges(self.settle)
        # tick() calls that issued a program ahead of the read of the one
        # before it, and the others by reason (SYNC_REASONS), warm-up apart
        self.ticks_overlapped = 0
        self.ticks_synchronous: Dict[str, int] = {}
        # token width -> the fused mixed program built at it: every width
        # of config.mixed_widths, all lowered at the first tick
        self._mixed_fns: Dict[int, object] = {}
        # jax_programs_lowered_total as ``serve.lower`` closed (None before)
        self._lowered_at_ready: Optional[int] = None
        # token width -> ticks run at it (warm-up apart): how often the
        # small program serves
        self.mixed_ticks: Dict[int, int] = {}
        # of those ticks, the ones in which a row sampled (temperature > 0):
        # the program's sampler took its sorting branch (sample_rows)
        self.sampled_ticks = 0
        # a routed model (mlp_type moe): the mixed program also returns,
        # in the tick's one host read, how many assignments of real
        # positions each expert received (0: a dense model, which pays
        # nothing for it)
        routed = arch.has_routed_layers
        # the experts this program HOLDS; a share of them also counts the
        # assignments that fell on absent experts (one more entry)
        self.num_experts = arch.moe_held if routed else 0
        self.moe_partial = routed and arch.moe_held < arch.moe_num_experts
        # token width -> (the form a routed layer's expert matmuls take at
        # it, the rows they are given over all routed layers, whether those
        # are a bound under the width's assignments: the load then ends in
        # the passes run beyond the first): static for a program (nn/moe.py
        # serve_rows, serve_bound)
        self._moe_rows = {
            width: inference_module.moe_serve_rows(width)
            for width in self.config.mixed_widths} if routed else {}
        # a looped model (loop_steps > 1): every tick walks the trunk
        # loop_steps times; with an exit gate the mixed program also
        # returns, in the tick's one host read, the exit distribution over
        # the steps summed over the tick's sampled positions
        self.loop_steps = arch.loop_steps
        self.loop_exit_gate = arch.loop_exit_gate
        self.tick_index = 0
        self.finished: List[Sequence] = []
        self.max_concurrent_prefills = 0
        self._next_req_id = 0
        # bench warmup: while True, completions emit no serve-request
        # events (the analyzer's percentiles must mirror the measured
        # workload, not the off-the-clock compile traffic)
        self.warmup_mode = False
        self._reg = obs.get_registry()
        # fleet mode: every metric this replica records carries a
        # ``replica`` label so per-replica pressure/shed/timeout rows
        # stay separable in the obs report (single-engine: no label, so
        # pre-fleet metric names — and their tests — are unchanged)
        self.replica_id = self.config.replica_id
        self._labels = (
            {"replica": str(self.replica_id)}
            if self.replica_id is not None else None
        )
        self._replica_fields = (
            {"replica": self.replica_id}
            if self.replica_id is not None else {}
        )
        # the registry's metrics this engine has touched, by (name, label
        # values): _metric (as obs.span holds its histograms)
        self._handles: Dict[object, object] = {}
        # what one tick's rows emitted, counted by the tick in one go
        # (_flush_tick_telemetry): inter-token gaps, tokens, prompt tokens
        self._tick_itl: List[float] = []
        self._tick_tokens = 0
        self._tick_prefilled = 0
        self._prefix_hits_flushed = 0  # scheduler counter already mirrored
        # the scheduler's eviction totals, likewise: seconds and blocks
        self._evict_flushed = (0.0, 0)
        # spans the recorder holds from before this are another engine's
        self._created_ns = time.monotonic_ns()
        self.prefilled_tokens = 0  # prompt tokens actually prefilled
        # resilience state (docs/SERVING.md "Resilience"): graceful
        # drain, overload-shed / deadline-timeout tallies, and the
        # crash-replay request journal
        self.draining = False
        self.shed_count = 0
        self.timeout_count = 0
        self.journal = None
        self._journal_pending: Dict[int, List[int]] = {}
        # live requests carrying any deadline: the tick-boundary expiry
        # sweep is skipped entirely while this is zero (the default
        # no-deadline configuration must not pay O(live) per tick).
        # Guarded by its own lock: in a fleet the router's submit thread
        # increments while the replica's tick thread decrements, and a
        # lost update that read 0 would silently skip live deadlines.
        self._deadline_live = 0
        self._deadline_lock = threading.Lock()
        if self.window_layers or self.window_latent_layers:
            # what the window layers keep, fixed when the pools are built: it
            # does not grow with the context
            fields = [kind for kind in line_layers(self.pools.kinds)
                      for _ in kind.LINES]     # the kind of each list of lines
            for name in ("window", "window_latent"):
                rings = [a for kind, lines in zip(fields, self.pools.lines)
                         if kind.NAME == name for a in lines]
                if not rings:
                    continue
                setattr(self, f"{name}_ring_lines", int(rings[0].shape[1]))
                self._gauge(f"serve_{name}_ring_lines").set(rings[0].shape[1])
                self._gauge(f"serve_{name}_ring_gb").set(
                    sum(a.size * a.dtype.itemsize for a in rings) / 1e9)

    # ------------------------------------------------------------- intake
    def submit(self, prompt: List[int], max_new_tokens: int,
               arrival_s: Optional[float] = None,
               eos_token_id: Optional[int] = None,
               temperature: float = 0.0,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None,
               deadline_ms: Optional[float] = None,
               ttft_deadline_ms: Optional[float] = None,
               req_id: Optional[int] = None,
               force: bool = False,
               count_shed: bool = True,
               trace: Optional[str] = None):
        """Admit one request, or reject it with a structured
        :class:`Backpressure` (draining, or over the shed watermarks) —
        the signal a fleet router retries elsewhere on. Returns the
        :class:`Sequence` on admission.

        ``req_id`` pins the request's identity (crash-replay: the
        sampler keys fold the id, so a journal replay MUST reuse it);
        by default ids are assigned sequentially. ``deadline_ms`` /
        ``ttft_deadline_ms`` override the EngineConfig defaults.
        ``force`` bypasses drain/backpressure rejection — journal
        replay re-enqueues recovery work, not new load, and must never
        be shed by the very overload policy the crash left armed.
        ``count_shed=False`` returns the Backpressure WITHOUT counting
        or journaling it: the fleet router passes it because a rejection
        it retries on another replica is not a client-visible shed (the
        router counts the fleet-level rejection itself, and the journal
        shed records must map 1:1 onto consumed workload items).

        ``trace`` pins the request's distributed-trace id explicitly
        (journal replay re-adopting a crashed request's identity); by
        default the ambient ``obs.trace_context`` — set by the bench at
        submit, or adopted from an RPC envelope by the replica worker —
        is inherited. Warmup traffic never allocates or adopts one."""
        get_fault_plan().fire("serve.admit")
        if force:
            bp = None
        elif self.draining:
            bp = Backpressure(
                reason="draining",
                pool_pressure=round(self.scheduler.pool_pressure(), 4),
                waiting=len(self.scheduler.waiting), draining=True,
            )
        else:
            bp = self.scheduler.admission_backpressure()
        if bp is not None:
            if not self.warmup_mode and count_shed:
                # a draining rejection is shutdown, not overload: it
                # stays out of the shed rate the overload gates judge
                # AND out of the journal (the bench does not consume
                # the workload item — it stays unsubmitted)
                if not bp.draining:
                    self.shed_count += 1
                    self._counter("serve_requests_shed_total").inc()
                    if self.journal is not None:
                        self.journal.record_shed(bp.reason)
                logger.log_event(
                    "serve-shed", _level="debug", reason=bp.reason,
                    pool_pressure=bp.pool_pressure, waiting=bp.waiting,
                    **self._replica_fields,
                )
            return bp
        if req_id is None:
            req_id = self._next_req_id
        self._next_req_id = max(self._next_req_id, req_id + 1)
        if self.warmup_mode:
            # warmup hygiene: traffic the --warmup flag keeps off the
            # books must not enter the trace-coverage denominator either
            trace = None
        elif trace is None:
            trace = obs.current_trace_id()
        req = Request(
            req_id=req_id, prompt=list(prompt),
            max_new_tokens=max_new_tokens,
            arrival_s=time.monotonic() if arrival_s is None else arrival_s,
            eos_token_id=eos_token_id,
            temperature=temperature, top_k=top_k, top_p=top_p,
            deadline_ms=(
                deadline_ms if deadline_ms is not None
                else self.config.default_deadline_ms
            ),
            ttft_deadline_ms=(
                ttft_deadline_ms if ttft_deadline_ms is not None
                else self.config.default_ttft_deadline_ms
            ),
            trace_id=trace,
        )
        if trace is not None:
            # the admit span is the trace's first engine-side record;
            # re-assert the context so an explicitly-passed trace
            # (journal replay, orphan re-dispatch) links up even with
            # no ambient context on this thread
            with obs.trace_context(trace):
                with self._span("serve.admit", req=req_id,
                                **self._replica_fields):
                    seq = self.scheduler.add_request(req)
        else:
            seq = self.scheduler.add_request(req)
        if req.deadline_ms is not None or req.ttft_deadline_ms is not None:
            with self._deadline_lock:
                self._deadline_live += 1
        if not self.warmup_mode:
            self._counter("serve_requests_admitted_total").inc()
            if self.journal is not None:
                self.journal.record_submit(req)
        return seq

    def attach_journal(self, journal) -> None:
        """Wire the crash-replay request journal (serve/journal.py):
        every non-warmup submission, tick's emitted tokens, and terminal
        status is appended so a supervised relaunch can replay."""
        self.journal = journal

    def begin_drain(self) -> None:
        """Graceful drain (the serving mirror of the trainer's
        coordinated preemption): admit nothing new — `submit` returns
        Backpressure(reason='draining') — while in-flight requests run
        to completion or their deadlines. The bench's tick loop stops
        submitting and exits 0 once the scheduler empties."""
        if self.draining:
            return
        self.draining = True
        logger.log_event(
            "serve-drain", tick=self.tick_index,
            running=len(self.scheduler.running),
            waiting=len(self.scheduler.waiting),
            **self._replica_fields,
        )

    # --------------------------------------------------- device programs
    def _dev(self, x):
        """ONE host array -> the operand a program call takes for it: one
        host-to-device transfer. Off-mesh that is the numpy array itself,
        which the jitted call's own argument path moves (on the chip 0.2-0.3
        ms a tick less than a ``device_put`` ahead of the call below the
        knee and no slower at 16 rows, PERF.md section 6, PR 37: one trip
        through the runtime instead of two); on a serving mesh it is
        device_put REPLICATED so the call mixes cleanly with the
        mesh-sharded pools and params. A tick moves its whole host state
        (tables, lengths, tokens, sampler rows) through here as the one
        packed vector of ``TickLayout``: a transfer costs the host ~0.26 ms
        whatever it carries, and the chip has nothing to run until the last
        has landed."""
        self.host_puts += 1
        if self._replicated is None:
            return x
        return self._jax.device_put(x, self._replicated)

    def _first_prev(self):
        """Zeros in ``prev``'s shape that a program call takes exactly as
        it takes a program's own samples, so that the call after a
        program's first finds its executable: on a serving mesh replicated;
        off it committed to the device the params or the pools are
        committed to, and uncommitted where they are not (a jitted call's
        outputs are committed if any operand is)."""
        zeros = self._np.zeros((self.config.num_slots, 1), self._np.int32)
        if self._replicated is not None:
            return self._jax.device_put(zeros, self._replicated)
        for leaf in self._jax.tree_util.tree_leaves(
                (self.inf.params, self._pool_state())):
            if getattr(leaf, "committed", False):
                return self._jax.device_put(zeros, leaf.sharding)
        return self._jax.numpy.asarray(zeros)

    def _metric(self, kind: str, name: str, labels: dict):
        """The registry's counter / gauge / histogram ``name`` under
        ``labels`` (and this replica's): found through the registry's
        lock at the label set's first use, from then on held here."""
        key = (name, *labels.values()) if labels else name
        handle = self._handles.get(key)
        if handle is None:
            if labels:
                labels.update(self._labels or {})
            handle = self._handles[key] = getattr(self._reg, kind)(
                name, labels or self._labels)
        return handle

    def _counter(self, name: str, **labels):
        return self._metric("counter", name, labels)

    def _gauge(self, name: str):
        return self._metric("gauge", name, {})

    def _histogram(self, name: str):
        return self._metric("histogram", name, {})

    def _pool_state(self):
        return self.pools.state()

    def _absorb(self, state) -> None:
        self.pools.absorb_state(state)

    def _span(self, name: str, **fields):
        """obs.span, silenced during bench warmup: warmup ticks carry the
        multi-second first-call jit compile, and a span record for them
        would dominate the analyzer's tick-time attribution for exactly
        the traffic --warmup exists to keep off the books."""
        if self.warmup_mode:
            import contextlib

            return contextlib.nullcontext()
        return obs.span(name, **fields)

    @staticmethod
    def _trace_fields(seqs, key: str = "traces") -> dict:
        """Span annotation linking a batch span to every traced request
        it advanced: ``{key: [trace ids]}``, empty dict when none are
        traced so trace-less runs emit byte-identical span records. The
        analyzer (obs/trace.py) indexes batch spans by these lists."""
        out: List[str] = []
        for s in seqs:
            tid = s.request.trace_id
            if tid and tid not in out:
                out.append(tid)
        return {key: out} if out else {}

    def _sample_grid(self, logits, temps, topps, topks, reqids, gen0,
                     base_key):
        """Sample every position of a (rows, s, vocab) logit grid with
        the key plain decode would use there: position ``i`` of a row
        draws with ``fold_in(fold_in(base, req), gen0 + i)``. The mixed
        program hands it ONE position a row (``s = 1``), the row's last
        real one, with ``gen0`` the tokens the request has generated by
        then: a chunk row that completes its prompt so draws its first
        token with the key of the request's first generated token, and a
        preempted and resumed request redraws the tokens it had."""
        from ..models.transformer.inference import (
            request_sample_key, sample_rows,
        )
        jnp = self._jax.numpy

        rows, s, vocab = logits.shape
        positions = gen0[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        keys = self._jax.vmap(
            self._jax.vmap(request_sample_key, in_axes=(None, None, 0)),
            in_axes=(None, 0, 0),
        )(base_key, reqids, positions)  # (rows, s, 2)

        def rep(x):
            return jnp.repeat(x, s, axis=0)

        flat = sample_rows(
            logits.reshape(rows * s, vocab), rep(temps), rep(topks),
            keys.reshape(rows * s, keys.shape[-1]), top_ps=rep(topps),
        )
        return flat.reshape(rows, s)

    def _build_mixed_fn(self, width: int):
        """ONE fused Sarathi-style program per tick, over a TOKEN-MAJOR
        batch: the tick's real tokens (a decode row's last token, a chunk
        row's ``<= prefill_chunk`` prompt tokens, nothing for an empty slot) lie
        back to back in slot order in ``tokens`` (``width``,), and the
        trunk (norms, QKV, rotary, MLP or routed MLP, output projection)
        runs over those ``width`` positions, shaped
        ``packed_batch_shape(width, mixed_width)`` — not over ``num_slots
        x mixed_width`` padded ones. Below the chip's ridge a tick so costs
        one read of the weights. Rows are tagged purely by traced per-row
        lengths, so a tick dispatches exactly one executable, however
        many sequences are prefilling.

        Addressing is derived on the device from ``new_lens`` alone
        (``packed_token_map``: a token's row by comparison against the
        rows' running ends, its offset from the row's start). The tick's
        whole host state arrives as ONE int32 vector (``TickLayout``:
        tables, lengths, sampler rows, the tokens last) and the program
        opens with static slices of it, so it takes five arguments:
        params, the donated pool state, that vector, the key, and
        ``prev``: the ``(num_slots, 1)`` samples of the program
        before this one, which never left the device (zeros before the
        first). The engine issues a tick before it has read the one before
        (``tick``), so a decode row whose last token is still in flight
        brings ``IN_FLIGHT`` in its place and the program opens with
        ``tokens = where(tokens < 0, prev[row, 0], tokens)``: a row that
        decoded and a chunk row that completed its prompt in the tick before
        both sampled their one position. Rotary positions are
        ``ctx_lens[row] + offset``. The paged branch
        (``Attention._paged_attention``) scatters each token's K/V through
        its row's table (what is no token goes to the trash block; rows
        never share pool blocks, so fusing their writes is exact),
        regroups the queries to the ``(num_slots, mixed_width)`` blocks
        the kernel takes and gathers its output back to token order.

        A row samples ONE position, its last real one (``new_len - 1``: a
        decode row's token, the end of a chunk row that completes its
        prompt), with its plain-decode key (``_sample_grid``). The program
        GATHERS that position of every row from the trunk's activations
        before the vocab projection, so the head prices ``num_slots``
        positions whatever the tick's token width.

        One program per width of ``EngineConfig.mixed_widths`` (two at
        most), all lowered at the engine's first tick — pinned in the
        serve_decode golden.

        The program returns the samples twice: what the host reads, and
        ``feed``, the grid alone, the next program's ``prev`` (one shape
        whatever follows the grid in the host's read, and at either width).
        A routed model's program returns ONE int32 vector instead of the
        grid for the host: the sampled grid flattened, then the (E,) load of the
        tick's real positions (``_run_layers(moe_load=True)``; behind it a
        sparse stack's count of tie breaks, ``_record_tie_breaks``), so that
        the load costs the tick no second host read. A looped model with
        an exit gate appends likewise the (loop_steps,) float32 exit
        distribution, summed over the sampled positions that hold a token,
        as its bits (``_run_layers(exit_p=True)``)."""
        from ..nn.attention import packed_token_map

        jnp = self._jax.numpy
        row_width = self.config.mixed_width
        shape = packed_batch_shape(width, row_width)
        routed = self.num_experts > 0
        gated = self.loop_exit_gate

        def mixed(params, state, packed, base_key, prev):
            tick = self._layout.split(packed)
            tables, ctx_lens, new_lens = (
                tick.tables, tick.ctx_lens, tick.new_lens)
            token_map = packed_token_map(new_lens, shape, row_width)
            row, offset = token_map.row, token_map.offset
            # what is no token keeps position 0 (finite rotary, whatever
            # the last row's context)
            pos = jnp.where(offset < new_lens[row], ctx_lens[row] + offset, 0)
            # a decode row whose last token the host has not read brings
            # IN_FLIGHT in its place: the token is the sample its row drew
            # in the program before this one
            tokens = tick.tokens.reshape(shape)
            tokens = jnp.where(tokens < 0, prev[:, 0][row], tokens)
            batch = self.inf._make_batch(tokens, pos)
            views = build_layer_views(state, tables, ctx_lens, new_lens,
                                      token_map, kinds=self.pools.kinds)
            # the row's sampled position (0 where it brings none). The
            # window keeps its unit axis and its sum with a one-element iota,
            # and ``_sample_grid`` its grid of one column: without them the
            # program computes the same values from ANOTHER lowered text,
            # which is the compile cache's key and what the ledger measured
            last = jnp.clip(new_lens - 1, 0, row_width - 1)
            window = last[:, None] + jnp.arange(1, dtype=jnp.int32)
            logits, new_views, *extra = self.inf._run_layers(
                params, batch, views, None,
                gather_index=jnp.take_along_axis(
                    token_map.row_tokens, window, axis=1),
                moe_load=routed, exit_p=gated,
            )
            # ``gen0`` is the key-fold base of the row's FIRST token: at its
            # last, the sample draws with the (request, position) key plain
            # decode would use there
            with self._jax.named_scope("head"):  # beside final norm and head
                sampled = self._sample_grid(
                    logits, tick.temps, tick.topps, tick.topks, tick.reqids,
                    tick.gen0 + last, base_key
                )
            feed = sampled  # the next program's ``prev``
            if routed:
                sampled = jnp.concatenate([sampled.reshape(-1), extra[0]])
            if gated:
                # (loop_steps, rows, 1): the sampled positions that hold a
                # token
                held = window < new_lens[:, None]
                exit_p = jnp.sum(jnp.where(held[None], extra[-1], 0.0),
                                 axis=(1, 2))
                sampled = jnp.concatenate([
                    sampled.reshape(-1),
                    self._jax.lax.bitcast_convert_type(exit_p, jnp.int32)])
            return sampled, feed, state_from_views(new_views)

        # the pool state dies with each call (_absorb takes the returned
        # one) and comes back in the structure it went in
        # (state_from_views), so each donated pool is aliased to the
        # output computed from it and scattered into in place (the alias
        # table is pinned in test_kvcache.py): anything else costs a copy
        # of every layer's whole pool every tick. CPU can't donate (every
        # call would warn).
        donate = (1,) if self._jax.default_backend() != "cpu" else ()
        # a profile keys an operation by its module's name and its own: two
        # widths' programs under ONE name are taken for each other's there
        mixed.__name__ = f"mixed_{width}"
        return self._jax.jit(mixed, donate_argnums=donate)

    def _lower_mixed_programs(self) -> None:
        """Build the program of every token width and run each once on an
        EMPTY tick (no row brings a token: every write lands in the trash
        block, the samples are dropped), so that all of them are traced,
        lowered and compiled (or loaded from the compile cache) at the
        engine's first tick, through the very call path later ticks take.
        A width first needed minutes into serving must not pay its
        lowering then."""
        # a span of its own, warm-up or not (the tick's spans are silenced
        # there, _span): this is where an engine's set-up goes, and the
        # ``compile.*`` rows of ``mixed_<width>`` fall inside it
        with obs.span("serve.lower", widths=list(self.config.mixed_widths)):
            # twice: a program's second call takes ``prev`` from a program,
            # as every later one does, and must find the first call's
            # executable
            for width in 2 * self.config.mixed_widths:
                fn = self._mixed_fns.get(width)
                if fn is None:
                    fn = self._mixed_fns[width] = self._build_mixed_fn(width)
                empty, _ = self._layout.host(width)
                _, self._prev, state = fn(
                    self.inf.params, self._pool_state(), self._dev(empty),
                    self._base_key, self._prev)
                self._absorb(state)
        # ready: every program a tick can run is lowered. What is lowered in
        # this process from here on is a recompile (stats_snapshot)
        self._lowered_at_ready = compile_events.programs_lowered()
        self._gauge("serve_ready_seconds").set(
            time.monotonic() - obs.process_start_s())

    # ------------------------------------------------------------- ticking
    def _reset_rows(self, slots: List[int]) -> None:
        for s in slots:
            self._temp[s] = 0.0
            self._topk[s] = 0
            self._topp[s] = 0.0
            self._reqid[s] = 0

    def _admit_slot(self, seq: Sequence) -> None:
        """Per-slot sampler state for a newly-admitted sequence."""
        slot = seq.slot
        self._temp[slot] = seq.request.temperature
        self._topk[slot] = seq.request.top_k or 0
        self._topp[slot] = seq.request.top_p or 0.0
        self._reqid[slot] = seq.request.req_id

    def _apply_cow(self, pairs) -> None:
        """Copy-on-write block forks the scheduler ordered this tick:
        duplicate pool block ``src`` into freshly-allocated ``dst``
        across every layer (K, V, int8 scales, a sparse layer's index
        keys), in every cache line of the layer's pool (a looped model's
        steps), BEFORE the tick's programs run. Eager host-dispatched ops — forks never occur in
        the steady state (full-block prefix sharing places writes past
        every shared block), so this path stays off the hot loop."""
        if not pairs:
            return
        p = self.pools
        for src, dst in pairs:
            src, dst = p.line_blocks(src), p.line_blocks(dst)
            for arrs in p.paged_leaves():
                for i in range(len(arrs)):
                    arrs[i] = arrs[i].at[dst].set(arrs[i][src])
        self._counter("serve_cow_forks_total").inc(len(pairs))

    def _issue(self, t: Tick, step: int) -> IssuedTick:
        """The fused tick (Sarathi piggybacking), as far as the host can
        take it without the program's result: ONE program call covers
        every prefill chunk AND the whole decode batch, the rows' real
        tokens packed back to back in slot order into the smallest token
        width that holds them, each row tagged by its traced
        ``new_len``/``ctx_len``. A decode row whose last token is still
        in flight carries ``IN_FLIGHT`` for it and the program takes the
        token from ``prev``, the samples of the program before it, on the
        device.

        The rows' sequences are PROJECTED past this tick as it is issued
        (``num_cached`` by what the row brings, ``in_flight`` by the token
        it will produce), so the next tick can be scheduled and issued
        before this one is read (``_read``)."""
        np = self._np
        cfg = self.config
        if not self._mixed_fns:
            self._lower_mixed_programs()
        traces = {**self._trace_fields(t.decodes),
                  **self._trace_fields(t.prefills, "chunk_traces")}
        with self._span("serve.mixed", step=step,
                        decodes=len(t.decodes), chunks=len(t.prefills),
                        **traces) as mixed_span:
            with self._span("serve.mixed.build", step=step):
                n = cfg.num_slots
                row_tokens: List[List[int]] = [[]] * n  # by slot
                # written at the widest token width, handed over at the
                # tick's own: the tokens lie last, so that is a prefix
                packed, tick = self._layout.host(cfg.mixed_widths[-1])
                tables, ctx, new_lens, gen0 = (
                    tick.tables, tick.ctx_lens, tick.new_lens, tick.gen0)
                firsts = []  # (seq, slot)
                prefilled = 0
                for seq in t.prefills:
                    slot = seq.slot
                    prompt = seq.resume_prompt
                    start = seq.num_cached
                    n_real = min(cfg.prefill_chunk, seq.prefill_len - start)
                    assert n_real > 0, \
                        "chunk row scheduled with nothing to prefill"
                    row_tokens[slot] = prompt[start:start + n_real]
                    new_lens[slot] = n_real
                    ctx[slot] = start
                    tables[slot, :len(seq.blocks)] = seq.blocks
                    if start == seq.prefix_cached:
                        # first chunk of this admission (prefix hits
                        # start past 0)
                        self._admit_slot(seq)
                    # the chunk's last REAL position must draw with the
                    # key plain decode uses for the request's first
                    # generated token
                    gen0[slot] = len(seq.generated) - (n_real - 1)
                    seq.num_cached = start + n_real
                    prefilled += n_real
                    if seq.num_cached == seq.prefill_len:
                        firsts.append((seq, slot))
                        seq.in_flight += 1
                for seq in t.decodes:
                    slot = seq.slot
                    last = IN_FLIGHT if seq.in_flight else seq.generated[-1]
                    row_tokens[slot] = [last]
                    new_lens[slot] = 1
                    ctx[slot] = seq.num_cached
                    tables[slot, :len(seq.blocks)] = seq.blocks
                    gen0[slot] = len(seq.generated) + seq.in_flight
                    seq.num_cached += 1
                    seq.in_flight += 1
                # inactive rows keep all-trash tables + new_len 0: they
                # bring no token and expose zero visible slots
                real = [tok for row in row_tokens for tok in row]
                # recurrent lines advance row by row below the full width,
                # which holds so many multi-token rows (nn/mamba.py)
                multi = (int(np.count_nonzero(new_lens > 1))
                         if self.split_lines else 0)
                width = next(
                    w for w in cfg.mixed_widths if len(real) <= w
                    and multi <= split_capacity(w, cfg.mixed_width))
                tick.tokens[:len(real)] = real
                # a row that brings no token samples nothing: a sequence
                # whose last token is in flight keeps its slot a tick longer
                # and must not hold the tick in the sampler's sorting branch
                tick.temps[:] = np.where(new_lens > 0, self._temp, 0.0)
                tick.topps[:] = self._topp
                tick.topks[:], tick.reqids[:] = self._topk, self._reqid
                packed = packed[:self._layout.size(width)]
            puts = self.host_puts
            with self._span("serve.mixed.dispatch", step=step) as dispatch:
                sampled, self._prev, state = self._mixed_fns[width](
                    self.inf.params, self._pool_state(), self._dev(packed),
                    self._base_key, self._prev,
                )
                # futures: the runtime queues the next program behind this
                self._absorb(state)
                if dispatch is not None:  # not warming up
                    moved = self.host_puts - puts
                    dispatch.annotate(operands=moved, bytes=packed.nbytes)
                    self.tick_operands += moved
            if not self.warmup_mode:  # mixed_span is a span
                # every value it reads was known before the call: it runs
                # here, while the chip is busy, not ahead of the dispatch
                self._annotate_mixed(mixed_span, width, len(real), ctx,
                                     new_lens, multi)
        if len(t.prefills) > self.max_concurrent_prefills:
            self.max_concurrent_prefills = len(t.prefills)
        return IssuedTick(
            step=step, tick=t, width=width, sampled=sampled, firsts=firsts,
            decodes=[(seq, seq.slot) for seq in t.decodes],
            prefilled=prefilled,
            positions=int(np.count_nonzero(new_lens)), traces=traces)

    def _read(self, issued: IssuedTick) -> None:
        """What a tick owes once its program's samples are on the host, its
        spans under the ``step`` it was issued at: ``serve.mixed.wait``,
        ``serve.emit`` (a token appended and stamped for every row, the
        tick's counters) and ``serve.retire``. A row whose sequence
        ended at the EOS read a tick before (``_emit_row``) is dropped."""
        np = self._np
        step = issued.step
        n = self.config.num_slots
        with self._span("serve.mixed.wait", step=step, **issued.traces):
            # the tick's ONE deliberate device->host pull: the sampled
            # token grid must land on host to be emitted to callers
            host_samples = np.asarray(issued.sampled)
        with self._span("serve.emit", step=step) as emit:
            # a slot's one sample, then what a routed or a gated model's
            # program appended to the same read
            host_samples = host_samples.reshape(-1)
            samples, tail = host_samples[:n].tolist(), host_samples[n:]
            if self.num_experts:
                if self.sparse_layers:
                    tail = self._record_tie_breaks(tail, emit)
                *_, bounded = self._moe_rows[issued.width]
                self._record_moe_load(tail, emit, bounded)
            if self.loop_exit_gate:
                self._record_exit(tail.view(np.float32), issued.positions,
                                  emit)
            now = time.monotonic()
            rows = 0
            self._tick_prefilled += issued.prefilled
            for seq, slot in issued.firsts + issued.decodes:
                rows += self._emit_row(seq, samples[slot], now)
            self._flush_tick_telemetry(emit, rows)
        with self._span("serve.retire", step=step) as retire_span:
            finished = self._retire_tick(issued.tick)
            if retire_span is not None:  # not warming up
                retire_span.annotate(finished=finished)

    def _emit_row(self, seq: Sequence, tok: int, now: float) -> bool:
        """One row's sample to its sequence; False for a row issued behind
        a token that turned out to be the EOS: its sequence was finished
        when that token was read, its sample is dropped (what the row wrote
        lies in blocks and lines the next admission resets)."""
        if seq.state is not SequenceState.RUNNING:
            return False
        seq.in_flight -= 1
        self._emit_token(seq, tok, now)
        eos = seq.request.eos_token_id
        if eos is not None and tok == eos:
            seq.in_flight = 0  # the row already issued behind it is dropped
        return True

    def _flush_tick_telemetry(self, emit_span, rows: int) -> None:
        """What the tick's rows emitted, counted once a tick where it was
        once a row or a token: the counters take their sums, the
        inter-token histogram one bulk observation. ``rows``: the rows
        emitted for (chunk rows that finished their prompt, decode rows)."""
        tokens, prefilled = self._tick_tokens, self._tick_prefilled
        itl, self._tick_itl = self._tick_itl, []
        self._tick_tokens = self._tick_prefilled = 0
        if self.warmup_mode:
            return
        emit_span.annotate(rows=rows, tokens=tokens)
        if prefilled:
            self.prefilled_tokens += prefilled
            self._counter("serve_prefill_tokens_total").inc(prefilled)
        if tokens:
            self._counter("serve_tokens_generated_total").inc(tokens)
        if itl:
            self._histogram("serve_itl_seconds").observe_many(itl)

    def _annotate_mixed(self, mixed_span, width: int, tokens: int, ctx,
                        new_lens, multi: int) -> None:
        """What a counted tick says of itself on ``serve.mixed`` and in
        the per-tick counters: ``tokens`` real tokens at ``width``, the
        rows' ``ctx`` / ``new_lens`` (the operand's own views), ``multi``
        rows that bring more than one token to recurrent lines. Called
        once the program is issued: nothing here depends on its result,
        and the row is written when the span closes."""
        np = self._np
        # rows that hold a visible slot and the paged kernel's tiles
        # among them: of a call's kv_tiles fetches, kv_rows - 1 are
        # first tiles that start under another row's fold; kv_subtiles
        # of the tiles' sub-tiles hold a slot, and only those are folded
        held = ctx + new_lens
        # the predicate of the program's sampler (sample_rows), known
        # before the call: the rows that bring a token and a temperature
        sampled_rows = int(np.count_nonzero(self._temp[new_lens > 0] > 0.0))
        mixed_span.annotate(
            width=width, tokens=tokens,
            sampled_rows=sampled_rows,
            kv_rows=int(np.count_nonzero(held)),
            kv_tiles=int((-(-held // self._kv_tile)).sum()),
            kv_subtiles=int((-(-held // self._kv_sub)).sum()),
        )
        self.sampled_ticks += sampled_rows > 0
        self._counter(
            "serve_sampler_ticks_total",
            path="sampled" if sampled_rows else "greedy",
        ).inc()
        # rows whose per-slot lines advanced, in every layer of a kind
        rows = int(np.count_nonzero(new_lens))
        for kind, lines in self.line_layers.items():
            mixed_span.annotate(**{f"{kind}_rows": rows,
                                   f"{kind}_lines": lines})
            self._counter(
                f"serve_{kind}_state_updates_total").inc(rows * lines)
        for kind, lines in self.split_lines.items():
            # the form that advanced them (nn/mamba.py): at the full
            # width whole rows, below it a step or a gathered chunk
            mixed_span.annotate(**{f"{kind}_step_rows": rows - multi,
                                   f"{kind}_chunk_rows": multi})
            paths = ({"whole": rows} if width == self.config.mixed_widths[-1]
                     else {"step": rows - multi, "chunk": multi})
            for path, count in paths.items():
                self._counter(f"serve_{kind}_rows_total", path=path).inc(
                    count * lines)
        if self.latent_layers:
            # what a latent layer's attention reads and multiplies
            # this tick: the lines of its rows (context + new), and
            # the (query, visible line) pairs
            n_new = new_lens.astype(np.int64)
            lines = int(held.sum())
            mixed_span.annotate(
                latent_layers=self.latent_layers, latent_lines=lines,
                latent_pairs=int(
                    (n_new * ctx + n_new * (n_new + 1) // 2).sum()))
            self._counter("serve_latent_lines_read_total").inc(
                lines * self.latent_layers)
        if self.sparse_layers:
            # what a sparse layer's indexer scores and what its attention
            # then reads: the index keys of the rows that bring tokens, the
            # (query, visible line) pairs the indexer scores, and the pairs
            # left after each query chose min(index_topk, what it sees)
            n_new = new_lens.astype(np.int64)
            seen = ctx.astype(np.int64)
            pairs = n_new * seen + n_new * (n_new + 1) // 2
            # a row's first `dense` new tokens still see no more than
            # index_topk lines and choose them all
            dense = np.clip(self.index_topk - seen, 0, n_new)
            chosen = (dense * seen + dense * (dense + 1) // 2
                      + (n_new - dense) * self.index_topk)
            busy = held[new_lens > 0]
            index_lines = int(busy.sum())
            mixed_span.annotate(
                sparse_layers=self.sparse_layers, index_lines=index_lines,
                index_pairs=int(pairs.sum()), chosen_pairs=int(chosen.sum()),
                # the least lines the attention reads: the union of a row's
                # queries' choices is no smaller
                chosen_lines=int(np.minimum(busy, self.index_topk).sum()))
            self._counter("serve_index_lines_read_total").inc(
                index_lines * self.sparse_layers)
            self._counter("serve_sparse_chosen_pairs_total").inc(
                int(chosen.sum()) * self.sparse_layers)
            if not self.latent_layers:
                # the rows of ONE token: a grouped-query sparse layer attends
                # each through the paged kernel under the mask of its choice
                # (a latent one still folds them in plain XLA)
                single_rows = int(np.count_nonzero(new_lens == 1))
                mixed_span.annotate(sparse_single_rows=single_rows)
                self._counter("serve_sparse_single_rows_total").inc(
                    single_rows * self.sparse_layers)
        if self.window_layers or self.window_latent_layers:
            # what a window layer's attention does this tick: its rows, those
            # whose context is past the window (there the window cuts what a
            # full layer would read), the ring lines the rows' queries see and
            # the (query, visible line) pairs
            n_new = new_lens.astype(np.int64)
            first = ctx.astype(np.int64)                  # a row's first query
            w = self.window_size
            past = int(np.count_nonzero(held[new_lens > 0] > w))
            # query i of a row sees min(first + i + 1, w) lines
            upto = np.clip(w - first, 0, n_new)   # queries that see under w
            pairs = (upto * (first + 1) + upto * (upto - 1) // 2
                     + (n_new - upto) * w)
            lines = np.where(n_new > 0, np.minimum(held, w - 1 + n_new), 0)
        if self.window_layers:
            mixed_span.annotate(
                window_layers=self.window_layers,
                window_rows_past=past, window_visible_lines=int(lines.sum()),
                window_pairs=int(pairs.sum()),
                # and a full layer's pairs beside them: every line up to the
                # query's own
                full_pairs=int((n_new * first + n_new * (n_new + 1) // 2).sum()))
            self._counter("serve_window_rows_total").inc(
                rows * self.window_layers)
            self._counter("serve_window_rows_past_window_total").inc(
                past * self.window_layers)
        if self.window_latent_layers:
            # the same of the windowed latent layers, and the form that
            # attended: the rows of one token in one call of the ring kernel,
            # a chunk row in a call of its own
            single = int(np.count_nonzero(new_lens == 1))
            mixed_span.annotate(
                window_latent_layers=self.window_latent_layers,
                window_latent_rows_past=past,
                window_latent_visible_lines=int(lines.sum()),
                window_latent_pairs=int(pairs.sum()),
                window_latent_single_rows=single,
                window_latent_chunk_rows=rows - single)
            for path, count in (("single", single), ("chunk", rows - single)):
                self._counter("serve_window_latent_rows_total", path=path).inc(
                    count * self.window_latent_layers)
        if self.hc_sublayers:
            # what the residual path moves this tick: every real token's
            # streams through every sub-layer's mapping
            mixed_span.annotate(hc_streams=self.hc_streams,
                                hc_sublayers=self.hc_sublayers)
            self._counter("serve_hc_token_sublayers_total").inc(
                tokens * self.hc_sublayers)
        if self.par_lines:
            mixed_span.annotate(par_lines=self.par_lines)
            self._counter("serve_parallel_mixer_passes_total").inc(
                self.par_lines)
        if self.num_experts:
            # the rows the tick's expert matmuls were given; with
            # serve_moe_assignments_total (the real, held assignments
            # they are for) the share of them that is real work
            path, moe_rows, _ = self._moe_rows[width]
            mixed_span.annotate(moe_rows=moe_rows)
            self._counter("serve_moe_rows_total", path=path).inc(
                moe_rows)
        if self.loop_steps > 1:
            mixed_span.annotate(loop_steps=self.loop_steps)
            self._counter("serve_loop_layer_passes_total").inc(
                self.loop_steps * self.pools.num_layers)
        self.mixed_ticks[width] = self.mixed_ticks.get(width, 0) + 1
        self._counter("serve_mixed_ticks_total", width=width).inc()

    def _record_tie_breaks(self, load, emit_span):
        """A sparse stack's load ends in one more entry: the calls of the
        tick's row walks (a layer, a chunk row or a pass of one-token rows)
        whose choice filled ties by position, which only a query with more
        visible scores at its threshold than room causes
        (nn/sparse_rows.py). Counts it; returns the load without it. (The
        count rides the experts' load: a sparse stack that routes nothing has
        no such vector and is not counted.)"""
        if not self.warmup_mode:
            tie_breaks = int(load[-1])
            self._counter("serve_sparse_tie_breaks_total").inc(tie_breaks)
            emit_span.annotate(tie_breaks=tie_breaks)
        return load[:-1]

    def _record_moe_load(self, load, emit_span, bounded: bool) -> None:
        """One tick's (E,) assignments of real positions, summed over the
        layers: the counter, and the tick's shape on its emit span."""
        if self.warmup_mode:
            return
        if bounded:
            # the expert matmuls' rows are a bound (nn/moe.py serve_bound):
            # the last entry counts the passes the routed layers ran beyond
            # their first, 0 unless a tick's held assignments exceeded it
            load, extra = load[:-1], int(load[-1])
            self._counter("serve_moe_extra_passes_total").inc(extra)
            emit_span.annotate(moe_extra_passes=extra)
        if self.moe_partial:
            # a share of the experts: the last entry counts the assignments
            # that fell on absent ones; the load is over those held
            load, absent = load[:-1], int(load[-1])
            self._counter("serve_moe_absent_assignments_total").inc(absent)
            emit_span.annotate(absent_assign=absent)
        self._counter("serve_moe_assignments_total").inc(int(load.sum()))
        emit_span.annotate(
            load_max=int(load.max()), load_mean=float(load.mean()),
            experts_idle=int((load == 0).sum()),
        )

    def _record_exit(self, exit_p, positions: int, emit_span) -> None:
        """One tick's exit distribution over the loop's steps, summed by
        the program over the tick's ``positions`` sampled positions: their
        mean, and the steps a token would run if it left at its draw."""
        if self.warmup_mode or not positions:
            return
        mean = [float(p) / positions for p in exit_p]
        emit_span.annotate(
            exit_p=[round(p, 6) for p in mean],
            exit_expected_steps=round(
                sum((u + 1) * p for u, p in enumerate(mean)), 6),
        )

    def _emit_token(self, seq: Sequence, tok: int, now: float) -> None:
        seq.generated.append(tok)
        if self.journal is not None and not self.warmup_mode:
            # batched into one journal line per (request, tick) at the
            # end of tick() — crash-replay regenerates anything a
            # mid-tick kill loses before the flush
            self._journal_pending.setdefault(
                seq.request.req_id, []
            ).append(tok)
        if seq.first_token_s is None:
            seq.first_token_s = now
            if not self.warmup_mode:
                arrival = seq.request.arrival_s
                self._histogram("serve_ttft_seconds").observe(now - arrival)
                # one row a request, no span: arrival to first token,
                # and how much of it was the wait for a slot. The stamps
                # are on time.monotonic(), the recorder's clock.
                obs.record_span(
                    "serve.first_token", arrival, now - arrival,
                    queue_s=seq.admitted_s - arrival,
                    prompt_tokens=len(seq.request.prompt),
                    req=seq.request.req_id, **self._replica_fields)
        elif seq.token_stamps:
            self._tick_itl.append(now - seq.token_stamps[-1])
        seq.token_stamps.append(now)
        self._tick_tokens += 1

    def _finish(self, seq: Sequence, now: float) -> None:
        self.scheduler.finish(seq)  # row reset rides the freed-slot drain
        self._retire(seq, now, "completed")

    def _retire(self, seq: Sequence, now: float, status: str) -> None:
        """Shared terminal bookkeeping for every way a request ends:
        journal + telemetry + the ``serve-request`` event whose
        ``status`` field ('completed' | 'timeout') the analyzer and the
        shed/timeout gates read."""
        seq.finish_status = status
        seq.finished_s = now
        self.finished.append(seq)
        req = seq.request
        if req.deadline_ms is not None or req.ttft_deadline_ms is not None:
            with self._deadline_lock:
                self._deadline_live -= 1
        if self.warmup_mode:
            return
        if self.journal is not None:
            pending = self._journal_pending.pop(seq.request.req_id, None)
            # final tokens + terminal status ride ONE append (tokens
            # strictly before status within it)
            self.journal.record_finish(
                seq.request.req_id, status, tokens=pending
            )
        if status == "completed":
            self._counter("serve_requests_completed_total").inc()
        else:
            self.timeout_count += 1
            self._counter("serve_requests_timeout_total").inc()
        itl = [
            b - a for a, b in zip(seq.token_stamps, seq.token_stamps[1:])
        ]
        fields = dict(
            req=seq.request.req_id,
            status=status,
            prompt_tokens=len(seq.request.prompt),
            output_tokens=len(seq.generated),
            e2e_s=round(now - seq.request.arrival_s, 6),
            itl_mean_s=round(sum(itl) / len(itl), 6) if itl else 0.0,
            preemptions=seq.preemptions,
            **self._replica_fields,
        )
        if seq.request.trace_id is not None:
            # the trace's terminal record: obs/trace.py reads e2e_s and
            # status from here and anchors the timeline's end on ts
            fields["trace"] = seq.request.trace_id
        if seq.admitted_s is not None:
            # arrival to the first slot, stamped by the scheduler: a
            # request shed by its deadline while waiting never had one
            fields["queue_wait_s"] = round(
                seq.admitted_s - seq.request.arrival_s, 6
            )
        if seq.first_token_s is not None:
            # a TTFT-deadline timeout never produced a first token — the
            # analyzer's percentiles must not see a fabricated sample
            fields["ttft_s"] = round(
                seq.first_token_s - seq.request.arrival_s, 6
            )
        logger.log_event("serve-request", _level="debug", **fields)

    def _expire_deadlines(self, now: float) -> None:
        """Tick-boundary deadline sweep: cancel every live request past
        its total deadline, or past its TTFT deadline with no first
        token yet. The scheduler releases slot + blocks (one reference
        each — trie-shared prefix blocks stay cached for the next
        requester), so the capacity is admissible THIS tick."""
        for seq in self._expired(now):
            self.scheduler.cancel(seq)
            self._retire(seq, now, "timeout")

    def _expired(self, now: float) -> List[Sequence]:
        """The live requests past a deadline at ``now``."""
        if not self._deadline_live:
            return []
        live = list(self.scheduler.running.values()) + list(
            self.scheduler.waiting
        )
        out = []
        for seq in live:
            req = seq.request
            waited_ms = (now - req.arrival_s) * 1000.0
            if (
                req.deadline_ms is not None and waited_ms > req.deadline_ms
            ) or (
                req.ttft_deadline_ms is not None
                and seq.first_token_s is None
                and waited_ms > req.ttft_deadline_ms
            ):
                out.append(seq)
        return out

    def tick(self) -> Tick:
        """One engine step, ONE TICK AHEAD of the tokens the host has read:
        schedule tick ``step`` from the projected state and issue its
        program (``serve.schedule``: expire deadlines, schedule;
        ``serve.mixed``: build, dispatch), and only
        THEN read the tick issued by the call before (``_read``: its
        ``serve.mixed.wait``, ``serve.emit`` and ``serve.retire`` carry ITS
        ``step``, one less), so the chip runs this program while the host
        emits that one's tokens. Each phase is a span at the place of the
        work (docs/OBSERVABILITY.md "Span taxonomy"), all under the
        ``serve.tick`` this opens itself. When nothing is left to schedule
        the call only reads what is in flight.

        Where the scheduler needs the tokens' VALUES the read comes first
        and the tick is synchronous (``_read_first``: a deadline that has
        run out, a pool that might preempt); the same
        code, the read moved ahead of the schedule. ``seq.generated`` and
        ``seq.token_stamps`` hold only tokens the host has read, whenever
        this returns."""
        get_fault_plan().fire("serve.tick")
        step = self.tick_index
        self._tick_thread = threading.get_ident()
        with self._span("serve.tick", step=step,
                        **self._replica_fields) as tick_span:
            before = self._issued
            reason = self._read_first(time.monotonic())
            if before is not None and reason is not None:
                self._read(before)
                before = None
            with self._span("serve.schedule", step=step) as sched_span:
                t = self._schedule_tick(step, sched_span)
            if t.preempted and before is not None:
                raise RuntimeError(
                    "the scheduler preempted with a tick in flight: "
                    "may_preempt() missed it")
            self._issued = (self._issue(t, step)
                            if t.prefills or t.decodes else None)
            if reason is None and (before is None or self._issued is None):
                reason = "drained" if self._issued is None else "first"
            if before is not None:
                self._read(before)
                if not self.scheduler.has_work:
                    # every row of the tick just issued was issued behind an
                    # EOS: nobody would come back for it
                    self.settle()
            if not (t.prefills or t.decodes):
                # no program of this step to settle: its retire says so (a
                # reader keys what a tick retired by the tick's step)
                with self._span("serve.retire", step=step) as retire_span:
                    if retire_span is not None:  # not warming up
                        retire_span.annotate(finished=0)
            if tick_span is not None:  # not warming up
                tick_span.annotate(decodes=len(t.decodes),
                                   chunks=len(t.prefills),
                                   overlapped=reason is None)
                if reason is None:
                    self.ticks_overlapped += 1
                    self._counter("serve_ticks_overlapped_total").inc()
                else:
                    self.ticks_synchronous[reason] = (
                        self.ticks_synchronous.get(reason, 0) + 1)
                    self._counter("serve_ticks_synchronous_total",
                                  reason=reason).inc()
            self.tick_index += 1
            if self.tick_index % self.config.flush_interval == 0:
                self._reg.flush_step(self.tick_index)
        return t

    def settle(self) -> None:
        """Read the tick in flight, if any, now: for a caller that needs
        every token the engine has asked for on the host before the next
        ``tick()`` (``obs`` calls it at a capture's edges). Only on the
        thread that ticks: from another (an in-process fleet's main thread)
        it leaves the tick to its own."""
        issued = self._issued
        if issued is not None and self._tick_thread == threading.get_ident():
            self._issued = None
            self._read(issued)

    def _read_first(self, now: float) -> Optional[str]:
        """Why the tick in flight must be read BEFORE the next is scheduled
        (None: it need not be), from what the engine can observe:
        ``"deadline"``, a running request has run out of time and its
        cancellation must see its first token, if that is what is in
        flight; ``"preempt"``, the pool might preempt
        (``scheduler.may_preempt``: a bound) and a victim's
        ``resume_prompt`` must hold every token it was given. A tick read
        early for nothing costs one gap, never a token."""
        if self._issued is None:
            return None
        if any(seq.slot is not None for seq in self._expired(now)):
            return "deadline"
        if self.scheduler.may_preempt():
            return "preempt"
        return None

    def _schedule_tick(self, step: int, sched_span=None) -> Tick:
        """Everything a tick decides before its programs run. A tick
        whose allocations ran the prefix cache's LRU eviction says so on
        ``sched_span`` (``serve.schedule``): ``evict_ms`` inside
        ``PrefixCache.evict`` and the blocks ``evicted`` (the stale
        entries its LRU heap skipped on the way are a lifetime total,
        ``stats_snapshot()["evict_stale"]``)."""
        self._expire_deadlines(time.monotonic())
        t = self.scheduler.schedule()
        if not self.warmup_mode:
            for seq in t.first_admitted:
                self._histogram("serve_queue_wait_seconds").observe(
                    seq.admitted_s - seq.request.arrival_s
                )
        if t.preempted:
            self._counter("serve_preemptions_total").inc(len(t.preempted))
            # a zero-width marker span: records WHICH traced requests
            # got pushed back to waiting this tick, so a trace's timeline
            # shows the preemption that explains its decode gap
            with self._span("serve.preempt", step=step,
                            count=len(t.preempted),
                            **self._trace_fields(t.preempted)):
                pass
        sched = self.scheduler
        evict_s, evicted = self._evict_flushed
        if sched.evict_seconds != evict_s:
            now = (sched.evict_seconds, sched.evicted_blocks)
            self._evict_flushed = now
            if sched_span is not None:  # not warming up
                sched_span.annotate(
                    evict_ms=round(1e3 * (now[0] - evict_s), 6),
                    evicted=now[1] - evicted)
        if sched.prefix_hit_tokens > self._prefix_hits_flushed:
            self._counter("serve_prefix_hit_tokens_total").inc(
                sched.prefix_hit_tokens - self._prefix_hits_flushed
            )
            self._prefix_hits_flushed = sched.prefix_hit_tokens
        self._reset_rows(self.scheduler.drain_freed_slots())
        if t.cow_pairs:
            # forks are ordered by this tick's (re-)admissions — the
            # prefill rows — so their traces are the ones the copy work
            # advanced (Tick flattens the per-seq pairs; the row list is
            # the per-request attribution that survives)
            with self._span("serve.cow", step=step,
                            pairs=len(t.cow_pairs),
                            **self._trace_fields(t.prefills)):
                self._apply_cow(t.cow_pairs)
        else:
            self._apply_cow(t.cow_pairs)
        return t

    def _retire_tick(self, t: Tick) -> int:
        """Everything a tick settles after its tokens are out; returns
        how many requests it retired."""
        now = time.monotonic()
        finished = 0
        for seq in list(t.prefills) + list(t.decodes):
            # by length, the last token was this tick's; at an EOS the row
            # already issued behind it is dropped (_emit_row)
            if seq.done and not seq.in_flight and seq.slot is not None:
                self._finish(seq, now)
                finished += 1
        self._reset_rows(self.scheduler.drain_freed_slots())
        if self.journal is not None and self._journal_pending:
            # ONE append for every row's tick tokens (completions
            # already flushed theirs inside _retire, tokens before
            # status): per-row appends convoyed the fleet's tick
            # threads on the GIL
            self.journal.record_tokens_batch(self._journal_pending)
            self._journal_pending.clear()
        for name, value in self.scheduler.gauges().items():
            self._gauge(name).set(value)
        return finished

    @property
    def prefill_program_count(self) -> int:
        """Mixed programs BUILT (``prefill_compiles``): the jitted closures
        this engine holds, one per token width of ``config.mixed_widths``
        from the first tick on, so at most 2 for an engine's whole life. A
        closure that is traced and lowered again does not move it:
        ``programs_lowered_since_ready`` counts that."""
        return len(self._mixed_fns)

    @property
    def programs_lowered_since_ready(self) -> Optional[int]:
        """Programs JAX has lowered in this PROCESS since this engine's
        ``serve.lower`` closed (``jax_programs_lowered_total`` now minus its
        value then): 0 in a healthy engine, whose every tick finds its
        program; anything else is a recompile, an eager operation on the
        tick's path or a jitted function met at a new shape, and the
        ``compile.*`` rows name it. None before the first tick. The counter
        is the process's: a second engine built in the same process shows
        its set-up in the first's number, and a process that never passed
        ``enable_compile_cache()`` counts nothing."""
        if self._lowered_at_ready is None:
            return None
        return compile_events.programs_lowered() - self._lowered_at_ready

    def stats_snapshot(self) -> dict:
        """One JSON-safe dict of the engine's load + lifetime tallies —
        the ``stats`` RPC reply a subprocess replica answers with
        (``serve.replica_proc``), which doubles as its heartbeat: every
        field the router's least-loaded sort, the supervisor's liveness
        pass, and the proc-fleet serve-summary read. Reads are plain
        attribute/len reads (GIL-atomic against a concurrent tick), so
        this is safe to call from an RPC handler thread without the
        tick lock."""
        sched = self.scheduler
        finished = list(self.finished)
        return {
            "replica": self.replica_id,
            "queue_depth": len(sched.waiting) + len(sched.running),
            "waiting": len(sched.waiting),
            "running": len(sched.running),
            "pool_pressure": sched.pool_pressure(),
            "has_work": sched.has_work,
            "draining": self.draining,
            "next_req_id": self._next_req_id,
            "tick": self.tick_index,
            "shed_count": self.shed_count,
            "timeout_count": self.timeout_count,
            "finished": len(finished),
            "completed": sum(
                1 for s in finished if s.finish_status == "completed"
            ),
            "output_tokens": sum(len(s.generated) for s in finished),
            "preemptions": sched.preemption_count,
            "prefix_hit_tokens": sched.prefix_hit_tokens,
            # stale entries the prefix cache's LRU heap has had to skip
            # while evicting (scheduler.PrefixCache.evict)
            "evict_stale": (sched.prefix_cache.stale_skipped
                            if sched.prefix_cache is not None else 0),
            "prefilled_tokens": self.prefilled_tokens,
            # closures built, at most one a token width; and the programs
            # lowered since they all were: the recompile alarm
            "prefill_compiles": self.prefill_program_count,
            "programs_lowered_since_ready": self.programs_lowered_since_ready,
            "max_concurrent_prefills": self.max_concurrent_prefills,
            # by token width (JSON keys are strings): ticks run at it (the
            # real tokens they held: serve.mixed's `tokens` beside `width`)
            "mixed_ticks": {str(w): c for w, c in self.mixed_ticks.items()},
            # tick() calls that issued a program ahead of the read of the
            # one before it, and the others by reason (SYNC_REASONS)
            "ticks_overlapped": self.ticks_overlapped,
            "ticks_synchronous": dict(self.ticks_synchronous),
            # host arrays a counted tick handed the device, running mean:
            # 1.0, the one packed operand (None before the first)
            "tick_operands": (
                self.tick_operands / sum(self.mixed_ticks.values())
                if self.mixed_ticks else None
            ),
            # share of the counted ticks in which a row sampled: the rest
            # ran the sampler's argmax branch and no sort
            "sampled_tick_share": (
                self.sampled_ticks / sum(self.mixed_ticks.values())
                if self.mixed_ticks else None
            ),
            # cache lines a token's K and V are written to (a looped model:
            # steps x layers) and the bytes the pools really hold
            "kv_lines": self.pools.kv_lines,
            "kv_pool_bytes": self.pools.device_bytes(),
            # bytes a cached token takes in the pools, all layers (a latent
            # layer: its KV latent and its rotary key's lane row)
            "kv_line_bytes": self.pools.line_bytes,
            "latent_layers": self.latent_layers,
            "sparse_layers": self.sparse_layers,
            "hc_streams": self.hc_streams,
            "hc_sublayers": self.hc_sublayers,
            "window_layers": self.window_layers,
            "window_latent_layers": self.window_latent_layers,
            # layers that keep a line a slot (Mamba-2 mixers' recurrent state,
            # short convolutions' tails; 0: a model without them) and the
            # bytes of those lines
            "state_lines": self.pools.state_lines,
            # the same by kind ({"ssm": 23}, {"delta": 6}, ..)
            "line_layers": dict(self.line_layers),
            "state_pool_bytes": self.pools.state_bytes(),
            # median ms of each serve.* span over this engine's last
            # TICK_PHASES_TICKS ticks, read from the span recorder on
            # the call ({} before the first counted tick)
            "tick_phases_ms": self.tick_phases_ms(),
        }

    def tick_phases_ms(self) -> Dict[str, float]:
        """Where a tick's time goes, by phase: the median duration of
        each ``serve.*`` span that lies inside one of this engine's last
        ``TICK_PHASES_TICKS`` ``serve.tick`` spans (inside it in time, and
        of its ``step`` or, the read of the tick before, of the one before:
        an in-process fleet shares one recorder and its replicas' steps
        collide). Three more that no span holds: ``"unspanned"``, a tick
        minus the leaf phases inside it (``LEAF_PHASES``; what nests in one
        of those is its parent's), ``"between"``, one tick's end to the
        next's start where the engine had work (both ran a program, so the
        earlier one left it in flight): the driver's loop around
        ``tick()``, and ``"overlapped_pct"``, the share of those ticks that
        issued their program ahead of the read of the one before
        (``serve.tick``'s ``overlapped``). Nothing is kept per tick for
        this."""
        rows = obs.recorded_tail("serve.tick", TICK_PHASES_TICKS)
        mine = [r for r in rows if r.name == "serve.tick"
                and r.start_ns >= self._created_ns
                and r.fields.get("replica") == self.replica_id]
        ticks = {r.step: (r.start_ns, r.start_ns + r.duration_ns)
                 for r in mine}
        by_name: Dict[str, List[int]] = {}
        leaves: Dict[int, int] = dict.fromkeys(ticks, 0)
        for r in rows:
            if not r.name.startswith("serve.") or r.step is None:
                continue
            for step in (r.step, r.step + 1):
                start, end = ticks.get(step, (1, 0))
                if start <= r.start_ns and r.start_ns + r.duration_ns <= end:
                    by_name.setdefault(r.name, []).append(r.duration_ns)
                    if r.name in LEAF_PHASES:
                        leaves[step] += r.duration_ns
                    break
        if not mine:
            return {}
        by_name["unspanned"] = [r.duration_ns - leaves[r.step] for r in mine]
        between = [
            b.start_ns - a.start_ns - a.duration_ns
            for a, b in zip(mine, mine[1:])
            if b.step == a.step + 1 and b.fields["decodes"] + b.fields["chunks"]
            and a.fields["decodes"] + a.fields["chunks"]]
        if between:
            by_name["between"] = between
        out = {name: round(statistics.median(d) / 1e6, 6)
               for name, d in sorted(by_name.items())}
        out["overlapped_pct"] = round(
            100.0 * sum(r.fields["overlapped"] for r in mine) / len(mine), 6)
        return out

    def run_until_done(self, max_ticks: int = 100_000) -> List[Sequence]:
        """Drain every submitted request; returns finished sequences in
        completion order."""
        ticks = 0
        while self.scheduler.has_work:
            self.tick()
            ticks += 1
            if ticks > max_ticks:
                raise RuntimeError(
                    f"engine made no progress draining the queue within "
                    f"{max_ticks} ticks — scheduler livelock?"
                )
        self._reg.flush_step(self.tick_index)
        return self.finished


def install_drain_handler(engine: ServeEngine) -> None:
    """SIGTERM -> graceful drain, chaining any previously installed
    handler exactly like the trainer's ``install_preemption_handler``
    (launchers and cluster agents keep theirs): the engine flips to
    draining — no new admissions, in-flight requests finish or hit
    their deadlines — and the bench loop exits 0 with a complete,
    parseable run dir. The serving mirror of the trainer's
    coordinated-preemption contract (docs/RESILIENCE.md)."""
    import signal

    prev = signal.getsignal(signal.SIGTERM)

    def handler(signum, frame):
        engine.begin_drain()
        if callable(prev):  # SIG_DFL/SIG_IGN are enum ints, skipped
            prev(signum, frame)

    signal.signal(signal.SIGTERM, handler)
