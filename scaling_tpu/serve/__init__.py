"""Continuous-batching inference serving (docs/SERVING.md).

The "millions of users" half of the north star: turns the single-request
``TransformerInferenceModule`` generate loop into a serving engine —

- :mod:`.kvcache` — block-paged KV cache: fixed-size blocks allocated
  from one device-resident pool per layer, addressed through per-sequence
  block tables (PagedAttention, SOSP '23); optional int8-quantized values.
- :mod:`.scheduler` — continuous batching (Orca, OSDI '22): admission
  from a request queue, per-tick prefill/decode mixing under a token
  budget, preemption on pool exhaustion, completed-slot recycling.
- :mod:`.engine` — the jitted device program: ONE fused mixed program
  per tick covering the whole slot set — prefill-chunk rows and
  one-token decode rows tagged by
  traced lengths (paged attention streamed through the Pallas kernel
  in ``nn/paged_attention.py``); per-request temperature/top-k/top-p
  sampling as traced per-row arrays (no per-request recompiles;
  signatures pinned in the ``serve_decode`` HLO audit section). The
  scheduler's prefix trie (``PrefixCache``) maps shared-prompt blocks
  straight into new sequences' tables, so a prompt family pays its
  prefill once (docs/SERVING.md "Raw speed").
- :mod:`.bench` / ``python -m scaling_tpu.serve bench`` — Poisson
  load generator reporting tokens/s, TTFT/ITL percentiles and the
  prefix-hit rate through ``obs.get_registry()``, gated
  by ``--assert-serve-throughput`` / ``--assert-ttft`` (mirroring the
  training MFU gates; ``--assert-max-shed-rate`` /
  ``--assert-max-serve-timeouts`` ride the analyzer).
- :mod:`.router` — the FLEET (docs/SERVING.md "The fleet"): N
  data-parallel engine replicas behind ``FleetRouter`` — least-loaded
  + hash-based prefix-affinity dispatch, retry-elsewhere on
  ``Backpressure``, SIGTERM drain fan-out, per-replica journal
  namespaces (``journal_path``) with token-exact replica-kill
  journal-resume; ``serve bench --replicas N [--mp K]`` drives the
  fleet through one Poisson stream (mp>1 shards every KV pool over
  the model axis — ``kvcache.init_pools`` — so big models fit and
  the mixed tick runs SPMD; ``tune --serve`` plans the (mp, replicas,
  block_size, token_budget) split and ``--config`` runs its pick).
- resilience (docs/SERVING.md "Resilience"): per-request TTFT/total
  deadlines cancelled at tick boundaries (terminal status
  ``timeout``), watermark overload shedding with hysteresis
  (``scheduler.Backpressure`` — the fleet router's signal), SIGTERM
  graceful drain (``engine.install_drain_handler``), the
  :mod:`.journal` crash-replay request journal behind
  ``serve bench --resume`` / ``--restarts`` (token-exact replay via
  the (request, position) sampler keys), and ``serve.tick`` /
  ``serve.admit`` / ``serve.journal`` / ``serve.pool`` fault points
  under ``SCALING_TPU_FAULTS``.

jax-free at import time (the engine imports it lazily): the scheduler and
request/bench plumbing must stay importable from the analyzer and tests
without paying backend init.
"""

from .journal import (
    JournalReplay,
    RequestJournal,
    journal_path,
    open_journal,
    replay_journal,
)
from .router import FleetRouter, ReplicaHandle, ReplicaStats
from .scheduler import (
    Backpressure,
    BlockAllocator,
    ContinuousBatchingScheduler,
    PrefixCache,
    Request,
    SchedulerConfig,
    Sequence,
    SequenceState,
)

__all__ = [
    "Backpressure",
    "BlockAllocator",
    "ContinuousBatchingScheduler",
    "FleetRouter",
    "JournalReplay",
    "PrefixCache",
    "ReplicaHandle",
    "ReplicaStats",
    "Request",
    "RequestJournal",
    "SchedulerConfig",
    "Sequence",
    "SequenceState",
    "journal_path",
    "open_journal",
    "replay_journal",
]
