"""Singleton logger with rank-scoped sinks.

Parity with the reference's logging stack (reference:
src/scaling/core/logging/logging.py:46-209): colored console, per-rank file
logs, rank-gated TensorBoard/wandb metric sinks. TensorBoard and wandb are
optional imports — absent packages degrade to no-ops.
"""

from __future__ import annotations

import logging as _pylogging
import sys
from pathlib import Path
from typing import Any, List, Optional

from pydantic import Field, model_validator

from ..config import BaseConfig

_LEVELS = {
    "debug": _pylogging.DEBUG,
    "info": _pylogging.INFO,
    "warning": _pylogging.WARNING,
    "error": _pylogging.ERROR,
    "critical": _pylogging.CRITICAL,
}

# ---------------------------------------------------------------- tracing
# obs.spans registers a provider at import time so every log_event record
# emitted under an active trace context carries the trace id. The hook
# lives HERE (a module-level callback, not an import) because logging
# sits below obs in the layering — obs depends on logging and never the
# reverse — yet the ISSUE-20 stamping contract belongs to log_event
# itself: serve-request events, capacity-lease events and supervisor
# transitions all gain trace identity without each call site opting in.
_trace_provider = None


def set_trace_provider(provider) -> None:
    """Register a zero-arg callable returning extra fields (or ``None``)
    to merge into every ``log_event`` record. Explicit fields win; a
    raising/absent provider costs nothing (telemetry is best-effort)."""
    global _trace_provider
    _trace_provider = provider


class LoggerConfig(BaseConfig):
    log_level: str = Field("info", description="")
    log_dir: Optional[str] = Field(None, description="directory for per-rank log files")
    events_path: Optional[str] = Field(
        None,
        description="jsonl file for structured lifecycle events "
        "(supervisor transitions, stall reports, preemption broadcasts) "
        "— machine-parseable post-mortems instead of stderr scraping. "
        "The SCALING_TPU_EVENTS_PATH env var overrides/provides this for "
        "subprocesses",
    )
    metrics_path: Optional[str] = Field(
        None,
        description="jsonl file for per-step metric records (the run-dir "
        "analyzer's input, see docs/OBSERVABILITY.md). Defaults to "
        "<log_dir>/metrics_rank_<rank>.jsonl whenever log_dir is set, so "
        "telemetry is on by default for any run that logs at all; the "
        "SCALING_TPU_METRICS_PATH env var overrides both",
    )
    metrics_jsonl: bool = Field(
        True,
        description="explicit off switch for the metrics jsonl sink "
        "(false disables it even when log_dir/metrics_path is set; the "
        "env var still wins)",
    )
    metrics_ranks: Optional[List[int]] = Field(
        None, description="global ranks that record metrics; None -> rank 0 only"
    )
    use_wandb: bool = Field(False, description="")
    use_tensorboard: bool = Field(False, description="")
    tensorboard_ranks: Optional[List[int]] = Field(
        None,
        description="global ranks that write to tensorboard. None -> rank 0 only.",
    )
    determined_metrics_ranks: Optional[List[int]] = Field(
        None,
        description="kept for config parity (reference logger_config.py:55); "
        "there is no Determined master here to report to",
    )
    wandb_ranks: Optional[List[int]] = Field(
        None, description="global ranks that log to wandb. None -> rank 0 only."
    )
    wandb_host: Optional[str] = Field(None, description="")
    wandb_team: Optional[str] = Field(None, description="")
    wandb_project: str = Field("scaling_tpu", description="")
    wandb_group: str = Field("default", description="")
    wandb_api_key: Optional[str] = Field(None, description="")

    @model_validator(mode="after")
    def _check_wandb_key(self):
        """(reference: logger_config.py wandb/api-key validation)"""
        import os

        if self.use_wandb and not (self.wandb_api_key or os.environ.get("WANDB_API_KEY")):
            raise ValueError(
                "If 'use_wandb' is set to True a wandb api key needs to be "
                "provided (wandb_api_key or the WANDB_API_KEY env variable)."
            )
        return self


def _rank_enabled(ranks: Optional[List[int]], rank: int) -> bool:
    if ranks is None:
        return rank == 0
    return rank in ranks


class _Logger:
    """Process-wide logger; ``configure`` wires sinks, default = console."""

    def __init__(self) -> None:
        self._log = _pylogging.getLogger("scaling_tpu")
        self._log.propagate = False
        self._configured = False
        self._rank = 0
        self._config: Optional[LoggerConfig] = None
        self._tb_writer: Any = None
        self._wandb: Any = None
        self._warned_nonnumeric: set = set()
        self._ensure_console()

    def _ensure_console(self) -> None:
        if not self._log.handlers:
            handler = _pylogging.StreamHandler(sys.stdout)
            handler.setFormatter(
                _pylogging.Formatter("[%(asctime)s] [%(levelname)s] %(message)s")
            )
            self._log.addHandler(handler)
            self._log.setLevel(_pylogging.INFO)

    def configure(
        self,
        config: Optional[LoggerConfig] = None,
        name: str = "",
        global_rank: int = 0,
    ) -> None:
        config = config or LoggerConfig()
        self._config = config
        self._rank = global_rank
        self._log.setLevel(_LEVELS.get(config.log_level, _pylogging.INFO))
        prefix = f"[rank {global_rank}]" + (f" [{name}]" if name else "")
        for h in list(self._log.handlers):
            self._log.removeHandler(h)
        console = _pylogging.StreamHandler(sys.stdout)
        console.setFormatter(
            _pylogging.Formatter(f"[%(asctime)s] {prefix} [%(levelname)s] %(message)s")
        )
        self._log.addHandler(console)
        if config.log_dir:
            log_dir = Path(config.log_dir)
            log_dir.mkdir(parents=True, exist_ok=True)
            fh = _pylogging.FileHandler(log_dir / f"rank_{global_rank}.log")
            fh.setFormatter(
                _pylogging.Formatter(f"[%(asctime)s] {prefix} [%(levelname)s] %(message)s")
            )
            self._log.addHandler(fh)
        if config.use_tensorboard and _rank_enabled(config.tensorboard_ranks, global_rank):
            try:
                from torch.utils.tensorboard import SummaryWriter

                tb_dir = Path(config.log_dir or ".") / "tensorboard"
                self._tb_writer = SummaryWriter(log_dir=str(tb_dir))
            except Exception:  # pragma: no cover - optional dep
                self.warning("tensorboard requested but unavailable; disabled")
        if config.use_wandb and _rank_enabled(config.wandb_ranks, global_rank):
            try:  # pragma: no cover - optional dep
                import os as _os

                if config.wandb_host:
                    _os.environ["WANDB_BASE_URL"] = config.wandb_host
                if config.wandb_api_key:
                    _os.environ["WANDB_API_KEY"] = config.wandb_api_key
                import wandb

                wandb.init(
                    project=config.wandb_project,
                    group=config.wandb_group,
                    entity=config.wandb_team,
                    name=name or None,
                )
                self._wandb = wandb
            except Exception as e:  # pragma: no cover
                self.warning(f"wandb requested but unavailable; disabled ({e})")
        self._configured = True

    # ------------------------------------------------------------ passthru
    def debug(self, msg: Any) -> None:
        self._log.debug(msg)

    def info(self, msg: Any) -> None:
        self._log.info(msg)

    def warning(self, msg: Any) -> None:
        self._log.warning(msg)

    def error(self, msg: Any) -> None:
        self._log.error(msg)

    def critical(self, msg: Any) -> None:
        self._log.critical(msg)

    # ------------------------------------------------------------- metrics
    def metrics_path(self) -> Optional[str]:
        """Resolved per-step metrics JSONL path, or None when the sink is
        off. ``metrics_ranks`` gates this resolution exactly like it
        gates ``log_metrics`` — the registry's ``flush_step`` rides the
        same decision, so a rank configured not to record metrics never
        writes snapshots either. For an enabled rank: env override first
        (a launcher redirecting a subprocess must win, same contract as
        the events path), then the explicit config path, then the
        log-dir default."""
        import os

        if self._config is not None and not _rank_enabled(
            self._config.metrics_ranks, self._rank
        ):
            return None
        env = os.environ.get("SCALING_TPU_METRICS_PATH")
        if env:
            return env
        c = self._config
        if c is None or not c.metrics_jsonl:
            return None
        if c.metrics_path:
            return c.metrics_path
        if c.log_dir:
            return str(Path(c.log_dir) / f"metrics_rank_{self._rank}.jsonl")
        return None

    def _warn_dropped_metrics(self, keys: List[str]) -> None:
        """One-time (per key) warning for non-numeric metric values the
        structured sinks (jsonl/tensorboard) cannot record — silent drops
        hide typos like logging a whole array object under 'loss'."""
        fresh = [k for k in keys if k not in self._warned_nonnumeric]
        if not fresh:
            return
        self._warned_nonnumeric.update(fresh)
        self.warning(
            "non-numeric metric value(s) dropped from structured sinks "
            f"(console still shows them): {sorted(fresh)} — logged once "
            "per key"
        )

    def log_metrics(self, metrics: dict, step: int) -> None:
        if self._config is not None and not _rank_enabled(
            self._config.metrics_ranks, self._rank
        ):
            return
        rendered = " | ".join(
            f"{k}: {float(v):.6g}" if _is_number(v) else f"{k}: {v}"
            for k, v in metrics.items()
        )
        self.info(f"step {step} | {rendered}")
        numeric = {k: float(v) for k, v in metrics.items()
                   if _is_number(v) and v is not None}
        dropped = [k for k in metrics if k not in numeric]
        if dropped:
            self._warn_dropped_metrics(dropped)
        path = self.metrics_path()
        if path:
            import json as _json
            import math as _math
            import time as _time

            rec = {
                "kind": "step", "step": step, "ts": _time.time(),
                "host": _host_id(self._rank),
                # NaN/Inf serialize as invalid-JSON bare tokens, which
                # would corrupt the file exactly during the non-finite
                # incidents this telemetry exists to diagnose; null keeps
                # the line parseable everywhere (jq, Go/JS parsers) and
                # the analyzer skips nulls
                "metrics": {
                    k: (v if _math.isfinite(v) else None)
                    for k, v in numeric.items()
                },
            }
            # single-syscall append (multi-writer-safe), no fsync: metric
            # lines are per-step and advisory, unlike lifecycle events
            try:
                Path(path).parent.mkdir(parents=True, exist_ok=True)
                append_jsonl_line(path, _json.dumps(rec, sort_keys=True))
            except OSError as e:
                self.warning(f"could not append metrics to {path}: {e!r}")
        if self._tb_writer is not None:
            for k, v in numeric.items():
                self._tb_writer.add_scalar(k, v, step)
        if self._wandb is not None:  # pragma: no cover
            self._wandb.log(metrics, step=step)

    def log_config(self, config: BaseConfig) -> None:
        self.info(f"config:\n{config.as_str()}")

    # -------------------------------------------------------------- events
    def events_path(self) -> Optional[str]:
        """Where structured events are appended, or None. Env first: the
        field doc promises the env var OVERRIDES the config value (a
        launcher redirecting a subprocess whose config already declares
        a path must win)."""
        import os as _os

        return _os.environ.get("SCALING_TPU_EVENTS_PATH") or (
            self._config.events_path if self._config is not None else None
        )

    def takes_events(self, level: str) -> bool:
        """Whether a ``log_event`` record at ``level`` reaches anything:
        the events file, or the log's mirror line. Per-tick span records
        ask before they build and serialise one."""
        return bool(self.events_path()) or self._log.isEnabledFor(
            _LEVELS.get(level, _pylogging.INFO))

    def log_event(self, event: str, _level: str = "info",
                  _fsync: bool = True, **fields: Any) -> None:
        """Structured lifecycle event: one JSON line, append-only.

        Post-mortems of supervised multi-host runs (who died, when the
        relaunch happened, which host broadcast preemption) must not
        depend on scraping human-formatted stderr — each event lands as
        a single flushed JSON object in the events file
        (the ``SCALING_TPU_EVENTS_PATH`` env var, else
        ``LoggerConfig.events_path``) and is mirrored to the normal log.
        Without a configured path only the mirror line is emitted.
        ``_level`` tunes only the mirror: high-frequency span events
        mirror at debug so steady-state training stays readable, while
        the events file receives every record either way. ``_fsync``
        defaults on for lifecycle events (a crashed supervisor must not
        lose its last transition); per-step span records pass False —
        an fsync per span on the step path is exactly the overhead the
        metrics sink already declines."""
        import json as _json
        import os as _os
        import time as _time

        rec = {"event": event, "ts": _time.time(), **fields}
        if _trace_provider is not None:
            try:
                extra = _trace_provider()
            except Exception:
                extra = None
            if extra:
                for k, v in extra.items():
                    rec.setdefault(k, v)
        line = _json.dumps(rec, sort_keys=True, default=str)
        getattr(self, _level, self.info)(f"EVENT {line}")
        path = self.events_path()
        if path:
            try:
                with open(path, "a") as f:
                    f.write(line + "\n")
                    f.flush()
                    if _fsync:
                        _os.fsync(f.fileno())
            except OSError as e:
                self.warning(f"could not append event to {path}: {e!r}")


def append_jsonl_line(path: Any, line: str) -> None:
    """Append one line in a SINGLE ``write(2)`` on an O_APPEND fd.

    Multiple host processes may share one metrics file (the supervised
    pod wires every worker's ``SCALING_TPU_METRICS_PATH`` at the same
    place); Python's buffered file object splits writes above its 8 KiB
    buffer into several syscalls, and a registry snapshot with many
    labelled histograms can cross that — two hosts' partial writes would
    interleave into torn lines. One syscall keeps the append atomic.
    Lives here (stdlib-only, below both packages) so ``obs`` depends on
    ``logging`` and never the reverse."""
    import os

    fd = os.open(str(path), os.O_WRONLY | os.O_APPEND | os.O_CREAT, 0o644)
    try:
        os.write(fd, (line + "\n").encode())
    finally:
        os.close(fd)


def _is_number(v: Any) -> bool:
    try:
        float(v)
        return True
    except (TypeError, ValueError):
        return False


def _host_id(rank: int) -> int:
    """Pod host id for metric records: the supervisor's env var when
    present (fake pods and real ones both set it), else the rank."""
    import os

    try:
        return int(os.environ.get("SCALING_TPU_HOST_ID", rank))
    except ValueError:
        return rank


logger = _Logger()
