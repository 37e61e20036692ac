"""Bytes the routed MLP needs, computed from shapes and from what the
program counted. The benchmark's own count (the yardstick), beside
``ops_count.py``: a later PR that claims a gain cannot change it."""

from __future__ import annotations

EXPERT_MATRICES = 3  # gate, up, down: each hidden x expert_width


def expert_weight_bytes(experts_read: int, hidden: int, expert_width: int,
                        bytes_per_value: int) -> float:
    """Bytes of expert weights a routed layer stack has to read when
    ``experts_read`` (layer, expert) pairs have at least one token to work
    on: each such expert's three matrices once. Router, activations and the
    experts no token chose are not counted: at serving batches the weights
    are what the mechanism is bound by."""
    return float(experts_read) * EXPERT_MATRICES * hidden * expert_width * bytes_per_value
