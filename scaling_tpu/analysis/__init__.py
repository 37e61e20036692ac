"""Static-analysis subsystem: JAX-aware AST lint + lowered-HLO audit.

Two complementary compile-time gates over the training/decode hot path
(ISSUE 2; the Megatron-LM / Mesh-TensorFlow practice of inspecting the
lowered program to keep collective and layout invariants honest):

- ``lint``: visitor-based AST pass over ``scaling_tpu/`` source with
  JAX-specific rules (tracer branches, host syncs, PRNG key reuse, ...).
  Rule IDs are stable (``STA001``..); suppress per line with
  ``# sta: disable=STA003``.
- ``hlo_audit``: AOT-lowers the jitted train step and the fused decode
  step on the virtual CPU mesh and walks the StableHLO / optimized-HLO
  text into a structured report (collective inventory per mesh axis,
  bf16->f32 upcasts feeding dots, host callbacks, rng ops, recompile-key
  signature), pinned against committed goldens.

CLI: ``python -m scaling_tpu.analysis [lint|audit|all] --json out.json``.

This module must stay import-light (no jax): the CLI sets up the virtual
device environment before anything pulls jax in.
"""

from __future__ import annotations

__all__ = ["main", "lint_paths", "Finding", "RULES"]


def main(argv=None) -> int:
    from .cli import main as _main

    return _main(argv)


def __getattr__(name):
    # lazy re-exports so `import scaling_tpu.analysis` stays jax-free
    if name in ("lint_paths", "Finding", "RULES"):
        from . import lint as _lint

        return getattr(_lint, name)
    raise AttributeError(name)
