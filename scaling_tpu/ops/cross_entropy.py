"""Cross-entropy from logits with a memory-lean custom VJP.

Autodiff of ``log_softmax -> gather`` keeps an fp32 ``(b, s, vocab)``
residual (the log-probabilities) alive from forward to backward — at the
bench shape (mbs 8, seq 2048, vocab 32k) that is ~2 GB of HBM doing
nothing but waiting. The closed-form gradient needs none of it:

    d loss / d logits = softmax(logits) - onehot(targets)

so the VJP here saves only the ORIGINAL low-precision logits (which the
lm-head already materialized) plus a ``(b, s)`` fp32 logsumexp, and
recomputes the softmax inside the backward. The cotangent is produced in
the logits' own dtype (bf16 in mixed precision), halving the backward
buffer too. Forward math is identical (logsumexp - target logit == the
gathered log-softmax), in fp32 either way.

Under tensor parallelism the logits arrive sharded over the vocabulary
(``(data, seq, model)``, what ``TransformerLMHead`` leaves them in), and
nothing here gathers it. The target's logit is selected by comparing an
iota over the columns with the target, never by indexing along the
vocabulary (a ``take_along_axis`` there is a gather GSPMD answers by
replicating the whole row): each shard sums the one column it may own and
zeros. What crosses the model axis is one number a position: all-reduces
of ``f32[b, s]`` for the row's sum of exponentials and for the target's
logit, and the row maximum (with its column, where the accuracy's argmax
beside this loss shares the pass; GSPMD writes them). The backward is
elementwise on the shard plus the saved ``(b, s)`` logsumexp, and its
cotangent keeps the logits' layout, because the head's sharding constraint
transposes to itself. One device runs the same code: the sum adds zeros to
the target's logit, so the value is the gathered one bit for bit, and the
gather had its price there too: it read a float32 copy of the logits that
the head's matmul then wrote beside the bf16 one (1.07 GB at Mistral-7B's
2 x 4096 x 32768, PERF.md, PR 54).

(reference analogue: model.py:43-76 computes plain torch cross entropy;
the memory shape of torch autograd is the same residual problem.)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.custom_vjp
def cross_entropy_from_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token cross entropy, fp32 ``targets.shape`` output."""
    loss, _ = _fwd(logits, targets)
    return loss


def _is_target(x, targets):
    """``(..., vocab)`` mask of each position's target column; a target
    outside the vocabulary selects nothing."""
    columns = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return columns == targets.astype(jnp.int32)[..., None]


def _compute(logits, targets):
    x = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(x, axis=-1)
    target_logit = jnp.where(_is_target(x, targets), x, 0.0).sum(axis=-1)
    return lse - target_logit, lse


def _fwd(logits, targets):
    loss, lse = _compute(logits, targets)
    # residuals: the logits AT THEIR ORIGINAL dtype (no fp32 copy kept
    # alive) + the (b, s) logsumexp; the fp32 softmax never outlives the
    # backward computation itself
    return loss, (logits, targets.astype(jnp.int32), lse)


def _bwd(res, g):
    logits, targets, lse = res
    x = logits.astype(jnp.float32)
    p = jnp.exp(x - lse[..., None])
    onehot = _is_target(x, targets).astype(jnp.float32)
    dlogits = (p - onehot) * g.astype(jnp.float32)[..., None]
    # cotangent in the primal dtype: bf16 logits get a bf16 gradient
    # buffer (autodiff of the fp32-upcast path would carry fp32 here and
    # cast at the matmul — same arithmetic, twice the bytes)
    return dlogits.astype(logits.dtype), None


cross_entropy_from_logits.defvjp(_fwd, _bwd)
