"""Inference: checkpoint -> logits / generate with KV cache.

(reference: src/scaling/transformer/inference/inference_model.py:30-263,
core/nn/parallel_module/inference_module.py). The reference hops layer
slices across GPUs with ``.to_(device)`` and grows a KV cache by
concatenation; under jit both collapse: layers run in one XLA program and
the cache is a fixed-capacity buffer written with ``dynamic_update_slice``
(static shapes — one compiled decode step serves the whole generation).

Cached vs uncached generate (reference: inference_model.py:159-235):
- cached (default): one prefill over the prompt, then ONE jitted
  ``lax.while_loop`` running every decode step on-device — KV caches in
  the carry, tokens/logits written into preallocated buffers, per-row
  stop masks, early exit when all rows are done. The reference (and the
  ``fused_decode=False`` escape hatch here) instead dispatches one jit
  call per token; on TPU each of those dispatches pays host-round-trip
  latency, which dominates decode wall-clock.
- uncached: the whole padded sequence is re-fed each step (parity baseline).
"""

from __future__ import annotations

from pathlib import Path
from typing import Any, Callable, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import yaml

from .config import TransformerConfig
from .layers.layer import MixerLayer, TransformerLayer
from .layers.lm_head import LayerNormWrapper, LoopExitGate, exit_distribution
from .model import init_model
from .tokenizer import Tokenizer
from ...checkpoint import load_model_checkpoint
from ...nn.attention import PagedKVCacheView
from ...nn.base_layer import state_views
from ...nn.moe import ParallelMoEMLP
from ...parallel.parallel_module import ParallelModule


# the layers of a trunk (they consume the serving state)
TRUNK_LAYERS = (TransformerLayer, MixerLayer)


class CompletionOutput(NamedTuple):
    completion_ids: List[int]
    completion: Optional[str]
    logits: Optional[jax.Array] = None


def sample_argmax(logits: jax.Array, key: Optional[jax.Array] = None) -> jax.Array:
    """Greedy sampling (reference: inference/sample.py)."""
    return jnp.argmax(logits, axis=-1)


class _LeftPadLayout(NamedTuple):
    """Position/segment/mask views for a left-padded (ragged) batch; all
    None when the batch is rectangular."""

    pos_all: Optional[jax.Array] = None  # (b, prompt+gen) rotary positions
    seg_all: Optional[jax.Array] = None  # (b, prompt+gen) pad segment = 1
    prompt_pos: Optional[jax.Array] = None  # prompt-prefix slices of the above
    prompt_seg: Optional[jax.Array] = None
    content_len: Optional[jax.Array] = None  # (b,) per-row rotary clock base
    pad_mask: Optional[jax.Array] = None  # (b,1,1,prompt+gen) additive -1e9

    @property
    def ragged(self) -> bool:
        return self.pos_all is not None


def _left_pad_layout(
    pad_start: Optional[jax.Array], prompt_len: int, max_tokens: int,
    use_cache: bool,
) -> _LeftPadLayout:
    """One left-padded layout over the full generation buffer: positions
    restart at each row's first content token and run straight into the
    generated slots; pads keep their own segment. Prefill slices the
    prompt prefix; the uncached path uses the full-buffer views directly;
    the decode paths blank the pad cache slots with the additive mask."""
    if pad_start is None:
        return _LeftPadLayout()
    slots_all = jnp.arange(prompt_len + max_tokens)[None]
    ps = pad_start[:, None]
    pos_all = jnp.clip(slots_all - ps, 0)
    seg_all = jnp.where(slots_all >= ps, 0, 1).astype(jnp.int32)
    return _LeftPadLayout(
        pos_all=pos_all,
        seg_all=seg_all,
        prompt_pos=pos_all[:, :prompt_len],
        prompt_seg=seg_all[:, :prompt_len],
        content_len=prompt_len - pad_start,
        pad_mask=(
            jnp.where(slots_all < ps, -1e9, 0.0)[:, None, None, :]
            if use_cache
            else None
        ),
    )


def make_sampler(
    temperature: float = 1.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """Temperature / top-k / top-p (nucleus) sampling, composable like the
    reference's transform chain (reference: inference/sample.py:17-45).

    The returned closure carries ``_sampler_key`` (its configuration), so
    the jitted decode loops recognise two ``make_sampler(...)`` calls with
    identical settings as the same sampler instead of re-tracing the whole
    while-loop program per ``generate()`` call. Custom sampler callables
    without the attribute fall back to object identity — reuse one object
    across calls to keep the compiled loop warm."""

    def sample(logits: jax.Array, key: jax.Array) -> jax.Array:
        scaled = logits.astype(jnp.float32) / max(temperature, 1e-6)
        if top_k is not None:
            kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        if top_p is not None:
            # keep the smallest prefix of descending-prob tokens whose
            # cumulative mass reaches top_p (always keeping the best token)
            sorted_logits = jnp.sort(scaled, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep_sorted = cum - probs < top_p
            kept = jnp.sum(keep_sorted, axis=-1, keepdims=True)
            cutoff = jnp.take_along_axis(sorted_logits, kept - 1, axis=-1)
            scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
        return jax.random.categorical(key, scaled, axis=-1)

    sample._sampler_key = ("make_sampler", temperature, top_k, top_p)
    return sample


def _sampler_cache_id(sample: Callable) -> Any:
    """Cache identity for a sampler: its configuration when it advertises
    one, the object itself otherwise."""
    return getattr(sample, "_sampler_key", sample)


def _mask_top_k_top_p(
    scaled: jax.Array,            # (rows, vocab) f32 logits / temperature
    top_ks: jax.Array,            # (rows,) i32
    top_ps: Optional[jax.Array],  # (rows,) f32
) -> jax.Array:
    """``scaled`` with what a row's top-k and then its nucleus cut away
    at -inf: ``make_sampler``'s two masks with k and p as traced per-row
    data, bit for bit, from ONE sort of the vocabulary."""
    vocab = scaled.shape[-1]
    # traced per-row k: make_sampler's static `sort(...)[..., -k]` becomes
    # a take_along_axis at index vocab - k on the ascending sort — the
    # identical cutoff value, so the masked logits match bit-for-bit
    sorted_scaled = jnp.sort(scaled, axis=-1)
    k_active = ((top_ks > 0) & (top_ks < vocab))[:, None]
    k_idx = jnp.clip(vocab - top_ks, 0, vocab - 1)
    kth = jnp.take_along_axis(sorted_scaled, k_idx[:, None], axis=-1)
    masked = jnp.where(k_active & (scaled < kth), -jnp.inf, scaled)
    if top_ps is None:
        return masked
    # nucleus cutoff AFTER top-k, exactly make_sampler's order: its mass is
    # computed over the surviving (possibly -inf-masked) logits, descending.
    # make_sampler sorts those again; the top-k mask is by value and what
    # it masks is the smallest, so the same mask on the sort already made,
    # reversed, holds the very same values
    p_active = (top_ps > 0.0) & (top_ps < 1.0)
    sorted_desc = jnp.where(
        k_active & (sorted_scaled < kth), -jnp.inf, sorted_scaled
    )[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = cum - probs < top_ps[:, None]
    kept = jnp.sum(keep_sorted, axis=-1, keepdims=True)
    cutoff = jnp.take_along_axis(
        sorted_desc, jnp.maximum(kept - 1, 0), axis=-1
    )
    return jnp.where(
        p_active[:, None] & (masked < cutoff), -jnp.inf, masked
    )


def sample_rows(
    logits: jax.Array,       # (rows, vocab)
    temperatures: jax.Array,  # (rows,) f32; <= 0 means greedy
    top_ks: jax.Array,        # (rows,) i32; <= 0 or >= vocab disables
    keys: jax.Array,          # (rows, 2) uint32 per-row PRNG keys
    top_ps: Optional[jax.Array] = None,  # (rows,) f32; <=0 or >=1 disables
) -> jax.Array:
    """Per-row temperature / top-k / top-p sampling with per-row keys —
    the serving engine's batched counterpart of :func:`make_sampler`.

    The engine decodes MANY requests in one jitted program, so the
    sampler configuration must be traced per-row data, never baked-in
    constants (a per-config program would be a recompile per request —
    the exact storm the ``serve_decode`` golden pins against). The math
    mirrors ``make_sampler`` (same temperature clamp, same sort-based
    top-k cutoff, same nucleus cutoff over the descending sort, same
    ``jax.random.categorical``) so a row here and a single-request
    ``generate()`` with the same settings and key draw the SAME token —
    parity-pinned in tests/transformer/test_serving.py.
    ``temperature <= 0`` is argmax: greedy stays the default AND the
    zero-temperature limit, with no randomness consumed.

    The cost follows the rows: ONE ``lax.cond`` on the call's own
    temperatures. A call in which no row samples runs the argmax and
    nothing else; everything only a sampling row reads (the float32
    divide, the sort, softmax / cumsum, the categorical's Gumbel draw)
    lives in the other branch. Greedy is so neither a separate program
    nor the sampling arithmetic, and a sampled row draws the same token
    whichever branch the calls around it took."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def sampled_path():
        scaled = logits.astype(jnp.float32) / jnp.maximum(
            temperatures, 1e-6
        )[:, None]
        masked = _mask_top_k_top_p(scaled, top_ks, top_ps)
        sampled = jax.vmap(
            lambda key, row: jax.random.categorical(
                key, row[None], axis=-1)[0]
        )(keys, masked)
        return jnp.where(
            temperatures <= 0.0, greedy, sampled.astype(jnp.int32)
        )

    return jax.lax.cond(
        jnp.any(temperatures > 0.0), sampled_path, lambda: greedy
    )


def request_sample_key(base_key: jax.Array, req_id: jax.Array,
                       num_generated: jax.Array) -> jax.Array:
    """The per-token sampling key: ``fold_in(fold_in(base, req_id), n)``
    where ``n`` counts tokens already generated for the request.

    Keyed by REQUEST position, not by engine tick: a preempted-and-
    resumed sequence regenerates its tokens at the same positions and so
    redraws the SAME samples — recompute-style preemption stays invisible
    in the output even for temperature > 0 rows."""
    return jax.random.fold_in(
        jax.random.fold_in(base_key, req_id), num_generated
    )


def gather_positions(a: jax.Array, index: jax.Array) -> jax.Array:
    """``a`` (batch, seq, ...) at the FLAT positions ``index`` into ``batch *
    seq``: ``index.shape + a.shape[2:]``. Only the two token axes are
    flattened: what follows them is a position's own, be it one width or
    (streams, width); flattened down to the last axis, a trunk of several
    streams a position would give rows of OTHER positions without an error."""
    return a.reshape((-1,) + a.shape[2:])[index]


class TransformerInferenceModule:
    """Single-host inference over a trained checkpoint."""

    def __init__(
        self,
        config: TransformerConfig,
        module: ParallelModule,
        params: Any,
        tokenizer: Optional[Tokenizer] = None,
    ):
        self.config = config
        self.architecture = config.transformer_architecture
        self.module = module
        self.params = params
        self.tokenizer = tokenizer
        self._logits_fn = None
        self._decode_fn = None
        # (max_len, ragged) the per-step decode closure was traced for
        self._decode_key: Optional[tuple] = None
        self._decode_loop = None
        self._decode_loop_key = None

    # ------------------------------------------------------------- loading
    @classmethod
    def from_checkpoint(
        cls,
        checkpoint_dir: Path | str,
        vocab_file: Optional[Path | str] = None,
        overwrite_config: Optional[dict] = None,
        topology: Optional[dict] = None,
    ) -> "TransformerInferenceModule":
        """Reads ``config.yml`` + per-layer npz files from a checkpoint dir
        (reference: inference_model.py:55-87).

        ``topology`` enables mesh-sharded inference for models too big for
        one chip: e.g. ``{"model_parallel_size": 4}`` tensor-parallelizes
        every layer over 4 devices (the reference instead hops layer slices
        across GPUs sequentially, inference_module.py:77-109 — TP keeps all
        devices busy every layer). Checkpoints are layout-independent, so
        any saved model loads at any ``model_parallel_size``."""
        ckpt = Path(checkpoint_dir)
        latest = ckpt / "latest"
        if latest.is_file():
            ckpt = ckpt / latest.read_text().strip()
        config_file = ckpt / "config.yml"
        if not config_file.is_file():
            raise FileNotFoundError(f"no config.yml in {ckpt}")
        from .config import strip_removed_config_keys

        config = TransformerConfig.from_dict(
            strip_removed_config_keys(yaml.safe_load(config_file.read_text())),
            overwrite_values=overwrite_config,
        )
        topo = None
        if topology is not None:
            from ...topology import Topology, TopologyConfig

            tdict = dict(topology)
            if tdict.get("pipe_parallel_size", 1) != 1:
                # explicit raise (not assert): stripped asserts would let a
                # pp>1 stack silently decode without its KV caches
                raise ValueError(
                    "inference shards with model parallelism only; use "
                    "model_parallel_size, not pipe stages"
                )
            tdict.setdefault("pipe_parallel_size", 1)
            tdict.setdefault("data_parallel_size", 1)
            tdict.setdefault("micro_batch_size", 1)
            tdict.setdefault("gradient_accumulation_steps", 1)
            topo = Topology(TopologyConfig.from_dict(tdict))
        module = init_model(config, topo)
        if topo is None:
            params = module.init_params(jax.random.PRNGKey(0))
            params = module.ckpt_unview(
                load_model_checkpoint(
                    ckpt, module.ckpt_view(params), module.ckpt_metas()
                ),
                params,
            )
        else:
            # init + load on host CPU first: doing it on the accelerator
            # would materialize the full model on device 0 and OOM exactly
            # the too-big-for-one-chip models sharded inference is for;
            # shard_params then device_puts each leaf pre-sharded
            with jax.default_device(jax.local_devices(backend="cpu")[0]):
                params = module.init_params(jax.random.PRNGKey(0))
                params = module.ckpt_unview(
                    load_model_checkpoint(
                        ckpt, module.ckpt_view(params), module.ckpt_metas()
                    ),
                    params,
                )
            params = module.shard_params(params)
        tokenizer = None
        vocab = Path(vocab_file) if vocab_file else ckpt / "vocab.json"
        if vocab.is_file():
            tokenizer = Tokenizer.from_file(vocab)
        return cls(config, module, params, tokenizer)

    # ------------------------------------------------------------- forward
    def _make_ctx(self):
        """The context of every pass this module runs: deterministic, and
        marked as serving (a routed MLP drops nothing: nn/moe.py)."""
        ctx = self.module._make_ctx(deterministic=True, dropout_key=None)
        ctx.serving = True
        return ctx

    def moe_serve_rows(self, places: int):
        """``(form, rows, bounded)`` of a pass over ``places`` positions: the
        form the routed layers' expert matmuls take
        (``ParallelMoEMLP.serve_rows``), the rows they are given over all
        routed layers and loop steps (the serving engine counts them a tick),
        and whether those are a bound under the ``places x k`` assignments
        (``serve_bound``: the load then ends in the passes beyond the first)."""
        mesh = self._make_ctx().mesh
        mlps = [
            mlp for layer in self.module.layers
            for mlp in (getattr(layer, "mlp", None), getattr(layer, "mixer", None))
            if isinstance(mlp, ParallelMoEMLP)]
        routed = [mlp.serve_rows(places, mesh) for mlp in mlps]
        bounded = any(form == "grouped" and rows < places * mlp.top_k
                      for mlp, (form, rows) in zip(mlps, routed))
        return routed[0][0], self.architecture.loop_steps * sum(
            rows for _, rows in routed), bounded

    def _paged_layer_calls(self, ctx):
        """``call(layer)``: the layer as a function of (params, activations,
        paged cache), jitted once an architecture. Layers built from one
        architecture are one function: jitted on its own, the serving
        engine's program traces and lowers it once, not once a layer (the
        engine lowers a program a token width at its first tick, and that
        is set-up time), and calls it with each layer's parameters. XLA
        inlines the calls."""
        shared = {}

        def call(layer):
            # a pattern stack's layers are one function a KIND of mixer; a
            # layer that keeps no state takes the real positions instead
            key = (type(layer), id(layer.architecture),
                   type(getattr(layer, "mixer", None)))
            if key not in shared and not state_views(layer):
                shared[key] = jax.jit(
                    lambda p, x, real: layer(p, x, ctx, real=real)
                )
            elif key not in shared:
                shared[key] = jax.jit(
                    lambda p, x, cache: layer(p, x, ctx, kv_cache=cache)
                )
            return shared[key]

        return call

    def _run_layers(self, params, batch, caches, offset, paged_kernel=None,
                    gather_index=None, moe_load=False, exit_p=False):
        """One pass through the stack; TransformerLayers consume/produce the
        KV caches, edge layers run as in training (deterministic).

        ``paged_kernel`` (static) overrides the attention back-end for
        block-paged caches, ``ForwardContext.paged_kernel``: by default
        the Pallas kernel; ``'xla'`` is the gather formulation that tests
        hold the kernel to. Dense caches ignore it.

        ``gather_index`` (a traced (rows, w) int32 array of FLAT positions
        into the batch's ``b * s``) picks those positions' trunk
        activations AFTER the last TransformerLayer and BEFORE the
        post-trunk layers — which are position-pointwise, so only the
        positions that will actually be SAMPLED pay the final norm and
        the vocab projection (the serving engine's mixed program samples
        ONE position of each row, its last, out of the tick's packed
        tokens; projecting all of them priced a logit block nobody
        read). The returned logits are then (rows, w, vocab), entry
        ``(r, j)`` that of position ``gather_index[r, j]``.

        ``moe_load`` (static; a routed model on block-paged caches) adds a
        third result: the (E,) int32 count of assignments each expert
        received from the rows' REAL positions, summed over the layers
        (nn/moe.py ``serve``); behind it, where the stack's attention is
        sparse, one more int32: the calls of the layers' row walks that filled
        ties by position (nn/sparse_rows.py ``threshold_choice``).

        ``exit_p`` (static; a looped model with an exit gate) adds a result:
        the float32 exit distribution over the steps, ``(loop_steps, ...)``
        of the positions the head reads (``_run_looped``). A looped model
        takes one cache per (step, layer), or one paged view a LAYER whose
        pools hold every step's blocks.

        A pipelined (pp>1) stack wraps its TransformerLayers in a
        ``PipelinedBody``, which cannot consume KV caches: the cached path
        raises instead of silently decoding with no history (the caches
        would be skipped and every token computed as if it were first);
        the uncached path runs the body unstacked, like training's
        ``ParallelModule.forward``."""
        from ...parallel.pipeline import PipelinedBody

        ctx = self._make_ctx()
        if paged_kernel is not None:
            ctx.paged_kernel = paged_kernel
        if self.architecture.loop_steps > 1:
            pick = None
            if gather_index is not None:
                def pick(h):
                    return gather_positions(h, gather_index)
            logits, new_caches, p = self._run_looped(
                params, batch, ctx, caches=caches, offset=offset, pick=pick,
                exit_p=exit_p)
            return (logits, new_caches, p) if exit_p else (logits, new_caches)
        if exit_p:
            raise ValueError("exit_p reads a looped model's exit gate; this "
                             "model has loop_steps 1")
        last_tl = None
        if gather_index is not None:
            tls = [
                i for i, l in enumerate(self.module.layers)
                if isinstance(l, TRUNK_LAYERS)
            ]
            if not tls:
                raise ValueError(
                    "gather_index needs a TransformerLayer trunk to "
                    "gather after (pipelined/edge-only stacks have none)"
                )
            last_tl = max(tls)
        paged_layer_call = self._paged_layer_calls(ctx)
        # the views of the serving engine's state this stack's layers declare
        views = tuple({view for l in self.module.layers
                       for view in state_views(l)})
        paged = bool(caches) and isinstance(caches[0], views)
        real = None
        if paged and self.architecture.layer_pattern is not None:
            # which positions hold a token: the routed layers keep no state
            # of their own to read it from
            real = PagedKVCacheView.token_rows(
                caches[0], batch["token_ids"].shape)[2]

        x = batch
        new_caches = []
        li = 0
        for i, layer in enumerate(self.module.layers):
            p = self.module._layer_params(params, i)
            if isinstance(layer, TRUNK_LAYERS):
                if (paged and self.architecture.sparse_layers
                        and "sparse_tie_breaks" not in x):
                    # the sparse layers' count starts at the first trunk
                    # layer, not at the first of them: one structure for
                    # every call of a layer's one function
                    x = dict(x, sparse_tie_breaks=jnp.int32(0))
                # a layer is handed the state of ITS kind, the view its
                # mixer declares (a TransformerLayer: attention's; a block of
                # two mixers: the pair, as a tuple); an MLP (routed or dense)
                # nothing
                consumes = state_views(layer)
                if not consumes and real is not None:
                    x = paged_layer_call(layer)(p, x, real)
                elif caches is None or not consumes:
                    x = layer(p, x, ctx)
                else:
                    names = " and a ".join(c.__name__ for c in consumes)
                    held = caches[li:li + len(consumes)]
                    if len(held) < len(consumes):
                        raise ValueError(
                            f"layer {i} consumes a {names} but only "
                            f"{len(caches)} were provided")
                    served = isinstance(held[0], views)
                    for cache, view in zip(held, consumes):
                        # a dense (k, v) pair is an attention layer's too
                        if not isinstance(cache, view) and (
                                served or view is not PagedKVCacheView):
                            raise ValueError(
                                f"layer {i} consumes a {names} and was "
                                f"handed a {type(cache).__name__}: the caches "
                                "are one a consuming mixer, in layer order")
                    cache = held[0] if len(consumes) == 1 else tuple(held)
                    if served:
                        x, kv = paged_layer_call(layer)(p, x, cache)
                    else:
                        x, kv = layer(p, x, ctx, kv_cache=cache, cache_offset=offset)
                    new_caches.extend([kv] if len(consumes) == 1 else kv)
                    li += len(consumes)
                if i == last_tl:
                    x = dict(x)
                    x["activations"] = gather_positions(
                        x["activations"], gather_index)
            elif isinstance(layer, PipelinedBody):
                if caches is not None:
                    raise ValueError(
                        "cached decode through a pipelined (pp>1) layer "
                        "stack would silently skip the KV caches and "
                        "recompute every token without history; decode at "
                        "pipe_parallel_size=1 (checkpoints are layout-"
                        "independent) or use generate(use_cache=False)"
                    )
                x = layer(p, x, ctx, stacked=False, remat=False)
            elif last_tl is not None and i > last_tl:
                # final norm and head of the positions that are sampled
                with jax.named_scope("head"):
                    x = layer(p, x, ctx)
            else:
                x = layer(p, x, ctx)
        if caches is not None and li != len(caches):
            raise ValueError(
                f"layer stack consumed {li} KV cache(s) but {len(caches)} "
                "were provided — a cache silently skipped here means "
                "silently wrong decode output"
            )
        if moe_load:
            load = x["moe_load"]
            if "sparse_tie_breaks" in x:
                load = jnp.concatenate([load, x["sparse_tie_breaks"][None]])
            return x["activations"], new_caches, load
        return x["activations"], new_caches

    def _loop_plan(self):
        """Where the parts of a looped stack lie in ``module.layers``: the
        indices before the trunk, the trunk's, the final norm's, the exit
        gate's (None without one), and those after."""
        layers = self.module.layers
        trunk = [i for i, l in enumerate(layers)
                 if isinstance(l, TransformerLayer)]
        norm = trunk[-1] + 1 if trunk else None
        if (not trunk or trunk != list(range(trunk[0], norm))
                or not isinstance(layers[norm], LayerNormWrapper)):
            raise ValueError(
                "a looped model's stack is embedding, a contiguous trunk of "
                "TransformerLayers, the final norm, [the exit gate,] the "
                f"head; got {[type(l).__name__ for l in layers]}"
            )
        gate = norm + 1 if isinstance(layers[norm + 1], LoopExitGate) else None
        after = (norm if gate is None else gate) + 1
        return (list(range(trunk[0])), trunk, norm, gate,
                list(range(after, len(layers))))

    def _run_looped(self, params, batch, ctx, caches=None, offset=None,
                    return_kv=False, pick=None, exit_p=False):
        """The walk of a looped model (``loop_steps > 1``): the trunk's
        layers ``loop_steps`` times over the SAME parameters, each step
        ending in the final norm (its output is what the next step starts
        from) and, with ``exit_p``, in the exit gate. ``pick`` maps the last
        normed ``(b, s, hidden)`` to the positions the gate and the head
        read (default: all). Returns ``(logits, caches or K/V, p)``; ``p``
        is the float32 exit distribution ``(loop_steps, ...)`` or None.

        The K and V of (step ``u``, layer ``l``) are cache line ``u *
        num_layers + l``:

        - no cache, or ``return_kv`` (the prompt pass): the steps are ONE
          rolled ``lax.scan`` whose body is the trunk, so the program holds
          ``num_layers`` layer applications whatever ``loop_steps``; the
          K/V come back as a list in line order.
        - block-paged caches (the serving engine): ``caches`` is one
          :class:`PagedKVCacheView` a LAYER, whose pools hold ``loop_steps x
          num_blocks`` blocks; step ``u`` addresses its own through the
          block table plus ``u * num_blocks`` (``PagedKVCacheView.at_step``;
          block 0 of every step's share is trash, as block 0 of a plain pool
          is), so kernel, scatter and view are the plain model's. The pools ride the scan's carry and
          are scattered into in place.
        - dense caches (``generate``): one ``(k, v)`` a line, the steps
          unrolled.
        """
        layers = self.module.layers
        steps = self.architecture.loop_steps
        before, trunk, norm_i, gate_i, after = self._loop_plan()
        num_layers = len(trunk)
        paged = caches is not None and isinstance(caches[0], PagedKVCacheView)
        if caches is not None:
            want = num_layers if paged else steps * num_layers
            if len(caches) != want:
                raise ValueError(
                    f"a looped stack of {num_layers} layers x {steps} steps "
                    f"takes {want} {'paged views (one a layer)' if paged else 'KV caches (one a line)'}"
                    f", got {len(caches)}: a cache silently skipped here "
                    "means silently wrong decode output"
                )
        if exit_p and gate_i is None:
            raise ValueError("exit_p reads the exit gate; set loop_exit_gate")

        def lp(i):
            return self.module._layer_params(params, i)

        x = batch
        for i in before:
            x = layers[i](lp(i), x, ctx)
        rest = {k: v for k, v in x.items() if k != "activations"}
        pick = pick or (lambda h: h)
        paged_layer_call = self._paged_layer_calls(ctx)

        def step(h, step_caches):
            """One pass of the trunk and the final norm over ``h``."""
            x = {**rest, "activations": h}
            out = []
            for l, i in enumerate(trunk):
                if step_caches is None and not return_kv:
                    x = layers[i](lp(i), x, ctx)
                    continue
                if step_caches is None:
                    x, kv = layers[i](lp(i), x, ctx, return_kv=True)
                elif paged:
                    x, kv = paged_layer_call(layers[i])(lp(i), x, step_caches[l])
                else:
                    x, kv = layers[i](lp(i), x, ctx, kv_cache=step_caches[l],
                                      cache_offset=offset)
                out.append(kv)
            h = layers[norm_i](lp(norm_i), x, ctx)["activations"]
            lam = None
            if exit_p:
                lam = layers[gate_i].exit_probability(lp(gate_i), pick(h))
            return h, out, lam

        def pools_of(view):
            return (view.pool_k, view.pool_v, view.scale_k, view.scale_v)

        h = x["activations"]
        with jax.named_scope("loop"):
            if caches is not None and not paged:
                new_caches, lams = [], []
                for u in range(steps):
                    h, out, lam = step(
                        h, caches[u * num_layers:(u + 1) * num_layers])
                    new_caches += out
                    lams.append(lam)
                lams = jnp.stack(lams) if exit_p else None
            else:
                num_blocks = caches[0].pool_k.shape[0] // steps if paged else 0

                def body(carry, u):
                    h, pools = carry
                    step_caches = None
                    if paged:
                        step_caches = [
                            view._replace(
                                pool_k=pk, pool_v=pv, scale_k=sk, scale_v=sv,
                            ).at_step(u, num_blocks)
                            for view, (pk, pv, sk, sv) in zip(caches, pools)
                        ]
                    h, out, lam = step(h, step_caches)
                    if paged:
                        pools, out = [pools_of(view) for view in out], None
                    return (h, pools), (out, lam)

                pools = [pools_of(view) for view in caches] if paged else None
                (h, pools), (kvs, lams) = jax.lax.scan(
                    body, (h, pools), jnp.arange(steps, dtype=jnp.int32))
                if paged:
                    new_caches = [
                        view._replace(pool_k=pk, pool_v=pv, scale_k=sk, scale_v=sv)
                        for view, (pk, pv, sk, sv) in zip(caches, pools)
                    ]
                else:  # stacked over the steps -> line order
                    new_caches = [(k[u], v[u]) for u in range(steps)
                                  for k, v in kvs]
        x = {**rest, "activations": pick(h)}
        if layers[norm_i].record_embeddings:
            x["embeddings"] = x["activations"]
        for i in after:
            x = layers[i](lp(i), x, ctx)
        p = exit_distribution(lams) if exit_p else None
        return x["activations"], new_caches, p

    def _make_batch(
        self,
        token_ids: jax.Array,
        position_ids: jax.Array,
        segment_ids: Optional[jax.Array] = None,
        scores_manipulation: Optional[jax.Array] = None,
    ) -> dict:
        b, s = token_ids.shape
        return {
            "token_ids": token_ids.astype(jnp.int32),
            "target_token_ids": jnp.zeros((b, s), jnp.int32),
            "position_ids": position_ids.astype(jnp.int32),
            "segment_ids": (
                jnp.zeros((b, s), jnp.int32)
                if segment_ids is None
                else segment_ids.astype(jnp.int32)
            ),
            "loss_weights": None,
            "embeddings": None,
            "attention_scores_manipulation": scores_manipulation,
        }

    def logits(self, token_ids, controls=None, control_log_additive=True) -> jax.Array:
        """Full-sequence logits (b, s, vocab).

        ``controls``: AtMan-style per-token attention controls
        (attention_control.Control) applied in every layer; with
        ``control_log_additive=True`` (reference default) as log(factor)
        score offsets, with ``False`` as multiplicative factors on
        min-shifted scores (reference: inference_settings.py:24-30 +
        attention.py:158-170)."""
        token_ids = jnp.asarray(token_ids)
        if token_ids.ndim == 1:
            token_ids = token_ids[None]
        b, s = token_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        manipulation = None
        if controls:
            from .attention_control import build_attention_scores_manipulation

            manipulation = build_attention_scores_manipulation(
                controls, seq_len=s, batch_size=b,
                log_additive=control_log_additive,
            )
        if self._logits_fn is None:
            def run(p, t, po, manip, log_additive):
                batch = self._make_batch(t, po)
                batch["attention_scores_manipulation"] = manip
                batch["attention_scores_manipulation_log_additive"] = log_additive
                return self._run_layers(p, batch, None, None)[0]

            # the flag is STATIC: each value compiles its own graph
            self._logits_fn = jax.jit(run, static_argnums=(4,))
        return self._logits_fn(
            self.params, token_ids, pos, manipulation, bool(control_log_additive)
        )

    def exit_probabilities(self, token_ids) -> jax.Array:
        """A looped model's exit distribution over its steps at every
        position, float32 ``(loop_steps, b, s)``, summing to 1 over the
        steps (needs ``loop_exit_gate``)."""
        token_ids = jnp.asarray(token_ids)
        if token_ids.ndim == 1:
            token_ids = token_ids[None]
        b, s = token_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        return jax.jit(
            lambda p, t, po: self._run_layers(
                p, self._make_batch(t, po), None, None, exit_p=True)[2]
        )(self.params, token_ids, pos)

    def hidden_states(
        self,
        token_ids,
        include: Optional[List[int]] = None,
        exclude: Optional[List[int]] = None,
    ) -> dict:
        """Per-layer hidden states keyed ``layer_{i}_{Class}``; filter with
        include/exclude layer indices (reference: HiddenStateRecorder,
        inference_module.py:24-74, inference_model.py:121-135)."""
        token_ids = jnp.asarray(token_ids)
        if token_ids.ndim == 1:
            token_ids = token_ids[None]
        b, s = token_ids.shape
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

        # (step, layer index) in the order the stack is walked; a looped
        # model walks its trunk and final norm once a step, and their states
        # are keyed ``step_{u}_layer_{i}_{Class}``
        order = [(None, i) for i in range(len(self.module.layers))]
        if self.architecture.loop_steps > 1:
            before, trunk, norm_i, _, _ = self._loop_plan()
            order = (
                [(None, i) for i in before]
                + [(u, i) for u in range(self.architecture.loop_steps)
                   for i in trunk + [norm_i]]
                + [(None, i) for i in range(norm_i + 1, len(self.module.layers))]
            )

        def run(params, t, po):
            ctx = self._make_ctx()
            x = self._make_batch(t, po)
            recorded = {}
            for u, i in order:
                layer = self.module.layers[i]
                x = layer(self.module._layer_params(params, i), x, ctx)
                if include is not None and i not in include:
                    continue
                if exclude is not None and i in exclude:
                    continue
                step = "" if u is None else f"step_{u}_"
                recorded[f"{step}layer_{i}_{type(layer).__name__}"] = x["activations"]
            return recorded

        return jax.jit(run)(self.params, token_ids, pos)

    # ------------------------------------------------------------ generate
    def _alloc_caches(self, kvs, max_len: int):
        caches = []
        for k, v in kvs:
            b, s = k.shape[0], k.shape[1]
            ck = jnp.zeros((b, max_len) + k.shape[2:], k.dtype)
            cv = jnp.zeros((b, max_len) + v.shape[2:], v.dtype)
            caches.append(
                (
                    jax.lax.dynamic_update_slice_in_dim(ck, k, 0, axis=1),
                    jax.lax.dynamic_update_slice_in_dim(cv, v, 0, axis=1),
                )
            )
        return caches

    def prefill_forward(self, params, token_ids, position_ids,
                        segment_ids=None, last_index=None, row_width: int = 1):
        """Traceable prompt pass: full stack with ``return_kv=True`` (the
        flash kernel stays active — no cache is CONSUMED here), returning
        (logits for one position, per-layer (k, v)).

        The sampled position is the last one by default; ``last_index``
        (a traced scalar) selects another — right-padded prompts, as the
        serving engine's bucketed prefill uses, sample at prompt_len-1.
        Shared by ``generate``'s dense-cache prefill and the serving
        engine's paged prefill (serve/engine.py), so the two products of
        one prompt pass can never diverge. ``row_width`` (static): the most
        tokens a row of a served tick will bring, which the pools' probe hands
        on for the layers whose final state is sized by it (a window layer's
        ring)."""
        from ...parallel.pipeline import PipelinedBody

        ctx = self._make_ctx()
        ctx.serve_row_width = row_width
        if self.architecture.loop_steps > 1:
            # a looped model: the K/V of every (step, layer), in line order
            def pick(h):
                if last_index is None:
                    return h[:, -1:]
                return jax.lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)

            batch = self._make_batch(token_ids, position_ids,
                                     segment_ids=segment_ids)
            return self._run_looped(params, batch, ctx, return_kv=True,
                                    pick=pick)[:2]
        transformer_idxs = [
            i for i, l in enumerate(self.module.layers)
            if isinstance(l, TRUNK_LAYERS)
        ]
        if not transformer_idxs:
            if any(isinstance(l, PipelinedBody) for l in self.module.layers):
                raise ValueError(
                    "cached generation through a pipelined (pp>1) layer "
                    "stack would silently decode without its KV caches; "
                    "decode at pipe_parallel_size=1 (checkpoints are "
                    "layout-independent) or use generate(use_cache=False)"
                )
            raise ValueError(
                "cannot run cached generation on a module with no "
                "TransformerLayer (nothing produces KV caches); use "
                "generate(use_cache=False) or fix the layer stack"
            )
        last_tl = max(transformer_idxs)

        x = self._make_batch(token_ids, position_ids, segment_ids=segment_ids)
        kvs = []
        for i, layer in enumerate(self.module.layers):
            p = self.module._layer_params(params, i)
            if isinstance(layer, TRUNK_LAYERS) and state_views(layer):
                # attention: its (k, v); a Mamba-2 mixer: its (ssm, conv)
                # lines; a short convolution: its tail; a block of two
                # mixers: one entry each, in the order it declares them
                x, kv = layer(p, x, ctx, return_kv=True)
                kvs.extend([kv] if len(state_views(layer)) == 1 else kv)
            else:
                x = layer(p, x, ctx)
            if i == last_tl:
                # only the sampled position feeds the post-trunk layers —
                # they are position-pointwise, and running the vocab
                # projection over the whole prompt would materialize
                # (b, s, vocab) logits (>1 GB at bench shapes, ~8 GB at a
                # 32k prompt)
                x = dict(x)
                if last_index is None:
                    x["activations"] = x["activations"][:, -1:]
                else:
                    x["activations"] = jax.lax.dynamic_slice_in_dim(
                        x["activations"], last_index, 1, axis=1
                    )
        return x["activations"], kvs

    def _prefill(
        self,
        token_ids: jax.Array,
        max_len: int,
        position_ids: Optional[jax.Array] = None,
        segment_ids: Optional[jax.Array] = None,
    ):
        """Prompt pass collecting per-layer KV, then seed fixed-size caches.

        ``position_ids``/``segment_ids`` carry left-padded (ragged) prompt
        batches: pads sit in their own segment so content never attends to
        them, and positions restart at the first content token so rotary
        phases match the unpadded prompt."""
        b, s = token_ids.shape
        pos = (
            jnp.broadcast_to(jnp.arange(s)[None], (b, s))
            if position_ids is None
            else position_ids
        )
        logits, kvs = jax.jit(self.prefill_forward)(
            self.params, token_ids, pos, segment_ids
        )
        return logits, self._alloc_caches(kvs, max_len)

    def _build_decode_loop(self, sample, stop_ids, steps, ragged=False):
        """The whole decode as one device program: ``lax.while_loop`` whose
        carry holds the KV caches, the last token, and preallocated
        (b, steps+1) token / (b, steps+1, vocab) logit buffers. The key
        sequence matches the per-step path exactly (first token sampled
        with the caller's key outside, each loop step splits), so fused
        and unfused decode produce identical generations.

        ``ragged``: the loop additionally takes per-row content lengths
        (the rotary clock — cache slots stay the causal clock, see
        nn/attention.py) and an additive pad mask that blanks the
        left-pad cache slots."""
        stop_arr = jnp.asarray(stop_ids, jnp.int32) if stop_ids else None

        def is_stop(tok):
            if stop_arr is None:
                return jnp.zeros(tok.shape, bool)
            return jnp.isin(tok, stop_arr)

        def loop(params, caches, tok0, logits0, prompt_len, key,
                 content_len=None, pad_mask=None):
            b = tok0.shape[0]
            tok0 = tok0.astype(jnp.int32)
            toks = jnp.zeros((b, steps + 1), jnp.int32)
            toks = jax.lax.dynamic_update_slice(toks, tok0[:, None], (0, 0))
            lgts = jnp.zeros((b, steps + 1, logits0.shape[-1]), logits0.dtype)
            lgts = jax.lax.dynamic_update_slice(lgts, logits0[:, None], (0, 0, 0))

            def cond(c):
                t, done = c[0], c[-1]
                return (t <= steps) & ~jnp.all(done)

            def body(c):
                t, caches, tok, key, toks, lgts, done = c
                key, sub = jax.random.split(key)
                offset = prompt_len + t - 1
                if ragged:
                    pos = (content_len + (t - 1))[:, None]
                    batch = self._make_batch(
                        tok[:, None], pos, scores_manipulation=pad_mask
                    )
                else:
                    pos = jnp.broadcast_to(offset[None, None], (b, 1))
                    batch = self._make_batch(tok[:, None], pos)
                logits, caches = self._run_layers(params, batch, caches, offset)
                nxt = sample(logits[:, -1], sub).astype(jnp.int32)
                # finished rows keep stepping (their output is trimmed on
                # the host), matching the per-step path's lockstep advance
                toks = jax.lax.dynamic_update_slice(toks, nxt[:, None], (0, t))
                lgts = jax.lax.dynamic_update_slice(
                    lgts, logits[:, -1][:, None], (0, t, 0)
                )
                return (t + 1, caches, nxt, key, toks, lgts, done | is_stop(nxt))

            init = (jnp.int32(1), caches, tok0, key, toks, lgts, is_stop(tok0))
            _, caches, _, _, toks, lgts, done = jax.lax.while_loop(
                cond, body, init
            )
            # the final caches are dead weight to the caller, but returning
            # them is what makes donate_argnums=(1,) real: donation only
            # frees an input when it aliases a same-shaped OUTPUT, and the
            # cache input has no other output to alias
            return toks, lgts, done, caches

        return loop

    def generate(
        self,
        input_ids,
        max_tokens: int = 32,
        sample_fn: Optional[Callable] = None,
        use_cache: bool = True,
        eos_token_id: Optional[int] = None,
        stop_tokens: Optional[List[int]] = None,
        seed: int = 0,
        fused_decode: bool = True,
    ) -> CompletionOutput:
        """Autoregressive decode (reference: inference_model.py:195-263).

        Stops at ``eos_token_id`` or any of ``stop_tokens`` (reference's
        ``stop_tokens`` sequence); per-step logits for the emitted tokens
        come back in ``CompletionOutput.logits`` like the reference's
        ``completion_logits``.

        Accepts a batch of prompts — a (b, s) array or a list of b token
        lists, including RAGGED lists of unequal length — and decodes all
        rows in one pass, each row stopping independently (the reference's
        cache is bs=1 only, attention.py:491). Ragged prompts are
        left-padded internally: pads sit in their own attention segment
        during prefill, decode masks their cache slots, and per-row rotary
        positions start at each row's first content token, so every row
        generates exactly what it would alone. Batched input returns a
        list of ``CompletionOutput``; 1-D input keeps the single-output
        form."""
        if isinstance(input_ids, str):
            assert self.tokenizer is not None, "text prompt needs a tokenizer"
            input_ids = self.tokenizer.encode(input_ids)
        elif (
            isinstance(input_ids, (list, tuple))
            and input_ids
            and isinstance(input_ids[0], str)
        ):
            # a batch of text prompts: encode each; unequal lengths ride
            # the ragged (left-padded) path below
            assert self.tokenizer is not None, "text prompts need a tokenizer"
            input_ids = [self.tokenizer.encode(s) for s in input_ids]
        pad_start = None
        if (
            isinstance(input_ids, (list, tuple))
            and input_ids
            and isinstance(input_ids[0], (list, tuple))
            and len({len(r) for r in input_ids}) > 1
        ):
            lens = [len(r) for r in input_ids]
            longest = max(lens)
            pad_start = jnp.asarray([longest - n for n in lens], jnp.int32)
            input_ids = [
                [0] * (longest - n) + list(r) for r, n in zip(input_ids, lens)
            ]
        prompt = jnp.asarray(input_ids, jnp.int32)
        single = prompt.ndim == 1
        if single:
            prompt = prompt[None]
        b, prompt_len = prompt.shape
        lay = _left_pad_layout(pad_start, prompt_len, max_tokens, use_cache)
        if eos_token_id is None and self.tokenizer is not None:
            eos_token_id = self.tokenizer.eos_token_id
        stop = set(stop_tokens or [])
        if eos_token_id is not None:
            stop.add(int(eos_token_id))
        sample = sample_fn or sample_argmax
        key = jax.random.PRNGKey(seed)
        row_tokens: List[List[int]] = [[] for _ in range(b)]
        # per row: a list of per-step (vocab,) arrays (per-step paths) OR
        # one contiguous (steps, vocab) slice (fused path); row_logits_out
        # below normalizes the union
        row_logits: List[Any] = [[] for _ in range(b)]
        finished = [False] * b

        def collect(tok, step_logits):
            """Append this step's token/logits to unfinished rows."""
            tok_host = np.asarray(tok)  # one transfer per step, not per row
            for i in range(b):
                if finished[i]:
                    continue
                row_tokens[i].append(int(tok_host[i]))
                row_logits[i].append(step_logits[i])
                finished[i] = row_tokens[i][-1] in stop

        arch = self.architecture
        if use_cache and (arch.layer_pattern is not None or arch.parallel_ssm):
            raise ValueError(
                "cached generate() keeps dense KV caches only; a layer_pattern "
                "stack or a parallel_ssm block (recurrent state beside KV) "
                "decodes through ServeEngine, or here with use_cache=False"
            )
        if use_cache:
            max_len = prompt_len + max_tokens
            logits, caches = self._prefill(
                prompt, max_len, position_ids=lay.prompt_pos, segment_ids=lay.prompt_seg
            )
            next_tok = sample(logits[:, -1], key)

        if use_cache and fused_decode:
            # max_tokens<=1 still emits the prologue's one token (matching
            # the per-step path); the loop body just never runs
            steps = max(0, max_tokens - 1)
            stop_ids = tuple(sorted(stop))
            ragged = lay.ragged
            fkey = (steps, _sampler_cache_id(sample), stop_ids, ragged)
            # shapes (batch, cache length, vocab) re-trace via jit; only
            # the baked-in constants need an explicit cache key
            if self._decode_loop is None or self._decode_loop_key != fkey:
                # the prefill caches die with this call — donating them
                # lets XLA run the loop carry in place instead of holding
                # a second (b, max_len) KV copy during decode. CPU can't
                # donate (every call would warn), so only accelerators do.
                donate = (1,) if jax.default_backend() != "cpu" else ()
                self._decode_loop = jax.jit(
                    self._build_decode_loop(sample, stop_ids, steps, ragged),
                    donate_argnums=donate,
                )
                self._decode_loop_key = fkey
            extra = (lay.content_len, lay.pad_mask) if ragged else ()
            toks, lgts, _, _ = self._decode_loop(
                self.params, caches, next_tok, logits[:, -1],
                jnp.asarray(prompt_len, jnp.int32), key, *extra,
            )
            toks_host = np.asarray(toks)  # ONE device->host transfer
            for i in range(b):
                end = toks_host.shape[1]
                for j in range(toks_host.shape[1]):
                    if int(toks_host[i, j]) in stop:
                        end = j + 1  # the stop token itself is emitted
                        break
                row_tokens[i] = [int(x) for x in toks_host[i, :end]]
                row_logits[i] = lgts[i, :end]  # contiguous, already stacked
        elif use_cache:
            collect(next_tok, logits[:, -1])

            # the jitted decode closure bakes in the sampler: invalidate on
            # a new length, a different sample_fn, or a raggedness change,
            # or a later call would silently reuse a stale closure
            ragged = lay.ragged
            if (
                self._decode_fn is None
                or self._decode_key != (max_len, ragged)
                or getattr(self, "_decode_sampler", None)
                != _sampler_cache_id(sample)
            ):
                def decode(params, caches, tok, offset, k, base=None, pm=None):
                    bb = tok.shape[0]
                    if base is not None:
                        pos = base[:, None]
                        batch = self._make_batch(
                            tok[:, None], pos, scores_manipulation=pm
                        )
                    else:
                        pos = jnp.broadcast_to(offset[None, None], (bb, 1))
                        batch = self._make_batch(tok[:, None], pos)
                    logits, new_caches = self._run_layers(params, batch, caches, offset)
                    nxt = sample(logits[:, -1], k)
                    return nxt, logits[:, -1], new_caches

                self._decode_fn = jax.jit(decode)
                self._decode_key = (max_len, ragged)
                self._decode_sampler = _sampler_cache_id(sample)

            tok = next_tok
            for t in range(1, max_tokens):
                if all(finished):
                    break
                key, sub = jax.random.split(key)
                # finished rows keep stepping (their output is discarded);
                # rows advance in lockstep so one shared cache_offset works
                extra = (lay.content_len + (t - 1), lay.pad_mask) if ragged else ()
                tok, step_logits, caches = self._decode_fn(
                    self.params, caches, tok,
                    jnp.asarray(prompt_len + t - 1, jnp.int32), sub, *extra,
                )
                collect(tok, step_logits)
        else:
            # refeed the whole (fixed-size) buffer each step: one compile
            max_len = prompt_len + max_tokens
            buf = jnp.zeros((b, max_len), jnp.int32)
            buf = jax.lax.dynamic_update_slice_in_dim(buf, prompt, 0, axis=1)
            fwd = jax.jit(
                lambda p, t, po, sg: self._run_layers(
                    p, self._make_batch(t, po, segment_ids=sg), None, None
                )[0]
            )
            if lay.ragged:
                pos, seg = lay.pos_all, lay.seg_all  # the shared left-padded layout
            else:
                pos = jnp.broadcast_to(jnp.arange(max_len)[None], (b, max_len))
                seg = None
            cur = prompt_len
            for _ in range(max_tokens):
                if all(finished):
                    break
                logits = fwd(self.params, buf, pos, seg)
                key, sub = jax.random.split(key)
                nxt = sample(logits[:, cur - 1], sub)
                collect(nxt, logits[:, cur - 1])
                buf = jax.lax.dynamic_update_slice(
                    buf, nxt[:, None].astype(jnp.int32), (0, cur)
                )
                cur += 1

        def row_logits_out(rl):
            if isinstance(rl, list):  # per-step paths collect step arrays
                return jnp.stack(rl, axis=0) if rl else None
            return rl  # fused path already holds the contiguous (end, vocab) slice

        outs = [
            CompletionOutput(
                completion_ids=row_tokens[i],
                completion=(
                    self.tokenizer.decode(row_tokens[i]) if self.tokenizer else None
                ),
                logits=row_logits_out(row_logits[i]),
            )
            for i in range(b)
        ]
        return outs[0] if single else outs
