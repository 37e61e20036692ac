"""Block-paged KV cache pools (PagedAttention, SOSP '23).

One device-resident pool per transformer layer: two leaves of
``(num_blocks, block_size, ...)``, carved into fixed-size
blocks that sequences of wildly different lengths share through
per-sequence block tables (replacing the per-request fixed-capacity
``_alloc_caches`` buffers, whose dense ``(b, prompt+max_tokens)`` shape
charged every row the longest row's memory). What the two leaves hold past
the block is the layer's mixer's to say: softmax attention's are keys and
values, ``(n_kv, h)`` each; a latent attention layer's have no head axis and
differ in width, the normed KV latent ``(kv_lora_rank,)`` and the one rotary
key ``(rope_line_width,)`` (nn/latent_attention.py). A SPARSE grouped-query
layer's line has a THIRD leaf beside K and V, the indexer's one key a token,
``(index_head_dim,)`` with no head axis (nn/sparse_attention.py): ``pool_i``,
paged through the same tables, written by the same scatter, counted in
``line_bytes``. A stack has it in every paged layer or in none.

KV shapes come from an abstract probe of the real layer stack
(``jax.eval_shape`` over ``prefill_forward``), the same idiom as
``TransformerLayer.init_token_slice_cache`` — GQA / head-dim / dtype
choices can never drift from the attention that fills the pool.

``kv_dtype='int8'`` stores values quantized with per-slot-per-head
scales; the quantizer lives in ``nn/attention.py`` (``kv_quantize_int8``)
so every write into the pool (``paged_scatter_kv``) rounds identically.

**mp > 1 (sharded serving, docs/SERVING.md "The fleet"):** when the
inference module rides a mesh with ``model_parallel_size > 1``, each
pool is SHARDED over the model axis on its kv-head dim — every mp shard
owns the ``(num_blocks, block_size, n_kv/mp, h)`` slice matching the
attention heads it computes, so pool memory per chip drops mp-fold and
big models' caches fit. Block tables / context lengths stay replicated
host state (they are addressing, not content), the engine's jitted
programs run SPMD over the serving mesh, and the Pallas paged kernel
runs per-shard on its slice (nn/attention.py wraps it in shard_map —
pallas calls are opaque to GSPMD).

**Looped models** (``loop_steps > 1``, docs/SERVING.md "Looped models"):
the K and V of every (step, layer) are a cache line of their own. A layer's
pool then holds ``loop_steps x num_blocks`` blocks, step ``u``'s at
``[u * num_blocks, (u + 1) * num_blocks)``; the scheduler's block ``b`` is
the same 16 tokens in every line, at ``u * num_blocks + b`` of every
layer's pool. Block 0 of every step's share is trash.

**Two rules, any number of kinds** (a ``layer_pattern`` stack,
docs/SERVING.md "Hybrid models"). What a layer keeps while it is served is
said in ONE place: its mixer names a view class (``STATE_VIEW``), and
``layer.consumes`` is that class. This file reads it there and knows two
rules, no kind:

- **paged**: ``PagedKVCacheView``, everything above. KV pools exist for the
  layers that declare it only.
- **a line a slot**: a view that declares ``LINES``, the names of the fields
  the pool owns (the view's other fields, ``context_len``, ``new_len``,
  ``token_map``, are the tick's addressing). Such a layer keeps, for every
  SLOT, one fixed-size line a field whatever the sequence's length: the
  probe's final state of the layer, one leaf a field in ``LINES``' order,
  with its leading dimension set to ``num_slots``. Nothing is paged, the
  scheduler counts no block for it. (Mamba-2's ``ssm`` and ``conv``,
  nn/mamba.py; a gated short convolution's ``tail``, nn/short_conv.py; a
  window attention layer's ring of ``k`` and ``v``, nn/window_attention.py:
  the last lines a query may still see, position ``p`` at line ``p % ring``.)

The state the engine's program takes and returns is ONE structure, so that
the lines are donated and aliased like the pools: ``(pool_k, pool_v, scale_k,
scale_v)``, then for every per-slot kind, in the order the stack first meets
them (``line_layers``), one list a field of its ``LINES``, each over that
kind's layers in layer order. ``kinds``, the view class of every consuming
layer in layer order, says which lists a state carries. A stack whose paged
lines have a third leaf carries ``pool_i`` LAST, after the per-slot lists (one
entry more than ``kinds`` accounts for); a two-leaf stack's state is what it
was before there was a third leaf.

**A layer under both rules** (a block whose attention and Mamba-2 mixer run
side by side: ``parallel_ssm``). Its ``consumes`` is a tuple, the two mixers'
views (``nn.base_layer.state_views``), and ``kinds`` holds an entry a consuming
MIXER: the layer's paged entry, then its per-slot one. Nothing else here
changes: the views are built and taken back one an entry, and the walk hands
such a layer the pair.
"""

from __future__ import annotations

from collections import Counter
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.attention import PagedKVCacheView, PagedTokenMap
from ..nn.base_layer import state_views


def serving_mesh(inference_module):
    """The inference module's mesh when it is model-parallel, else None —
    the ONE predicate the engine, the pool allocator and the audit
    section use to decide whether serving state must be mesh-placed."""
    topo = getattr(inference_module.module, "topology", None)
    if topo is None or topo.model_parallel_size <= 1:
        return None
    return topo.mesh


def line_layers(kinds: Optional[Iterable[type]]) -> Counter:
    """``{view class: layers}`` of the kinds among ``kinds`` that keep a line
    a slot (their view declares ``LINES``), in the order the stack first meets
    them: the order of their lists in the state, after the four of the
    pools."""
    return Counter(kind for kind in kinds or () if hasattr(kind, "LINES"))


def build_layer_views(
    state: Tuple,                    # (pool_k, pool_v, scale_k, scale_v)
    block_table: jax.Array,          # (rows, max_blocks) int32
    context_len: jax.Array,          # (rows,) int32
    new_len: Optional[jax.Array] = None,  # (rows,) int32 real new tokens
    token_map: Optional[PagedTokenMap] = None,  # a token-major batch's
    kinds: Optional[List[type]] = None,  # the view class a consuming layer
) -> List:
    """Per-layer :class:`PagedKVCacheView` s over the raw pool state —
    the shape the engine's jitted programs thread through ``_run_layers``
    (a looped model's too: one view a layer, whose pool holds every step's
    blocks and whose table ``_run_layers`` shifts step by step).

    ``new_len`` carries the chunked-prefill pad contract (mid-prompt
    pad-to-trash routing): of the ``s`` tokens a fixed-size chunk
    program presents, only the first ``new_len`` per row are real — the
    attention path writes the rest to the trash block and masks their
    slots, so ONE compiled chunk program serves every chunk length
    (including the final ragged chunk of every prompt). ``token_map``
    (``nn.attention.packed_token_map``) rides along when the batch holds
    the rows' tokens packed token-major instead of one row a batch row.

    A state that carries per-slot lines (more than four entries) comes with
    ``kinds``, the view class each consuming layer declares, in layer order:
    the views are then one a consuming layer, of its class (a per-slot kind's
    over the slots' lines of that layer), and lie in that order."""
    pool_k, pool_v, scale_k, scale_v = state[:4]
    per_slot = line_layers(kinds)
    # the third leaf of the paged lines, where the stack has one: the entry
    # after the per-slot kinds' lists
    line_lists = sum(len(kind.LINES) for kind in per_slot)
    pool_i = state[4 + line_lists] if len(state) > 4 + line_lists else None
    kv_views = [
        PagedKVCacheView(
            pool_k=pool_k[i], pool_v=pool_v[i],
            block_table=block_table, context_len=context_len,
            scale_k=None if scale_k is None else scale_k[i],
            scale_v=None if scale_v is None else scale_v[i],
            new_len=new_len, token_map=token_map,
            pool_i=None if pool_i is None else pool_i[i],
        )
        for i in range(len(pool_k))
    ]
    if not per_slot:
        return kv_views
    lists = iter(state[4:])
    by_kind = {PagedKVCacheView: iter(kv_views)}
    for kind in per_slot:
        fields = [next(lists) for _ in kind.LINES]
        by_kind[kind] = iter([
            kind(**dict(zip(kind.LINES, lines)), context_len=context_len,
                 new_len=new_len, token_map=token_map)
            for lines in zip(*fields)
        ])
    return [next(by_kind[kind]) for kind in kinds]


def state_from_views(views: List[PagedKVCacheView]) -> Tuple:
    """Inverse of :func:`build_layer_views`: the updated pools of the
    per-layer views ``_run_layers`` returns, as the
    state tuple ``(pool_k, pool_v, scale_k, scale_v)`` the program took
    (``None`` scales stay ``None``).

    What the engine's program returns beside its tokens. JAX pairs a
    donated buffer with an output of its shape and dtype in FLATTENED
    order, so only a state that leaves in the structure it entered in
    aliases ``pool_k[i]`` to the output computed from ``pool_k[i]``, and
    only then does XLA run the layer's scatter in place. The views
    themselves flatten layer-major (``k0, v0, table, ctx, new_len, map,
    k1, ...``): returned as they are they hand ``pool_k[1]``'s buffer to
    output ``v0``, written before layer 1 has read it, and XLA copies
    every pool but the first on every call. Their table, lengths,
    ``new_len`` and token map are the program's inputs (or derived from
    them) and do not come back. Per-slot views among them put the lists of
    their lines after the four of the pools, grouped by the views' own class
    (``line_layers``); the paged lines' third leaf, where they have one, comes
    last."""
    paged = [v for v in views if isinstance(v, PagedKVCacheView)]
    quantized = paged[0].scale_k is not None
    state = (
        [v.pool_k for v in paged],
        [v.pool_v for v in paged],
        [v.scale_k for v in paged] if quantized else None,
        [v.scale_v for v in paged] if quantized else None,
    )
    for kind in line_layers(type(v) for v in views):
        state += tuple([getattr(v, field) for v in views if type(v) is kind]
                       for field in kind.LINES)
    if paged[0].pool_i is not None:
        state += ([v.pool_i for v in paged],)
    return state


class PagedKVPools:
    """Per-layer block pools (the engine builds per-layer views from the
    raw state inside its jitted programs — ``build_layer_views``).

    Pytree-friendly: the device state is plain lists of arrays, handed to
    the jitted programs as ``(pool_k, pool_v, scale_k, scale_v)`` and
    taken back in that same structure (``state_from_views``), which is
    what lets the donated pools be updated in place."""

    def __init__(self, pool_k: List[jax.Array], pool_v: List[jax.Array],
                 scale_k: Optional[List[jax.Array]],
                 scale_v: Optional[List[jax.Array]],
                 block_size: int, loop_steps: int = 1,
                 kinds: Optional[List[type]] = None,
                 lines: Tuple[List[jax.Array], ...] = (),
                 pool_i: Optional[List[jax.Array]] = None):
        self.pool_k = pool_k
        self.pool_v = pool_v
        self.scale_k = scale_k
        self.scale_v = scale_v
        self.block_size = block_size
        # cache lines a layer's pool holds: a looped model's steps
        self.loop_steps = loop_steps
        # the view class each consuming layer declares, in layer order (None:
        # a stack of paged layers alone), and the per-slot kinds' lists in
        # state order: what the state carries after the four of the pools
        self.kinds = kinds
        self.lines = tuple(lines)
        # the third leaf of a sparse grouped-query layer's line, its index
        # keys (None: lines of two leaves)
        self.pool_i = pool_i

    @property
    def num_layers(self) -> int:
        return len(self.pool_k)

    @property
    def kv_lines(self) -> int:
        """Cache lines a token's K and V are written to: one a (step,
        layer)."""
        return self.loop_steps * len(self.pool_k)

    @property
    def quantized(self) -> bool:
        return self.scale_k is not None

    @property
    def num_blocks(self) -> int:
        """Blocks the scheduler counts (each lies in every line)."""
        return self.pool_k[0].shape[0] // self.loop_steps

    def line_blocks(self, block: int):
        """Where the scheduler's block ``block`` lies in a layer's pool:
        once a step."""
        return block + self.num_blocks * np.arange(self.loop_steps)

    @property
    def state_lines(self) -> int:
        """Layers that keep a line a slot."""
        return sum(line_layers(self.kinds).values())

    def state(self) -> Tuple:
        """What the jitted programs take (donated) and return."""
        third = () if self.pool_i is None else (self.pool_i,)
        return (self.pool_k, self.pool_v, self.scale_k, self.scale_v,
                *self.lines, *third)

    def absorb_state(self, state: Tuple) -> None:
        """Take back the updated state a jitted program returned."""
        self.pool_k, self.pool_v, self.scale_k, self.scale_v = state[:4]
        if self.pool_i is not None:
            *state, self.pool_i = state
        self.lines = tuple(state[4:])

    def paged_leaves(self) -> Tuple:
        """The lists of the paged lines' leaves that are there: what a
        copy-on-write fork copies and ``device_bytes`` counts."""
        return tuple(arrs for arrs in (self.pool_k, self.pool_v, self.scale_k,
                                       self.scale_v, self.pool_i)
                     if arrs is not None)

    @property
    def line_bytes(self) -> int:
        """Bytes a token's line takes in the pools, over all layers and
        steps: what a cached token costs."""
        return self.device_bytes() // (
            self.pool_k[0].shape[0] // self.loop_steps * self.block_size)

    def device_bytes(self) -> int:
        return sum(a.size * a.dtype.itemsize
                   for arrs in self.paged_leaves() for a in arrs)

    def state_bytes(self) -> int:
        """Bytes of the per-slot lines (beside ``device_bytes``)."""
        return sum(a.size * a.dtype.itemsize
                   for arrs in self.lines for a in arrs)


def init_pools(inference_module, num_blocks: int, block_size: int,
               kv_dtype: str = "native", num_slots: int = 0,
               row_width: int = 1) -> PagedKVPools:
    """Allocate zeroed pools shaped by probing the real layer stack.

    A stack with layers that keep a line a slot also gets their lines,
    ``num_slots`` of each, shaped by the same probe (the final state of a
    one-token pass; ``row_width``, the most tokens a row will bring to a
    tick, is handed to the probe for the kinds whose line is sized by it: a
    window layer's ring, nn/window_attention.py).

    ``kv_dtype``: ``'native'`` keeps the probe's KV dtype (the model's
    compute dtype); ``'int8'`` stores int8 values + float32 scales.

    On a model-parallel mesh each pool is sharded over the model axis on
    its kv-head dim (shape stays the GLOBAL ``(num_blocks, block_size,
    n_kv, h)``; every shard holds ``n_kv/mp`` heads) — the jitted
    programs compile SPMD and per-chip pool memory drops mp-fold."""
    if kv_dtype not in ("native", "int8"):
        raise ValueError(f"kv_dtype must be 'native' or 'int8', got {kv_dtype!r}")
    params = inference_module.params
    probe_tokens = jnp.zeros((1, 1), jnp.int32)
    probe_pos = jnp.zeros((1, 1), jnp.int32)

    def probe(p, t, po):
        return inference_module.prefill_forward(
            p, t, po, row_width=row_width)[1]

    kv_shapes = jax.eval_shape(probe, params, probe_tokens, probe_pos)
    # a pattern stack's probe holds the final state a consuming mixer, in
    # layer order: (k, v) of a paged one, of a per-slot one its lines (a
    # block of two mixers: an entry each)
    kinds = [view for layer in inference_module.module.layers
             for view in state_views(layer)]
    per_slot = line_layers(kinds)
    if per_slot:
        finals = list(zip(kinds, kv_shapes))
        kv_shapes = [f for kind, f in finals if kind not in per_slot]
    if not kv_shapes:
        raise ValueError(
            "the layer stack keeps no KV cache line: the paged engine serves "
            "stacks with at least one attention layer")
    # the probe returns the (k, v) of every cache line: a looped model's
    # lines are its steps x its layers, and a layer's pool holds its steps'
    loop_steps = inference_module.architecture.loop_steps
    if len(kv_shapes) % loop_steps:
        raise ValueError(
            f"the layer stack produced {len(kv_shapes)} KV cache lines, not a "
            f"multiple of loop_steps {loop_steps}"
        )
    kv_shapes = kv_shapes[:len(kv_shapes) // loop_steps]
    pool_blocks = loop_steps * num_blocks
    # commit the fresh pools to the device(s) the programs will run on:
    # an uncommitted zeros-array keys a SECOND executable-cache entry for
    # the engine's very first program call (every later call sees the
    # committed jit outputs absorb_state hands back) — a silent 2x
    # compile of the largest serving programs
    mesh = serving_mesh(inference_module)
    if mesh is None:
        # co-locate the pools with the params: the fleet bench places
        # each replica's params on its own device, and the pools (and so
        # every jitted program) must follow — mixed placements would pin
        # every replica back onto device 0
        device = jax.local_devices()[0]
        leaves = jax.tree_util.tree_leaves(params)
        if leaves and hasattr(leaves[0], "devices"):
            leaf_devices = leaves[0].devices()
            if len(leaf_devices) == 1:
                device = next(iter(leaf_devices))

        def placed(shape, dtype, head_dim):
            del head_dim
            return jax.device_put(jnp.zeros(shape, dtype), device)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..topology.topology import MODEL_AXIS

        mp = mesh.shape[MODEL_AXIS]

        def placed(shape, dtype, head_dim):
            n_kv = shape[head_dim]
            if n_kv % mp:
                raise ValueError(
                    f"mp={mp} sharded serving needs kv heads divisible by "
                    f"the model axis; this stack has n_kv={n_kv} — pick an "
                    f"mp that divides it (docs/SERVING.md)"
                )
            spec = [None] * len(shape)
            spec[head_dim] = MODEL_AXIS
            return jax.device_put(
                jnp.zeros(shape, dtype), NamedSharding(mesh, P(*spec))
            )

    pool_k: List[jax.Array] = []
    pool_v: List[jax.Array] = []
    pool_i: List[jax.Array] = []
    scale_k: Optional[List[jax.Array]] = [] if kv_dtype == "int8" else None
    scale_v: Optional[List[jax.Array]] = [] if kv_dtype == "int8" else None
    from ..nn.paged_attention import kv_pool_dims

    for k_aval, v_aval, *third in kv_shapes:
        if third:
            # a sparse grouped-query layer: K and V as any other's, and the
            # indexer's one key a token, no head axis
            if kv_dtype != "native" or mesh is not None:
                raise ValueError(
                    "a sparse attention layer's cache line has an index key "
                    "beside K and V: its rounding in an int8 pool is not "
                    "measured and it has no head axis for the model axis to "
                    "divide; serve it with kv_dtype='native' at "
                    "model_parallel_size 1")
            pool_i.append(placed(
                (pool_blocks, block_size, third[0].shape[2]),
                third[0].dtype, 2))
        if k_aval.ndim == 3:
            # a line without a head axis (latent attention): its two leaves
            # as the probe gave them, a token's values minor
            if kv_dtype != "native" or mesh is not None:
                raise ValueError(
                    "a latent attention layer's cache line has no head axis: "
                    "neither the per-head int8 scales nor the model axis has "
                    "anything to divide; serve it with kv_dtype='native' at "
                    "model_parallel_size 1")
            for pools, aval in ((pool_k, k_aval), (pool_v, v_aval)):
                pools.append(placed(
                    (pool_blocks, block_size, aval.shape[2]), aval.dtype, 2))
            continue
        n_kv, h = k_aval.shape[2], k_aval.shape[3]
        store = jnp.int8 if kv_dtype == "int8" else k_aval.dtype
        dims, head_dim = (block_size, n_kv, h), 2
        if kv_dtype == "native":
            # heads narrower than the 128 lanes lie several a lane row; blocks
            # of heads wider than the lanes, or of one head, lie head-major
            dims, head_dim = kv_pool_dims(
                block_size, n_kv, h, jnp.dtype(store).itemsize,
                1 if mesh is None else mp)
        pool_k.append(placed((pool_blocks, *dims), store, head_dim))
        pool_v.append(placed((pool_blocks, *dims), store, head_dim))
        if kv_dtype == "int8":
            scale_k.append(
                placed((pool_blocks, block_size, n_kv), jnp.float32, 2)
            )
            scale_v.append(
                placed((pool_blocks, block_size, n_kv), jnp.float32, 2)
            )
    if pool_i and len(pool_i) != len(pool_k):
        raise ValueError(
            f"{len(pool_i)} of {len(pool_k)} paged layers keep an index key: "
            "a stack's paged lines have a third leaf in every layer or in "
            "none")
    if not per_slot:
        return PagedKVPools(pool_k, pool_v, scale_k, scale_v, block_size,
                            loop_steps, pool_i=pool_i or None)
    if mesh is not None:
        raise ValueError("per-slot state lines are not sharded: serve a "
                         "layer_pattern stack at model_parallel_size 1")
    if num_slots <= 0:
        raise ValueError("a stack with layers that keep a line a slot needs "
                         "num_slots")
    lines = []
    for kind in per_slot:
        # a layer's final state: one leaf a field of LINES, in that order
        layers = [jax.tree_util.tree_leaves(f) for k, f in finals if k is kind]
        lines += [[placed((num_slots, *a.shape[1:]), a.dtype, 1) for a in field]
                  for field in zip(*layers)]
    return PagedKVPools(pool_k, pool_v, scale_k, scale_v, block_size,
                        loop_steps, kinds, lines, pool_i or None)
