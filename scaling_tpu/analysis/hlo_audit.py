"""Lowered-HLO auditor: compile-time invariants of the jitted hot paths.

AOT-lowers the real jitted train step (single-device and pp=2/mp=2/dp=2
mesh layouts) and the fused decode loop on the virtual CPU mesh —
``jax.jit(...).lower(...)`` — then walks both text forms of the program:

- the **StableHLO** (pre-optimization: what the user's program actually
  says) for precision hygiene — ``convert`` chains that widen bf16->f32
  into a ``dot_general`` operand, host callbacks / infeed / outfeed,
  rng-bit-generator counts;
- the **optimized HLO** (post SPMD partitioning: what the chip runs) for
  the collective inventory — all-reduce / all-gather / reduce-scatter /
  collective-permute / all-to-all counts and byte estimates per mesh
  axis, attributed by matching each op's replica groups against the
  topology's device grid.

A recompile-key signature (abstract input shapes + static step config)
rounds out each section so shape-signature drift shows up as a diff, not
a silent second compile on the chip.

The structured report is pinned against goldens in ``analysis/goldens/``
(exact on counts/signatures, banded on bytes/flops for XLA version
noise); ``python -m scaling_tpu.analysis audit --repin`` re-baselines.
"""

from __future__ import annotations

import hashlib
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

GOLDEN_DIR = Path(__file__).resolve().parent / "goldens"

# relative slack on byte/flop pins (XLA version noise; counts stay exact)
BYTES_RTOL = 0.15

_COLLECTIVE_OPS = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all",
)
# '= <result shapes> <op>(' — result may be a single 'f32[8,16]{1,0}' or a
# variadic tuple '(f32[100]{0}, f32[200]{0})'; dropping the tuple case
# would silently uncount fused gradient syncs (migrated from
# tests/transformer/test_hlo_cost_pins.py).
_COLLECTIVE_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"(" + "|".join(_COLLECTIVE_OPS) + r")(-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1}

_GROUPS_LITERAL_RE = re.compile(r"replica_groups=\{(\{[0-9,{} ]*\})\}")
_GROUPS_IOTA_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{([0-9,{} ]*)\}")


def _shape_bytes(shapes_text: str, skip_first: bool = False) -> int:
    """Bytes of the result shape(s). ``skip_first`` drops the leading
    tuple element — async ``-start`` ops return ``(operand, result, ...)``,
    and counting the aliased operand would double the payload versus the
    same collective in sync form."""
    shapes = _SHAPE_RE.findall(shapes_text)
    if skip_first and len(shapes) > 1:
        shapes = shapes[1:]
    total = 0
    for dtype, shape in shapes:
        n = 1
        for dim in shape.split(","):
            if dim:
                n *= int(dim)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def _parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    m = _GROUPS_LITERAL_RE.search(line)
    if m:
        return [
            [int(x) for x in grp.split(",") if x.strip()]
            for grp in re.findall(r"\{([0-9, ]*)\}", m.group(0))
        ]
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        n_groups, group_size = int(m.group(1)), int(m.group(2))
        dims = [int(x) for x in m.group(3).split(",")]
        total = 1
        for d in dims:
            total *= d
        ids: List[int] = list(range(total))
        if m.group(4):
            perm = [int(x) for x in m.group(4).split(",")]
            # transpose(reshape(iota, dims), perm).flatten()
            import itertools

            strides = [0] * len(dims)
            acc = 1
            for i in range(len(dims) - 1, -1, -1):
                strides[i] = acc
                acc *= dims[i]
            out = []
            for idx in itertools.product(*[range(dims[p]) for p in perm]):
                flat = sum(idx[k] * strides[perm[k]] for k in range(len(perm)))
                out.append(flat)
            ids = out
        return [
            ids[g * group_size:(g + 1) * group_size] for g in range(n_groups)
        ]
    return None


def _parse_pairs(line: str) -> Optional[List[Tuple[int, int]]]:
    m = _PAIRS_RE.search(line)
    if not m:
        return None
    return [
        (int(a), int(b))
        for a, b in re.findall(r"\{(\d+),(\d+)\}", m.group(0))
    ]


class MeshAxes:
    """Attribute collectives to mesh axes by matching their replica groups
    against the topology's device grid (arange(world).reshape(sizes))."""

    def __init__(self, axis_names: Sequence[str], axis_sizes: Sequence[int]):
        self.names = list(axis_names)
        self.sizes = list(axis_sizes)
        self.world = 1
        for s in self.sizes:
            self.world *= s
        self._by_groups: Dict[frozenset, str] = {}
        n = len(self.sizes)
        # every non-empty axis subset gets its canonical grouping (a grad
        # sync over data+context is one fused all-reduce spanning both)
        for mask in range(1, 1 << n):
            subset = [i for i in range(n) if mask & (1 << i)]
            if any(self.sizes[i] == 1 for i in subset):
                continue  # size-1 axes never appear in real groups
            groups = self._axis_groups(subset)
            name = "+".join(self.names[i] for i in subset)
            self._by_groups.setdefault(groups, name)

    def _coords(self, flat: int) -> List[int]:
        coords = []
        rem = flat
        for size in reversed(self.sizes):
            coords.append(rem % size)
            rem //= size
        return list(reversed(coords))

    def _axis_groups(self, subset: List[int]) -> frozenset:
        groups: Dict[tuple, List[int]] = {}
        for flat in range(self.world):
            coords = self._coords(flat)
            fixed = tuple(c for i, c in enumerate(coords) if i not in subset)
            groups.setdefault(fixed, []).append(flat)
        return frozenset(frozenset(g) for g in groups.values())

    def axis_of_groups(self, groups: List[List[int]]) -> str:
        key = frozenset(frozenset(g) for g in groups)
        if key in self._by_groups:
            return self._by_groups[key]
        if all(len(g) == self.world for g in groups):
            return "world"
        if all(len(g) == 1 for g in groups):
            return "self"
        return "unknown"

    def axis_of_pairs(self, pairs: List[Tuple[int, int]]) -> str:
        axes = set()
        for src, dst in pairs:
            cs, cd = self._coords(src), self._coords(dst)
            for i, (a, b) in enumerate(zip(cs, cd)):
                if a != b:
                    axes.add(self.names[i])
        return "+".join(sorted(axes)) if axes else "self"


def collective_inventory(
    hlo_text: str, mesh: Optional[MeshAxes] = None
) -> List[dict]:
    """Per-(op, axis) collective counts and byte estimates from optimized
    HLO text. Bytes are the per-partition result bytes (the same
    accounting the HLO cost pins calibrated their bands against)."""
    agg: Dict[Tuple[str, str], dict] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        if "-done(" in line:
            continue  # async pair: count the -start, not the -done
        shapes_text, op, is_start = m.group(1), m.group(2), bool(m.group(3))
        axis = "unattributed"
        if mesh is not None:
            groups = _parse_replica_groups(line)
            pairs = _parse_pairs(line)
            if groups:
                axis = mesh.axis_of_groups(groups)
            elif pairs:
                axis = mesh.axis_of_pairs(pairs)
        rec = agg.setdefault(
            (op, axis), {"op": op, "axis": axis, "count": 0, "bytes": 0}
        )
        rec["count"] += 1
        rec["bytes"] += _shape_bytes(shapes_text, skip_first=is_start)
    return sorted(agg.values(), key=lambda r: (r["op"], r["axis"]))


def collective_bytes(compiled) -> Dict[str, int]:
    """Back-compat surface for the HLO cost pins: total per-partition bytes
    moved by each collective op kind in a ``.compile()``d step."""
    out: Dict[str, int] = {}
    for rec in collective_inventory(compiled.as_text()):
        out[rec["op"]] = out.get(rec["op"], 0) + rec["bytes"]
    return out


# ------------------------------------------------------- StableHLO audit
_SH_CONVERT_RE = re.compile(
    r"%(\S+) = stablehlo\.convert %(\S+) : "
    r"\(tensor<[^>]*xbf16>\) -> tensor<[^>]*xf32>"
)
_SH_OPERAND_RE = re.compile(r"%([\w#.]+)")


def stablehlo_precision_audit(text: str) -> dict:
    """Walk the lowered (pre-optimization) StableHLO: bf16->f32 converts
    that feed dot_general operands (an fp32 matmul hiding in a bf16 path
    doubles its MXU cost), plus host-callback / infeed-outfeed presence
    and rng op counts. Value names are function-scoped, so the convert
    table resets at each ``func.func``."""
    upcast_feeds_dot = 0
    dots = 0
    converts_bf16_f32: set = set()
    rng = 0
    callbacks = 0
    infeed_outfeed = 0
    for line in text.splitlines():
        if re.search(r"^\s*func\.func\b", line):
            converts_bf16_f32 = set()
        m = _SH_CONVERT_RE.search(line)
        if m:
            converts_bf16_f32.add(m.group(1))
        if "stablehlo.dot_general" in line:
            dots += 1
            ops = _SH_OPERAND_RE.findall(
                line.split("stablehlo.dot_general", 1)[1]
            )[:2]
            if any(o in converts_bf16_f32 for o in ops):
                upcast_feeds_dot += 1
        if "stablehlo.rng_bit_generator" in line or "stablehlo.rng " in line:
            rng += 1
        if "stablehlo.custom_call" in line and "callback" in line:
            callbacks += 1
        if "stablehlo.infeed" in line or "stablehlo.outfeed" in line:
            infeed_outfeed += 1
    return {
        "dot_general_count": dots,
        "bf16_to_f32_dot_upcasts": upcast_feeds_dot,
        "host_callbacks": callbacks,
        "infeed_outfeed": infeed_outfeed,
        "rng_ops": rng,
    }


# --------------------------------------------------------- recompile key
def recompile_signature(args, static_config: dict) -> dict:
    """Stable signature of a jitted step's input avals + static config:
    shape-signature drift (a new static argnum, a changed batch layout)
    changes the hash and is caught as golden drift."""
    import jax

    lines: List[str] = [json.dumps(static_config, sort_keys=True)]
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    for path, leaf in flat:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        lines.append(f"{jax.tree_util.keystr(path)} {shape} {dtype}")
    text = "\n".join(lines)
    return {
        "hash": "sha256:" + hashlib.sha256(text.encode()).hexdigest()[:16],
        "leaves": len(flat),
        "static": static_config,
    }


# ------------------------------------------------------ section builders
def make_train_config(pp=1, dp=1, mp=1, gas=1, zero=False, seq=64, mbs=2,
                      hidden=128, layers=2, vocab=512, kv_heads=None,
                      mlp_factor=2.0, remat=None, vpp=1, slices=1):
    """The ONE GQA+RoPE+SwiGLU+RMS train-config builder shared by the
    audit sections (tiny defaults) and the HLO cost pins (which pass the
    bench-flagship shape) — a field added here reaches both, so the pins
    and the goldens keep measuring the same program family."""
    from scaling_tpu.models.transformer import TransformerConfig

    d = {
        "topology": {
            "model_parallel_size": mp, "pipe_parallel_size": pp,
            "data_parallel_size": dp, "micro_batch_size": mbs,
            "gradient_accumulation_steps": gas,
            "pipe_virtual_size": vpp, "pipe_token_slices": slices,
        },
        "transformer_architecture": {
            "vocab_size": vocab, "hidden_size": hidden, "num_layers": layers,
            "num_attention_heads": hidden // 64,
            "attention_num_kv_heads": (
                hidden // 64 if kv_heads is None else kv_heads
            ),
            "sequence_length": seq, "precision": "bfloat16",
            "mlp_type": "swiglu", "mlp_factor": mlp_factor, "norm_type": "rms",
            "relative_position_embedding_type": "rotary", "causal": True,
            "masked_softmax": {"kernel": "torch"},
            "weight_tying": False, "attention_qkv_in_one": False,
            "dropout_embedding": 0.0, "dropout_attention_probs": 0.0,
            "dropout_after_attention": 0.0, "dropout_after_mlp": 0.0,
        },
        "optimizer": {"gradient_clipping": 1.0, "zero": zero,
                      "loss_scaler": {"enable": False}},
        "learning_rate_scheduler": {"learning_rate": 3e-4,
                                    "learning_rate_warmup_steps": 10,
                                    "learning_rate_decay_iters": 1000},
        "trainer": {"train_iterations": 10, "seed": 0},
        "data": {}, "logger": {"log_dir": None},
    }
    if remat:
        d["topology"]["activation_checkpointing_type"] = remat
    return TransformerConfig.from_dict(d)


def lower_train_step(config):
    """Build + AOT-lower the real jitted train step for ``config`` with a
    synthetic stacked batch; returns ``(lowered, args, topology)``. The
    ONE copy of this recipe — the HLO cost pins' ``compile_step`` wraps
    it, so the audit goldens pin the same program the pins measure."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from scaling_tpu.models.transformer.model import (
        init_model, init_optimizer, loss_function,
    )
    from scaling_tpu.topology import Topology

    topology = Topology(config.topology)
    module = init_model(config, topology)
    optimizer = init_optimizer(config, module, topology)
    key = jax.random.PRNGKey(0)
    params = module.shard_params(module.init_params(key))
    opt_state = optimizer.init_state(params)
    step = module.build_train_step(optimizer, loss_function)
    arch = config.transformer_architecture
    topo = config.topology
    b = topo.micro_batch_size * topo.data_parallel_size
    gas, seq = topo.gradient_accumulation_steps, arch.sequence_length
    rng = np.random.default_rng(0)
    tokens = rng.integers(1, arch.vocab_size, size=(gas, b, seq), dtype=np.int64)
    batch = module.shard_batch(
        {
            "token_ids": jnp.asarray(tokens, jnp.int32),
            "target_token_ids": jnp.asarray(np.roll(tokens, -1, -1), jnp.int32),
            "position_ids": jnp.asarray(
                np.broadcast_to(np.arange(seq, dtype=np.int32), (gas, b, seq))
            ),
            "segment_ids": jnp.zeros((gas, b, seq), jnp.int32),
            "loss_weights": jnp.ones((gas, b, seq), jnp.float32),
        },
        stacked=True,
    )
    args = (params, opt_state, batch, key)
    lowered = step.lower(*args)
    return lowered, args, topology


def _audit_lowered(lowered, args, static_config: dict,
                   mesh: Optional[MeshAxes]) -> dict:
    compiled = lowered.compile()
    report = stablehlo_precision_audit(lowered.as_text())
    report["collectives"] = collective_inventory(compiled.as_text(), mesh)
    report["recompile_key"] = recompile_signature(args, static_config)
    try:
        an = compiled.cost_analysis()
        an = an[0] if isinstance(an, list) else an
        flops = an.get("flops")
        # a vanished key is 'cost analysis died', not 'zero flops' — keep
        # the distinction so the golden gate can flag it
        report["flops"] = None if flops is None else float(flops)
    except Exception:
        report["flops"] = None
    return report


def audit_train_section(pp=1, dp=1, mp=1, gas=1, zero=False, vpp=1,
                        slices=1, layers=2) -> dict:
    config = make_train_config(pp=pp, dp=dp, mp=mp, gas=gas, zero=zero,
                               vpp=vpp, slices=slices, layers=layers)
    lowered, args, topology = lower_train_step(config)
    mesh = MeshAxes(topology.mesh.axis_names, topology.mesh.devices.shape)
    static = {
        "kind": "train_step",
        "pp": pp, "dp": dp, "mp": mp, "gas": gas, "zero": zero,
        "donate_argnums": [0, 1],
    }
    # new schedule knobs enter the signature only when active, so the
    # legacy sections' pinned recompile-key hashes stay byte-identical
    if vpp > 1:
        static["vpp"] = vpp
    if slices > 1:
        static["token_slices"] = slices
    if layers != 2:
        static["layers"] = layers
    report = _audit_lowered(lowered, args, static, mesh)
    report["mesh"] = dict(
        zip(topology.mesh.axis_names, topology.mesh.devices.shape)
    )
    return report


def audit_decode_section(prompt_len=4, max_tokens=4) -> dict:
    """The fused decode loop (one ``lax.while_loop`` device program per
    generation): a host callback or a per-step sync sneaking into it is
    exactly the regression that turns decode latency into RTT-bound."""
    import jax
    import jax.numpy as jnp

    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule, sample_argmax,
    )
    from scaling_tpu.models.transformer.model import init_model

    config = make_train_config()
    module = init_model(config, None)
    params = module.init_params(jax.random.PRNGKey(0))
    inf = TransformerInferenceModule(config, module, params)
    prompt = jnp.arange(1, prompt_len + 1, dtype=jnp.int32)[None]
    logits, caches = inf._prefill(prompt, prompt_len + max_tokens)
    tok0 = sample_argmax(logits[:, -1])
    steps = max(0, max_tokens - 1)
    loop = jax.jit(inf._build_decode_loop(sample_argmax, (), steps))
    args = (params, caches, tok0, logits[:, -1],
            jnp.asarray(prompt_len, jnp.int32), jax.random.PRNGKey(0))
    lowered = loop.lower(*args)
    static = {
        "kind": "fused_decode", "prompt_len": prompt_len,
        "max_tokens": max_tokens, "steps": steps,
    }
    report = _audit_lowered(lowered, args, static, mesh=None)
    report["mesh"] = {}
    return report


def _count_pallas_custom_calls(text: str) -> int:
    """Pallas kernels lower to ``tpu_custom_call`` custom-calls on a real
    chip; off-TPU (interpret mode) the kernel body inlines as plain HLO
    and the count is 0. Pinning the count makes a silent fall-off-the-
    kernel regression (someone reroutes decode through the gather path on
    chip) golden drift, not a quiet 2x HBM-traffic surprise."""
    return len(re.findall(r"stablehlo\.custom_call\s*@tpu_custom_call", text))


def audit_serve_decode_section(num_slots=8, block_size=16,
                               max_blocks=4, prefill_chunk=32,
                               mp=1) -> dict:
    """The serving engine's MIXED program (serve/engine.py, ISSUE 11,
    token-major since ISSUE 33): ONE jitted step per tick covers the
    whole slot set — one-token decode rows and prefill-chunk rows
    alike, their real tokens
    packed back to back into one of the engine's (at most two) token
    widths, tagged purely by traced per-row lengths. Its recompile-key
    signature is the no-recompile-storm contract: the key bakes the
    chunk width plus the engine shape config, and
    NOTHING per-request — a scheduler change that moves prompt lengths
    or prefill offsets into the signature shows up as
    golden drift here, not as a compile storm on the chip.

    The section is the program of the SMALL width, which nearly every
    tick runs; the full width's program is the same function at
    another ``T`` and is pinned beside it under ``full_width`` (its own
    signature hash, ``dot_general_count`` and ``flops``), so that a
    change that makes the two differ in more than their width shows. The
    defaults are the smallest engine with both widths (8 slots x 32: 128
    under 256). ``pallas_custom_calls`` counts the paged-attention
    kernel's custom calls in the lowered HLO (0 off-TPU where the kernel
    runs interpreted).

    ``mp > 1`` lowers the SHARDED mixed program (ISSUE 14): the engine's
    KV pools shard over the model axis, the program partitions SPMD over
    the serving mesh, and the collective inventory pins the model-axis
    activation all-reduces the sharded tick pays — plus the recompile
    key grows an ``mp`` entry (only when sharded, so the mp=1 section's
    static config never names it)."""
    import jax

    from scaling_tpu.models.transformer.inference import (
        TransformerInferenceModule,
    )
    from scaling_tpu.models.transformer.model import init_model
    from scaling_tpu.serve.engine import EngineConfig, ServeEngine

    config = make_train_config(mp=mp)
    topology = None
    if mp > 1:
        from scaling_tpu.topology import Topology

        topology = Topology(config.topology)
    module = init_model(config, topology)
    params = module.init_params(jax.random.PRNGKey(0))
    if topology is not None:
        params = module.shard_params(params)
    inf = TransformerInferenceModule(config, module, params)
    engine = ServeEngine(inf, EngineConfig(
        num_slots=num_slots, block_size=block_size,
        num_blocks=2 * max_blocks + 1, max_blocks_per_seq=max_blocks,
        token_budget=64, prefill_chunk=prefill_chunk,
    ))
    base_key = engine._dev(jax.random.PRNGKey(0))
    small, full = engine.config.mixed_widths

    def lowered_at(width):
        # the tick's ONE host operand (serve/engine.py TickLayout: tables,
        # lengths, sampler rows, the packed tokens last), a token a row
        packed, tick = engine._layout.host(width)
        tick.new_lens[:] = 1
        args = (params, engine._pool_state(), engine._dev(packed), base_key,
                engine._prev)
        return engine._build_mixed_fn(width).lower(*args), args

    static = {
        "kind": "serve_mixed_step", "num_slots": num_slots,
        "block_size": block_size, "max_blocks_per_seq": max_blocks,
        "kv_dtype": engine.config.kv_dtype,
        "prefill_chunk": prefill_chunk,
        "mixed_width": engine.config.mixed_width,
        # the token widths the engine builds programs at, and this one's
        "token_widths": [small, full],
        "token_width": small,
    }
    mesh = None
    if mp > 1:
        # mp joins the recompile key ONLY when sharded
        static["mp"] = mp
        mesh = MeshAxes(
            topology.mesh.axis_names, topology.mesh.devices.shape
        )
    lowered, args = lowered_at(small)
    report = _audit_lowered(lowered, args, static, mesh=mesh)
    report["mesh"] = (
        dict(zip(topology.mesh.axis_names, topology.mesh.devices.shape))
        if mp > 1 else {}
    )
    report["pallas_custom_calls"] = _count_pallas_custom_calls(
        lowered.as_text()
    )
    lowered, args = lowered_at(full)
    at_full = _audit_lowered(
        lowered, args, {**static, "token_width": full}, mesh=mesh
    )
    report["full_width"] = {
        "hash": at_full["recompile_key"]["hash"],
        "dot_general_count": at_full["dot_general_count"],
        "flops": at_full["flops"],
        "collectives": at_full["collectives"],
    }
    return report


SECTIONS = {
    "train_single": lambda: audit_train_section(),
    "train_pp2_mp2": lambda: audit_train_section(pp=2, dp=2, mp=2, zero=True),
    # interleaved virtual stages: v x more pipe-axis collective-permutes
    # for ~v x less fill/drain garbage — the inventory pins that trade
    # (ISSUE 7; layers=4 so the 4 chunks hold one layer each)
    "train_pp2_vpp2": lambda: audit_train_section(
        pp=2, dp=2, mp=2, zero=True, gas=2, vpp=2, layers=4
    ),
    # TeraPipe token slicing: same permute family over S x more, thinner
    # work items, plus the KV-cache attention path
    "train_pp2_tokenslice": lambda: audit_train_section(
        pp=2, dp=2, mp=2, zero=True, gas=2, slices=2
    ),
    "decode_fused": lambda: audit_decode_section(),
    # continuous-batching serving: the paged decode step (ISSUE 9)
    "serve_decode": lambda: audit_serve_decode_section(),
    # mp=2 sharded serving: the SAME mixed program partitioned over the
    # model axis — per-axis collective inventory + mp in the recompile
    # key (ISSUE 14; the mp=1 section above stays byte-identical)
    "serve_decode_mp2": lambda: audit_serve_decode_section(mp=2),
}


def run_audit(sections: Optional[Sequence[str]] = None) -> dict:
    names = list(sections) if sections else list(SECTIONS)
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        raise ValueError(f"unknown audit sections {unknown}; have {list(SECTIONS)}")
    return {name: SECTIONS[name]() for name in names}


# ----------------------------------------------------- layout cost summary
def cost_summary_from_report(report: dict) -> dict:
    """Reusable per-layout cost summary from an audit section report (or
    a committed golden's JSON — same schema): per-axis and per-op totals
    of the collective inventory plus the compiled FLOPs and mesh. The
    exported surface the auto-sharding tuner (``scaling_tpu.tune``)
    consumes, so downstream cost models never reach into the
    audit-internal record lists."""
    per_axis: Dict[str, dict] = {}
    per_op: Dict[str, dict] = {}
    for rec in report.get("collectives") or []:
        for key, table in ((rec["axis"], per_axis), (rec["op"], per_op)):
            slot = table.setdefault(key, {"bytes": 0, "count": 0})
            slot["bytes"] += int(rec["bytes"])
            slot["count"] += int(rec["count"])
    return {
        "per_axis": per_axis,
        "per_op": per_op,
        "collectives": list(report.get("collectives") or []),
        "flops": report.get("flops"),
        "mesh": dict(report.get("mesh") or {}),
    }


def layout_cost_summary(pp=1, dp=1, mp=1, gas=1, zero=False, vpp=1,
                        slices=1, layers=2) -> dict:
    """Lower the real jitted train step for this layout (tiny audit
    shapes) and summarize its collective traffic per mesh axis — the
    artifact-fed ingredient of the tuner's cost model (docs/TUNING.md).
    Needs enough devices for the mesh (the 8-device virtual CPU mesh in
    CI)."""
    return cost_summary_from_report(
        audit_train_section(pp=pp, dp=dp, mp=mp, gas=gas, zero=zero,
                            vpp=vpp, slices=slices, layers=layers)
    )


def golden_cost_summary(name: str,
                        golden_dir: Optional[Path] = None) -> dict:
    """The committed golden's cost summary — per-axis collective bytes
    from a REAL lowered program, readable without jax or a mesh (the
    goldens are artifacts of past audits)."""
    path = golden_path(name, golden_dir)
    return cost_summary_from_report(json.loads(path.read_text()))


# ------------------------------------------------------------- golden pin
def golden_path(name: str, golden_dir: Optional[Path] = None) -> Path:
    return (golden_dir or GOLDEN_DIR) / f"{name}.json"


def compare_to_golden(
    name: str, report: dict, golden_dir: Optional[Path] = None,
    rtol: float = BYTES_RTOL,
) -> List[str]:
    """Drift lines (empty == clean). Counts, axes, signatures and op kinds
    compare exactly; bytes and flops within ``rtol`` (XLA version noise —
    the same philosophy as the HLO cost-pin bands)."""
    path = golden_path(name, golden_dir)
    if not path.is_file():
        return [f"{name}: no golden at {path} (run audit --repin)"]
    golden = json.loads(path.read_text())
    drift: List[str] = []

    def exact(field, a, b):
        if a != b:
            drift.append(f"{name}.{field}: golden {a!r} != current {b!r}")

    for field in (
        "bf16_to_f32_dot_upcasts", "host_callbacks", "infeed_outfeed",
        "rng_ops", "dot_general_count", "mesh",
        # serving sections only (None == None elsewhere): the paged
        # kernel's custom-call presence is part of the hot-path contract
        "pallas_custom_calls",
    ):
        exact(field, golden.get(field), report.get(field))
    exact("recompile_key.hash", golden.get("recompile_key", {}).get("hash"),
          report.get("recompile_key", {}).get("hash"))
    # the serving sections pin the engine's second program, the mixed
    # step at its full token width, beside the small one they audit
    g_full = golden.get("full_width") or {}
    c_full = report.get("full_width") or {}
    for field in ("hash", "dot_general_count", "collectives"):
        exact(f"full_width.{field}", g_full.get(field), c_full.get(field))

    def inv_map(inv):
        return {(r["op"], r["axis"]): r for r in inv or []}

    g_inv, c_inv = inv_map(golden.get("collectives")), inv_map(
        report.get("collectives")
    )
    for key in sorted(set(g_inv) | set(c_inv)):
        g, c = g_inv.get(key), c_inv.get(key)
        if g is None:
            drift.append(f"{name}: NEW collective {key} x{c['count']} "
                         f"({c['bytes']} B)")
        elif c is None:
            drift.append(f"{name}: collective {key} vanished "
                         f"(golden x{g['count']})")
        else:
            if g["count"] != c["count"]:
                drift.append(
                    f"{name}: collective {key} count {g['count']} -> "
                    f"{c['count']}"
                )
            gb, cb = g["bytes"], c["bytes"]
            if gb and abs(cb - gb) > rtol * gb:
                drift.append(
                    f"{name}: collective {key} bytes {gb} -> {cb} "
                    f"(> {rtol:.0%} band)"
                )
    for what, g, c in (("flops", golden, report),
                       ("full_width.flops", g_full, c_full)):
        gf, cf = g.get("flops"), c.get("flops")
        if (gf is None) != (cf is None):
            # cost analysis silently dying must not silently un-enforce
            # the pin
            drift.append(
                f"{name}: {what} availability changed {gf!r} -> {cf!r}")
        elif gf is not None and abs(cf - gf) > rtol * max(abs(gf), 1.0):
            drift.append(
                f"{name}: {what} {gf:.3g} -> {cf:.3g} (> {rtol:.0%} band)")
    return drift


def write_golden(name: str, report: dict,
                 golden_dir: Optional[Path] = None) -> Path:
    path = golden_path(name, golden_dir)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(report, indent=1, sort_keys=True) + "\n")
    return path
