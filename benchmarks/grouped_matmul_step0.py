"""Step 0 of ISSUE 50, on the chip: a routed layer's expert matmuls alone.

At the three routed cells' shapes, with ``group_sizes`` drawn as a cell's
router draws them (``router``: the tick's ~95 real places; ``full``: every
place) and with every row on one expert (``one``):

- ``--phase matmuls``: one matrix a timing, ``REPS`` of it chained in a
  ``fori_loop`` inside one jitted call, the host's clock around
  ``block_until_ready``: the one-hot einsum over ``(E, rows, 32, h)``
  capacity buffers, ``jax.lax.ragged_dot`` (for the record: its Mosaic
  kernels lose the ``moe`` scope), the library's
  ``jax.experimental.pallas.ops.tpu.megablox.gmm`` and this repo's
  ``ops/grouped_matmul.py`` over their tiles;
- ``--phase layers``: a routed layer's whole ``serve`` (router, ordering,
  experts, combine, shared expert), six layers with weights of their own in
  one program, the one-hot form against the grouped one;
- ``--phase both``.

    python benchmarks/grouped_matmul_step0.py --phase both      # the chip
    JAX_PLATFORMS=cpu python benchmarks/grouped_matmul_step0.py --smoke

Appends one JSON line a timing to ``chiprun_out/step0.jsonl`` and prints it.
The host clock reads the kernels ~13% slower than the device trace of a
cell's run does (PERF.md, PR 50): compare forms with it, take times from a
``--trace 2`` run of ``benchmark/run.py``.
"""
import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax
import jax.numpy as jnp
import numpy as np

# cell: experts held, of, k, h, f, places T, real places, glu
CELLS = {
    "lfm2": dict(E=64, of=64, k=4, h=2048, f=1536, T=256, real=95),
    "hybrid": dict(E=64, of=128, k=6, h=2688, f=1856, T=256, real=95),
    "olmoe": dict(E=64, of=64, k=8, h=2048, f=1024, T=128, real=64),
}
REPS = 10


def draw_group_sizes(rng, cell, how):
    """(E,) int32: the held experts' rows of one tick."""
    E, of, k, T, real = (cell[n] for n in ("E", "of", "k", "T", "real"))
    if how == "one":
        sizes = np.zeros(E, np.int32)
        sizes[3] = T * k
        return sizes
    places = real if how == "router" else T
    sizes = np.zeros(E, np.int32)
    for _ in range(places):
        for e in rng.choice(of, size=k, replace=False):
            if e < E:
                sizes[e] += 1
    return sizes


def timed(fn, *args):
    """Milliseconds a repetition: min and median of 7 calls of REPS each."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3 / REPS)
    return min(ts), float(np.median(ts))


def chain(matmul):
    """``REPS`` of ``matmul(x, w, ...)`` in one program, each fed by the
    last through one row of ``x`` (nothing is hoisted or merged)."""

    def run(x, *rest):
        def body(_, x):
            out = matmul(x, *rest)
            # every output feeds the next repetition: nothing is narrowed
            flat = out.reshape(-1, 128).sum(0).astype(x.dtype) * 1e-9
            return x.reshape(-1).at[:128].add(flat).reshape(x.shape)

        return jax.lax.fori_loop(0, REPS, body, x)

    return jax.jit(run)


LAYER_KW = {
    "lfm2": dict(router="sigmoid_bias", norm_topk_eps=1e-6),
    "hybrid": dict(router="sigmoid_bias", glu=False, shared_expert_width=3712),
    "olmoe": dict(norm_topk_prob=False),
}


def layers_phase(args, record):
    """A routed layer's whole ``serve`` (router, ordering, experts, combine,
    shared expert), six layers of weights of their own chained in one
    program: the one-hot form at C = 32 against the grouped one."""
    from scaling_tpu.nn.moe import ParallelMoEMLP

    for name in args.cells:
        cell = dict(CELLS[name])
        kw = dict(LAYER_KW[name])
        if args.smoke:
            shrink(cell)
            kw.pop("shared_expert_width", None)
        E, of, k, h, f, T, real_n = (
            cell[n] for n in ("E", "of", "k", "h", "f", "T", "real"))
        n_layers = 2 if args.smoke else 6
        layer = ParallelMoEMLP(
            io_features=h, intermediate_feature_factor=1.0, intermediate=f,
            num_experts=of, experts_held=E, top_k=k, dtype=jnp.bfloat16, **kw)
        params = [jax.jit(layer.init)(jax.random.PRNGKey(i))
                  for i in range(n_layers)]
        record(cell=name, phase="layer", leaf_formats={
            n: str(getattr(v, "format", None)) for n, v in params[0].items()
            if n.startswith("w_")})
        x = jax.random.normal(jax.random.PRNGKey(9), (T // 32, 32, h)).astype(
            jnp.bfloat16)
        for places, label in ((real_n, "router"), (T, "full")):
            real = (jnp.arange(T) < places).reshape(T // 32, 32)
            for form in ("dense", "grouped"):
                if form == "dense":
                    layer.serve_rows = lambda p, mesh=None: ("dense", E * p)
                else:
                    layer.__dict__.pop("serve_rows", None)

                @jax.jit
                def run(params, x, real):
                    load = 0
                    for p in params:
                        y, ld = layer.serve(p, x, real)
                        x = x + (0.01 * y).astype(x.dtype)
                        load = load + ld
                    return x, load

                ts = []
                out = run(params, x, real)
                jax.block_until_ready(out)
                for _ in range(9):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(params, x, real))
                    ts.append((time.perf_counter() - t0) * 1e3 / n_layers)
                record(cell=name, phase="layer", form=form, real_places=places,
                       draw=label, ms_layer_min=min(ts),
                       ms_layer_med=float(np.median(ts)),
                       load_sum=int(out[1][:E].sum()))
            layer.__dict__.pop("serve_rows", None)
        del params


def shrink(cell):
    """A cell at a size the CPU rehearses."""
    cell.update(E=4, of=4 * cell["of"] // cell["E"], h=256, f=384, T=64,
                real=20, k=2)


def matmuls_phase(args, record):
    """One matrix a timing: today's einsum, ``ragged_dot``, the library's
    ``gmm`` at the tiles that did best in call A's sweep (PERF.md, PR 50),
    this repo's kernel over its tiles."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from scaling_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(50)
    dt = jnp.bfloat16

    def attempt(matmul, *operands, **labels):
        try:
            mn, md = timed(chain(matmul), *operands)
            record(**labels, ms_min=mn, ms_med=md)
            return True
        except Exception as e:  # a tiling Mosaic refuses is a finding too
            record(**labels, error=repr(e)[:400])
            return False

    for name in args.cells:
        cell = dict(CELLS[name])
        if args.smoke:
            shrink(cell)
        E, k, h, f, T = (cell[n] for n in ("E", "k", "h", "f", "T"))
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        sides = {
            "up": (h, f, (jax.random.normal(keys[0], (E, h, f)) * 0.02).astype(dt)),
            "out": (f, h, (jax.random.normal(keys[1], (E, f, h)) * 0.02).astype(dt)),
        }
        for side, (kk, nn, w) in sides.items():
            labels = dict(cell=name, side=side)
            # today's: (E, rows, C = 32, kk) x (E, kk, nn)
            xin = jax.random.normal(keys[2], (E, T // 32, 32, kk)).astype(dt)
            attempt(lambda x, w: jnp.einsum("ebch,ehf->ebcf", x, w), xin, w,
                    form="einsum", weights_mb=E * kk * nn * 2 / 1e6, **labels)
            x = jax.random.normal(keys[3], (T * k, kk)).astype(dt)
            draws = {how: jnp.asarray(draw_group_sizes(rng, cell, how))
                     for how in ("router", "full", "one")}
            for how, gs in draws.items():
                drawn = dict(draw=how, rows=int(gs.sum()), **labels)
                attempt(lambda x, w, gs: jax.lax.ragged_dot(
                    x, w, gs, preferred_element_type=dt), x, w, gs,
                    form="ragged_dot", **drawn)
                for tiling in ([(16, kk, 128)] if args.smoke else [
                        (64, kk, 768 if nn % 768 == 0 else 512), (128, 512, nn)]):
                    attempt(lambda x, w, gs, t=tiling: gmm(
                        x, w, gs, preferred_element_type=dt, tiling=t,
                        interpret=args.smoke), x, w, gs,
                        form="gmm", tiling=list(tiling), **drawn)
                # this repo's kernel: one step a (column tile, group)
                tms = (32,) if args.smoke else (
                    (16, 32, 64, 128) if how == "router" else (32, 128))
                tns = [nn] if args.smoke else [nn, -(-nn // 2 // 128) * 128, 512]
                for tm, tn in [(tm, tn) for tn in tns for tm in tms]:
                    attempt(lambda x, w, gs, t=(tm, tn): grouped_matmul(
                        x, w, gs, tiles=t, interpret=args.smoke), x, w, gs,
                        form="own", tiling=[tm, tn], **drawn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cells", nargs="*", default=list(CELLS))
    ap.add_argument("--phase", default="matmuls",
                    choices=["matmuls", "layers", "both"])
    args = ap.parse_args()
    dev = jax.devices()[0]
    if not args.smoke and dev.platform != "tpu":
        sys.exit(f"step 0 measures a TPU; JAX found {dev.platform}")
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "step0.jsonl"), "a") as out_f:
        def record(**kw):
            line = json.dumps({**kw, "device_kind": dev.device_kind})
            out_f.write(line + "\n")
            out_f.flush()
            print(line, flush=True)

        if args.phase in ("layers", "both"):
            layers_phase(args, record)
        if args.phase in ("matmuls", "both"):
            matmuls_phase(args, record)


if __name__ == "__main__":
    main()
