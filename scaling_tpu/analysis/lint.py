"""JAX-aware static lint over the package source (AST pass, no jax import).

Rules — stable IDs, severities, and the contexts they fire in:

========  ========  ==========================================================
ID        severity  meaning
========  ========  ==========================================================
STA001    error     Python ``if``/``while``/``bool()`` branching on a
                    traced-array expression inside a traced context (a
                    retrace hazard / ConcretizationTypeError on the chip).
STA002    error     ``numpy`` host op applied to a traced value inside a
                    traced context (silently falls off the device).
STA003    error     host sync inside a traced context: ``.item()`` /
                    ``float()`` / ``int()`` / ``bool()`` / ``np.asarray()``
                    on array values (stalls the dispatch pipeline).
STA004    error     PRNG key reuse: the same key variable consumed by two
                    ``jax.random.*`` draws with no ``split``/``fold_in``
                    reassignment in between (correlated randomness).
STA005    warning   mutable default argument value.
STA006    warning   dtype literal that bypasses the configured precision
                    policy (hardcoded f16/f64 in model code; the policy
                    admits bf16/f32 via ``precision`` config only).
STA007    error     swallowed exception in resilience-critical code
                    (``trainer/``, ``checkpoint/``, ``data/``,
                    ``resilience/``, ``runner/``, ``obs/``): a bare ``except:`` /
                    ``except Exception`` / ``except BaseException``
                    handler that neither re-raises, logs, nor uses the
                    bound exception — a fault-masking black hole in the
                    exact layer whose job is surfacing faults.
STA008    error     stage-shift ``jnp.concatenate`` in a traced context:
                    one operand expanded (``x[None]`` /
                    ``jnp.expand_dims``) concatenated with a partial
                    slice (``s[:-1]`` / ``s[1:]``) of another array —
                    the exact idiom jax 0.4.37's XLA SPMD partitioner
                    MISCOMPILED under model-parallel params riding a
                    vmapped stage dimension (PR 7: every pp x mp
                    MULTICHIP arm computed wrong activations, max error
                    ~11 vs sequential). Use roll-then-overwrite
                    (``jnp.roll(s, 1, 0).at[0].set(inp)``) instead —
                    exact, and partitions correctly.
STA009    error     lock-discipline race: an instance attribute mutated
                    on one thread (a ``threading.Thread(target=...)``
                    entry point's reachable set) and read/written on
                    another (the class's main-thread public API)
                    without a common ``with self.<lock>:`` guard on
                    both paths. Whole-program rule (concurrency.py);
                    ``# sta: lock(<attr>)`` declares deliberate
                    lock-free fields.
STA010    error     device sync on the hot path: ``block_until_ready``
                    / ``device_get`` / ``effects_barrier`` / ``.item()``
                    / ``float()``/``np.asarray()`` on device values in
                    code reachable from the trainer step dispatch, the
                    serve tick, or the fleet router dispatch. The
                    static complement of test_step_path.py's runtime
                    booby-trap. Whole-program rule (concurrency.py).
STA011    error     raw I/O (``open``/``os.replace``/``os.write``/
                    sockets/``Path.read_text``-family) in the gated
                    subsystems (resilience/, serve/, runner/, obs/,
                    checkpoint/) not reachable under ``retry_io`` or a
                    ``FaultPlan`` point — the ROADMAP's "new I/O paths
                    take a fault point + retry" contract, enforced
                    mechanically. Whole-program rule (concurrency.py).
STA012    error     barrier-divergence: an exit path (return /
                    fall-through) skips a named control-plane barrier
                    that another path rendezvouses on, AFTER a shared
                    side-effect in their common prefix — the PR 4
                    split-exit deadlock shape (one host enters
                    ``commit:step-N``, a peer exits early; the barrier
                    never fills). ``raise``/``sys.exit`` exits, abort-
                    flag-checked drains, ``cp.arrive`` paths, and
                    ``# sta: barrier-exempt(<name>)`` are sanctioned.
                    Whole-program rule (protocol.py).
STA013    error     RPC-contract mismatch between a module's client
                    send sites (dict literals with an ``"op"`` key)
                    and its server dispatch table: an op with no
                    handler, a dead handler no client sends, a reply
                    key a client reads that no handler path returns.
                    Whole-program rule (protocol.py).
STA014    error     protocol-edge coverage: an RPC send, named-barrier
                    wait, or replica spawn/kill site in the gated
                    subsystems (+ trainer/) not under a ``FaultPlan``
                    point / ``retry_io`` guard or not inside/beneath an
                    ``obs.span`` — STA011's contract extended to the
                    protocol layer. Whole-program rule (protocol.py).
STA015    warning   stale suppression: a ``# sta: disable=...`` comment
                    on a line where no (suppressed) finding fires, or a
                    ``# sta: lock(attr)`` annotation suppressing no
                    cross-thread hazard. Stale suppressions pre-silence
                    the next real finding on that line/field. Emitted
                    by the whole-program pass only (a per-file-only run
                    cannot tell which program-rule suppressions are
                    live).
STA016    error     trace-propagation: an RPC request dict literal (an
                    ``"op"`` key) in serve/ without a literal
                    ``"trace"`` key. The serving fleet's distributed-
                    tracing contract (docs/OBSERVABILITY.md, Tracing):
                    every envelope crossing a process boundary carries
                    the ambient trace context — even as None — or a
                    failover re-dispatch silently severs the request's
                    timeline. Control-plane envelopes (resilience/)
                    are exempt: their cross-host identity is DERIVED
                    (``derive_trace_id``) at both ends, not carried.
                    Whole-program rule (protocol.py).
========  ========  ==========================================================

Suppress a finding on its line with ``# sta: disable=STA003`` (a comma
rule list, ``# sta: disable=STA009,STA011``, suppresses exactly those
rules) or a bare ``# sta: disable`` (every rule on the line). Suppressed
findings are still reported (with ``suppressed: true``) but do not fail
the gate. STA015 itself is deliberately NOT silenced by the bare form
(a stale bare disable would self-suppress); an explicit
``# sta: disable=STA015`` in the comment's rule list is honored.

*Traced context* (where STA001-STA003 apply) is detected structurally:
functions decorated with ``jax.jit`` / ``jax.checkpoint`` / ``jax.vmap`` /
``jax.grad`` / ``jax.custom_vjp``-style transforms (including through
``functools.partial``), functions passed by name into ``jax.jit`` /
``jax.lax.scan`` / ``while_loop`` / ``cond`` / ``fori_loop`` / ``vmap`` /
``grad`` / ``checkpoint``, ``__call__`` methods of layer classes in the
traced-module allowlist (``nn/``, ``parallel/``, ``ops/``,
``models/transformer/layers/``), and anything nested inside those.
"""

from __future__ import annotations

import ast
import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

RULES = {
    "STA001": ("error", "python branch on a traced-array expression"),
    "STA002": ("error", "numpy host op on a traced value"),
    "STA003": ("error", "host sync inside a traced context"),
    "STA004": ("error", "PRNG key consumed twice without split/fold_in"),
    "STA005": ("warning", "mutable default argument"),
    "STA006": ("warning", "dtype literal bypasses the precision policy"),
    "STA007": ("error", "swallowed exception (broad except without "
                        "re-raise/logging/use)"),
    "STA008": ("error", "stage-shift concatenate (expand + partial slice) "
                        "in a traced context — XLA SPMD miscompile hazard"),
    "STA009": ("error", "cross-thread attribute access without a common "
                        "lock guard on both paths"),
    "STA010": ("error", "device sync reachable from the trainer step / "
                        "serve tick hot path"),
    "STA011": ("error", "raw I/O in a gated subsystem outside every "
                        "retry_io / FaultPlan guard"),
    "STA012": ("error", "exit path skips a barrier another path "
                        "rendezvouses on after shared side-effects"),
    "STA013": ("error", "RPC op/reply contract mismatch between client "
                        "sends and the server dispatch table"),
    "STA014": ("error", "protocol edge (rpc send / barrier wait / replica "
                        "spawn-kill) missing fault/retry guard or span"),
    "STA015": ("warning", "stale suppression: a '# sta:' annotation that "
                          "no longer suppresses any finding"),
    "STA016": ("error", "serve/ RPC request dict without a literal "
                        "'trace' key — the envelope must carry the "
                        "ambient trace context"),
}

# Module allowlist for traced-context rules (ISSUE 2: nn/, parallel/, ops/;
# the transformer layer stack is the same traced surface).
TRACED_MODULE_DIRS = (
    "nn",
    "parallel",
    "ops",
    "models/transformer/layers",
)

# Directory allowlist for STA007 (ISSUE 3; runner/ added by ISSUE 4): the
# layers that stand between a fault and a lost run — an exception silently
# eaten here is exactly how a torn checkpoint, a dead data mount, or a
# worker failure the supervisor should have relaunched goes unnoticed.
SWALLOW_SCOPE_DIRS = (
    "trainer",
    "checkpoint",
    "data",
    "resilience",
    "runner",
    # ISSUE 5: telemetry that silently eats its own failures is telemetry
    # you cannot trust during the post-mortem that needed it
    "obs",
    # ISSUE 9: the serving engine is a production loop — a swallowed
    # scheduler/pool/device error here is a request that silently never
    # completes (the exact failure mode the TTFT gates exist to catch)
    "serve",
    # ISSUE 15: the tuner grew CLI/serving-layout I/O (run-dir
    # records, emitted configs, goldens) — a swallowed read there turns
    # a corrupt calibration file into a silently wrong placement
    "tune",
)

# calls that count as "the handler surfaced the problem"
_LOG_CALL_ATTRS = {
    "debug", "info", "warning", "warn", "error", "exception", "critical",
    "log", "print_exc", "print_exception",
}

# jax transforms whose function argument (or decorated function) is traced
_TRACING_TRANSFORMS = {
    "jax.jit",
    "jax.vmap",
    "jax.pmap",
    "jax.grad",
    "jax.value_and_grad",
    "jax.checkpoint",
    "jax.remat",
    "jax.custom_vjp",
    "jax.custom_jvp",
    "jax.lax.scan",
    "jax.lax.while_loop",
    "jax.lax.cond",
    "jax.lax.fori_loop",
    "jax.lax.map",
    "jax.lax.associative_scan",
    "jax.experimental.shard_map.shard_map",
    "jax.eval_shape",
}

# jax.random draws that CONSUME their key (reusing it correlates streams);
# split/fold_in/PRNGKey/key/key_data/wrap_key_data derive, they don't draw.
_KEY_CONSUMERS = {
    "ball", "bernoulli", "beta", "binomial", "bits", "categorical", "cauchy",
    "chisquare", "choice", "dirichlet", "double_sided_maxwell", "exponential",
    "f", "gamma", "generalized_normal", "geometric", "gumbel", "laplace",
    "loggamma", "logistic", "lognormal", "maxwell", "multivariate_normal",
    "normal", "orthogonal", "pareto", "permutation", "poisson", "rademacher",
    "randint", "rayleigh", "t", "triangular", "truncated_normal", "uniform",
    "wald", "weibull_min",
}

_SUPPRESS_RE = re.compile(r"#\s*sta:\s*disable(?:=([A-Za-z0-9_, ]+))?")


def iter_comments(source: str) -> List[Tuple[int, str]]:
    """(lineno, text) for every actual COMMENT token. Annotation scans
    (``# sta: disable`` / ``lock(...)`` / ``barrier-exempt(...)``) go
    through here so a docstring QUOTING an annotation — this package's
    own docs are full of them — neither suppresses anything nor trips
    the stale-suppression audit. Falls back to a whole-line scan only
    when the source does not tokenize (the syntax-error path, where
    nothing downstream runs anyway)."""
    import io
    import tokenize

    out: List[Tuple[int, str]] = []
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type == tokenize.COMMENT:
                out.append((tok.start[0], tok.string))
    except (tokenize.TokenError, IndentationError, SyntaxError):
        return [(i, text) for i, text in
                enumerate(source.splitlines(), start=1) if "#" in text]
    return out


def parse_suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> suppressed rule set (None = bare disable, every rule).
    Shared by the per-file pass and the whole-program rules
    (concurrency.py) so ``# sta: disable=STA009,STA011`` means the same
    thing everywhere. Only real comments count (see iter_comments)."""
    out: Dict[int, Optional[Set[str]]] = {}
    for i, text in iter_comments(source):
        m = _SUPPRESS_RE.search(text)
        if not m:
            continue
        if m.group(1):
            out[i] = {r.strip().upper() for r in m.group(1).split(",")
                      if r.strip()}
        else:
            out[i] = None  # bare disable: every rule
    return out


@dataclasses.dataclass
class Finding:
    rule: str
    severity: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        sup = " (suppressed)" if self.suppressed else ""
        return f"{self.path}:{self.line}:{self.col}: {self.rule} {self.message}{sup}"


# --------------------------------------------------------------- name maps
class _Aliases:
    """Canonicalize attribute chains through the module's imports:
    ``jnp.where`` -> ``jax.numpy.where``, ``np.asarray`` ->
    ``numpy.asarray``, ``partial`` -> ``functools.partial``."""

    def __init__(self, tree: ast.Module):
        self.map: Dict[str, str] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    self.map[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module and node.level == 0:
                for a in node.names:
                    self.map[a.asname or a.name] = f"{node.module}.{a.name}"

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Dotted canonical name of a Name/Attribute chain, or None."""
        parts: List[str] = []
        while isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.map.get(node.id, node.id)
        return ".".join([root] + list(reversed(parts)))


def _is_jax_array_call(aliases: _Aliases, node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = aliases.resolve(node.func)
    return bool(
        name
        and (
            name.startswith("jax.numpy.")
            or name.startswith("jax.lax.")
            or name.startswith("jax.nn.")
            or name.startswith("jax.random.")
            or name.startswith("jax.scipy.")
        )
    )


def _contains(node: ast.AST, pred) -> bool:
    return any(pred(n) for n in ast.walk(node))


# Metadata that is static under tracing: `x.shape`-derived ints are host
# values by design, so `int(s * factor)` or `np.zeros(seg.shape, ...)` on
# them is NOT a host sync (float0 cotangents, capacity planning, ...).
_STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "itemsize", "aval",
                 "sharding")


def _walk_skip_static(node: ast.AST):
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, ast.Attribute) and n.attr in _STATIC_ATTRS:
            continue  # don't descend: `x.shape` never carries device data
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _contains_traced(aliases: _Aliases, node: ast.AST, names: Set[str]) -> bool:
    """Does ``node`` reference a traced name or jax array call, ignoring
    static-metadata attribute chains?"""
    return any(
        (isinstance(n, ast.Name) and n.id in names)
        or _is_jax_array_call(aliases, n)
        for n in _walk_skip_static(node)
    )


# ------------------------------------------------------------ module lint
class _ModuleLint:
    def __init__(self, path: Path, rel: str, source: str):
        self.path = path
        self.rel = rel
        self.source = source
        self.tree = ast.parse(source, filename=str(path))
        self.aliases = _Aliases(self.tree)
        self.findings: List[Finding] = []
        self.suppressions = self._parse_suppressions(source)
        norm = rel.replace("\\", "/")
        self.in_traced_dir = any(
            f"/{d}/" in f"/{norm}" or norm.startswith(f"scaling_tpu/{d}/")
            for d in TRACED_MODULE_DIRS
        )
        self.in_swallow_scope = any(
            f"/{d}/" in f"/{norm}" or norm.startswith(f"scaling_tpu/{d}/")
            for d in SWALLOW_SCOPE_DIRS
        )
        self.is_config_module = Path(rel).name == "config.py"
        self._parents: Dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(self.tree):
            for child in ast.iter_child_nodes(parent):
                self._parents[child] = parent

    @staticmethod
    def _parse_suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
        return parse_suppressions(source)

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 0)
        rules_at = self.suppressions.get(line, "absent")
        suppressed = rules_at is None or (
            isinstance(rules_at, set) and rule in rules_at
        )
        severity = RULES[rule][0]
        self.findings.append(
            Finding(rule, severity, self.rel, line,
                    getattr(node, "col_offset", 0), message, suppressed)
        )

    # ------------------------------------------------- traced-context set
    def _traced_functions(self) -> Set[ast.AST]:
        funcs = [
            n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        traced: Set[ast.AST] = set()

        def _transform_target(name: Optional[str]) -> bool:
            # .defvjp/.defjvp catch the fwd/bwd registered on a custom_vjp
            return bool(name) and (
                name in _TRACING_TRANSFORMS
                or name.rsplit(".", 1)[-1]
                in ("shard_map", "pallas_call", "defvjp", "defjvp")
            )

        def _decorator_traces(dec: ast.AST) -> bool:
            name = self.aliases.resolve(dec)
            if _transform_target(name):
                return True
            if isinstance(dec, ast.Call):
                fn = self.aliases.resolve(dec.func)
                if _transform_target(fn):
                    return True
                if fn in ("functools.partial", "partial"):
                    return bool(dec.args) and _transform_target(
                        self.aliases.resolve(dec.args[0])
                    )
            return False

        # (a) decorated with a tracing transform
        for fn in funcs:
            if any(_decorator_traces(d) for d in fn.decorator_list):
                traced.add(fn)
        # (b) passed by name into a tracing transform
        passed: Set[str] = set()
        for call in ast.walk(self.tree):
            if not isinstance(call, ast.Call):
                continue
            if _transform_target(self.aliases.resolve(call.func)):
                for arg in call.args:
                    if isinstance(arg, ast.Name):
                        passed.add(arg.id)
        for fn in funcs:
            if fn.name in passed:
                traced.add(fn)
        # (c) __call__ / forward methods of classes in traced modules
        if self.in_traced_dir:
            for fn in funcs:
                if fn.name in ("__call__", "forward") and isinstance(
                    self._parents.get(fn), ast.ClassDef
                ):
                    traced.add(fn)
        # (d) closure: anything nested inside a traced function
        changed = True
        while changed:
            changed = False
            for fn in funcs:
                if fn in traced:
                    continue
                p = self._parents.get(fn)
                while p is not None:
                    if p in traced:
                        traced.add(fn)
                        changed = True
                        break
                    p = self._parents.get(p)
        return traced

    # ------------------------------------------------------- rule drivers
    def run(self) -> List[Finding]:
        traced = self._traced_functions()
        for fn in traced:
            self._check_traced_function(fn, traced)
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_mutable_defaults(node)
                self._check_key_reuse(node)
        if self.in_traced_dir and not self.is_config_module:
            self._check_dtype_policy()
        if self.in_swallow_scope:
            self._check_swallowed_exceptions()
        return self.findings

    # ------------------------------------------------------ STA007 driver
    def _check_swallowed_exceptions(self) -> None:
        """A broad handler must do SOMETHING with the exception: re-raise,
        log it (any ``logger``-style method, ``warnings.warn``, ``print``,
        ``traceback.print_exc``), or at least reference the bound name
        (propagating it by other means, e.g. queueing it for a consumer).
        """
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._is_broad_catch(node.type):
                continue
            if not self._handler_surfaces(node):
                caught = (
                    "bare except" if node.type is None
                    else f"except {self.aliases.resolve(node.type) or '...'}"
                )
                self._emit(
                    "STA007", node,
                    f"{caught} swallows the exception (no re-raise, no "
                    "logging, bound name unused); faults in this layer "
                    "must surface",
                )

    def _is_broad_catch(self, type_node: Optional[ast.AST]) -> bool:
        if type_node is None:
            return True  # bare except:
        types = (
            list(type_node.elts)
            if isinstance(type_node, ast.Tuple)
            else [type_node]
        )
        for t in types:
            name = self.aliases.resolve(t)
            if name and name.rsplit(".", 1)[-1] in ("Exception", "BaseException"):
                return True
        return False

    def _handler_surfaces(self, handler: ast.ExceptHandler) -> bool:
        bound = handler.name
        for n in ast.walk(handler):
            if isinstance(n, ast.Raise):
                return True
            if isinstance(n, ast.Call):
                fname = self.aliases.resolve(n.func)
                if fname in ("print", "warnings.warn", "traceback.print_exc"):
                    return True
                if (
                    isinstance(n.func, ast.Attribute)
                    and n.func.attr in _LOG_CALL_ATTRS
                ):
                    return True
            if (
                bound
                and isinstance(n, ast.Name)
                and n.id == bound
                and isinstance(n.ctx, ast.Load)
            ):
                return True
        return False

    # ------------------------------------------------ traced-context rules
    def _own_nodes(self, fn: ast.AST) -> Iterable[ast.AST]:
        """Walk ``fn``'s body without descending into nested functions
        (each traced nested function is checked on its own)."""
        stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _traced_names(self, fn) -> Set[str]:
        """Parameters + anything (transitively) assigned from them or from
        a jax call — tuple unpacking included, so ``a, b = res`` taints
        both halves."""
        names = {
            a.arg
            for a in list(fn.args.args) + list(fn.args.kwonlyargs)
            + list(fn.args.posonlyargs)
            if a.arg not in ("self", "cls")
        }

        def tainted(value: ast.AST) -> bool:
            return _contains_traced(self.aliases, value, names)

        changed = True
        while changed:
            changed = False
            for node in self._own_nodes(fn):
                targets: List[ast.AST] = []
                if isinstance(node, ast.Assign) and tainted(node.value):
                    targets = list(node.targets)
                elif (
                    isinstance(node, (ast.AnnAssign, ast.AugAssign))
                    and node.value is not None
                    and tainted(node.value)
                ):
                    targets = [node.target]
                elif isinstance(node, ast.For) and tainted(node.iter):
                    targets = [node.target]
                for tgt in targets:
                    for el in ast.walk(tgt):
                        if isinstance(el, ast.Name) and el.id not in names:
                            names.add(el.id)
                            changed = True
        return names

    def _check_traced_function(self, fn, traced: Set[ast.AST]) -> None:
        traced_names = self._traced_names(fn)

        def expr_is_traced(node: ast.AST) -> bool:
            return _contains_traced(self.aliases, node, traced_names)

        for node in self._own_nodes(fn):
            # STA001: branch whose test computes on device
            if isinstance(node, (ast.If, ast.While)):
                if self._test_computes_on_device(node.test, traced_names):
                    self._emit(
                        "STA001", node,
                        "python control flow on a traced-array expression "
                        "(retrace/concretization hazard); use jnp.where / "
                        "lax.cond",
                    )
            if isinstance(node, ast.Call):
                fname = self.aliases.resolve(node.func)
                # STA001 (bool() concretization)
                if (
                    fname == "bool"
                    and node.args
                    and expr_is_traced(node.args[0])
                ):
                    self._emit(
                        "STA001", node,
                        "bool() on a traced value concretizes the tracer",
                    )
                # STA003: float()/int() host syncs
                elif (
                    fname in ("float", "int")
                    and node.args
                    and expr_is_traced(node.args[0])
                ):
                    self._emit(
                        "STA003", node,
                        f"{fname}() on a traced value blocks on a "
                        "device->host transfer",
                    )
                # STA003: np.asarray/np.array pulls the value to host
                elif (
                    fname in ("numpy.asarray", "numpy.array")
                    and node.args
                    and expr_is_traced(node.args[0])
                ):
                    self._emit(
                        "STA003", node,
                        f"{fname.replace('numpy', 'np')}() on a traced value "
                        "is a host sync; use jnp.asarray",
                    )
                # STA002: any other numpy op fed a traced value
                elif (
                    fname
                    and fname.startswith("numpy.")
                    and fname not in ("numpy.dtype", "numpy.ndarray")
                    and any(expr_is_traced(a) for a in node.args)
                ):
                    self._emit(
                        "STA002", node,
                        f"{fname} applied to a traced value runs on host; "
                        "use the jnp equivalent",
                    )
                # STA003: .item()
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and not node.args
                ):
                    self._emit(
                        "STA003", node,
                        ".item() inside a traced context is a host sync",
                    )
                # STA008: stage-shift concatenate (the PR 7 SPMD
                # miscompile idiom: concatenate([inp[None], s[:-1]]))
                elif (
                    fname in ("jax.numpy.concatenate", "jax.lax.concatenate")
                    and node.args
                    and isinstance(node.args[0], (ast.List, ast.Tuple))
                    and self._is_stage_shift_concat(node.args[0].elts)
                ):
                    self._emit(
                        "STA008", node,
                        "concatenate of an expanded operand with a partial "
                        "slice builds a shifted array; XLA SPMD miscompiles "
                        "this under model-parallel params on a vmapped "
                        "stage dim (PR 7) — use roll-then-overwrite "
                        "(jnp.roll(...).at[0].set(...))",
                    )

    # ------------------------------------------------------ STA008 helpers
    def _is_stage_shift_concat(self, elts) -> bool:
        """True when the operand list pairs an EXPANDED array (``x[None]``
        / ``x[None, ...]`` / ``jnp.expand_dims(x, 0)``) with a PARTIAL
        slice of another (``s[:-1]`` / ``s[1:]``) — together they build a
        shifted copy, the shape XLA SPMD mis-partitions when a stage
        vmap carries model-parallel params."""

        def is_expand(e: ast.AST) -> bool:
            if isinstance(e, ast.Subscript):
                idx = e.slice
                parts = idx.elts if isinstance(idx, ast.Tuple) else [idx]
                return any(
                    isinstance(p, ast.Constant) and p.value is None
                    for p in parts
                )
            if isinstance(e, ast.Call):
                name = self.aliases.resolve(e.func)
                return bool(name) and name.rsplit(".", 1)[-1] == "expand_dims"
            return False

        def is_partial_slice(e: ast.AST) -> bool:
            if not isinstance(e, ast.Subscript):
                return False
            idx = e.slice
            parts = idx.elts if isinstance(idx, ast.Tuple) else [idx]
            return any(
                isinstance(p, ast.Slice)
                and (p.lower is not None or p.upper is not None)
                for p in parts
            )

        return any(is_expand(e) for e in elts) and any(
            is_partial_slice(e) and not is_expand(e) for e in elts
        )

    def _test_computes_on_device(self, test: ast.AST, traced_names) -> bool:
        """A branch test is device-valued when it CALLS into jax (jnp.any,
        lax reductions) or reduces a traced name via .any()/.all()/.sum()/
        .max()/.min(); bare name/attribute tests (``if mask is None``,
        ``if self.causal``) stay host-static and legal."""
        for n in ast.walk(test):
            if _is_jax_array_call(self.aliases, n):
                return True
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Attribute)
                and n.func.attr in ("any", "all", "sum", "max", "min", "mean")
                and _contains(
                    n.func.value,
                    lambda m: isinstance(m, ast.Name) and m.id in traced_names,
                )
            ):
                return True
        return False

    # ------------------------------------------------------ STA004 driver
    def _check_key_reuse(self, fn) -> None:
        """Statement-aware scan: a draw's USES evaluate before the
        statement's own ASSIGNS (``key = normal(key)`` is a reuse after a
        prior draw), and mutually exclusive if/else branches each get
        their own copy of the consumed-key state (one draw per branch is
        fine; a draw in either branch conflicts with a later one)."""
        self._scan_key_stmts(list(fn.body), {})

    def _key_expr_events(self, node: ast.AST, last_use: Dict[str, int],
                         with_assigns: bool = False) -> None:
        uses: List[Tuple[int, int, str]] = []
        assigns: List[str] = []
        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested functions scanned on their own
            if isinstance(n, ast.Call):
                name = self.aliases.resolve(n.func)
                if (
                    name
                    and name.startswith("jax.random.")
                    and name.rsplit(".", 1)[-1] in _KEY_CONSUMERS
                    and n.args
                    and isinstance(n.args[0], ast.Name)
                ):
                    uses.append((n.lineno, n.col_offset, n.args[0].id))
            targets: List[ast.AST] = []
            if with_assigns and isinstance(n, ast.Assign):
                targets = list(n.targets)
            elif with_assigns and isinstance(n, (ast.AugAssign, ast.AnnAssign)):
                targets = [n.target]
            elif isinstance(n, ast.NamedExpr):
                targets = [n.target]
            for tgt in targets:
                for el in ast.walk(tgt):
                    if isinstance(el, ast.Name):
                        assigns.append(el.id)
            stack.extend(ast.iter_child_nodes(n))
        for line, col, name in sorted(uses):
            if name in last_use:
                self._emit(
                    "STA004",
                    _Loc(line, col),
                    f"PRNG key {name!r} already consumed at line "
                    f"{last_use[name]}; split/fold_in before drawing again",
                )
            else:
                last_use[name] = line
        for name in assigns:  # RHS evaluates first: assigns clear AFTER uses
            last_use.pop(name, None)

    def _assign_targets(self, tgt: ast.AST, last_use: Dict[str, int]) -> None:
        for el in ast.walk(tgt):
            if isinstance(el, ast.Name):
                last_use.pop(el.id, None)

    def _scan_key_stmts(
        self, stmts: List[ast.AST], last_use: Dict[str, int]
    ) -> Dict[str, int]:
        for st in stmts:
            if isinstance(
                st, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            if isinstance(st, ast.If):
                self._key_expr_events(st.test, last_use)
                b1 = self._scan_key_stmts(list(st.body), dict(last_use))
                b2 = self._scan_key_stmts(list(st.orelse), dict(last_use))
                last_use = {**b1, **b2}
            elif isinstance(st, (ast.For, ast.AsyncFor)):
                self._key_expr_events(st.iter, last_use)
                self._assign_targets(st.target, last_use)
                last_use = self._scan_key_stmts(list(st.body), last_use)
                last_use = self._scan_key_stmts(list(st.orelse), last_use)
            elif isinstance(st, ast.While):
                self._key_expr_events(st.test, last_use)
                last_use = self._scan_key_stmts(list(st.body), last_use)
                last_use = self._scan_key_stmts(list(st.orelse), last_use)
            elif isinstance(st, (ast.With, ast.AsyncWith)):
                for item in st.items:
                    self._key_expr_events(item.context_expr, last_use)
                    if item.optional_vars is not None:
                        self._assign_targets(item.optional_vars, last_use)
                last_use = self._scan_key_stmts(list(st.body), last_use)
            elif isinstance(st, ast.Try):
                merged = self._scan_key_stmts(list(st.body), dict(last_use))
                for h in st.handlers:
                    merged = {
                        **merged,
                        **self._scan_key_stmts(list(h.body), dict(last_use)),
                    }
                last_use = self._scan_key_stmts(list(st.orelse), merged)
                last_use = self._scan_key_stmts(list(st.finalbody), last_use)
            else:
                self._key_expr_events(st, last_use, with_assigns=True)
        return last_use

    # ------------------------------------------------------ STA005 driver
    def _check_mutable_defaults(self, fn) -> None:
        for default in list(fn.args.defaults) + [
            d for d in fn.args.kw_defaults if d is not None
        ]:
            mutable = isinstance(default, (ast.List, ast.Dict, ast.Set)) or (
                isinstance(default, ast.Call)
                and isinstance(default.func, ast.Name)
                and default.func.id in ("list", "dict", "set", "bytearray")
            )
            if mutable:
                self._emit(
                    "STA005", default,
                    f"mutable default argument in {fn.name}(); "
                    "default to None and construct inside",
                )

    # ------------------------------------------------------ STA006 driver
    def _check_dtype_policy(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Attribute):
                name = self.aliases.resolve(node)
                if name in (
                    "jax.numpy.float16", "jax.numpy.float64",
                    "numpy.float16", "numpy.float64",
                ):
                    self._emit(
                        "STA006", node,
                        f"hardcoded {name.rsplit('.', 1)[-1]} bypasses the "
                        "configured precision policy (config.precision "
                        "decides bf16/f32)",
                    )
            elif isinstance(node, ast.Call):
                is_astype = (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "astype"
                )
                for kw in node.keywords:
                    if (
                        kw.arg == "dtype"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value in ("float16", "float64")
                    ):
                        self._emit(
                            "STA006", kw.value,
                            f"dtype string {kw.value.value!r} bypasses the "
                            "precision policy",
                        )
                if is_astype and node.args:
                    a = node.args[0]
                    if isinstance(a, ast.Constant) and a.value in (
                        "float16", "float64"
                    ):
                        self._emit(
                            "STA006", a,
                            f"astype({a.value!r}) bypasses the precision "
                            "policy",
                        )


class _Loc:
    """Synthetic location carrier for findings not tied to one node."""

    def __init__(self, lineno: int, col_offset: int):
        self.lineno = lineno
        self.col_offset = col_offset


# ------------------------------------------------------------- public API
def lint_file(path: Path, root: Optional[Path] = None) -> List[Finding]:
    root = root or Path.cwd()
    try:
        rel = str(path.resolve().relative_to(root.resolve()))
    except ValueError:
        rel = str(path)
    source = path.read_text()
    try:
        return _ModuleLint(path, rel, source).run()
    except SyntaxError as e:
        return [
            Finding("STA000", "error", rel, e.lineno or 0, e.offset or 0,
                    f"syntax error: {e.msg}")
        ]


def _stale_disables(
    files: List[Path], root: Path, findings: List[Finding]
) -> List[Finding]:
    """STA015 (disable half): every ``# sta: disable[=rules]`` comment
    must suppress at least one finding that actually fires on its line
    (restricted to the listed rules when a list is given). Emitted
    unsuppressed by design — a stale bare disable must not silence its
    own staleness finding; an explicit ``disable=STA015`` is honored
    (and marks the comment intentional)."""
    by_loc: Dict[Tuple[str, int], Set[str]] = {}
    for f in findings:
        if f.suppressed:
            by_loc.setdefault((f.path, f.line), set()).add(f.rule)
    out: List[Finding] = []
    for path in files:
        try:
            rel = str(path.resolve().relative_to(root.resolve()))
        except ValueError:
            rel = str(path)
        try:
            source = path.read_text()
        except OSError:
            continue
        for line, rules in sorted(parse_suppressions(source).items()):
            if rules is not None and "STA015" in rules:
                continue  # explicitly opted out / self-referential
            fired = by_loc.get((rel, line), set())
            live = fired if rules is None else (fired & rules)
            if live:
                continue
            listed = "" if rules is None else "=" + ",".join(sorted(rules))
            out.append(Finding(
                "STA015", RULES["STA015"][0], rel, line, 0,
                f"stale '# sta: disable{listed}': no finding fires on "
                "this line any more — remove the comment so it cannot "
                "pre-suppress the next real finding here",
                False,
            ))
    return out


def lint_paths(
    paths: Iterable[Path | str],
    root: Optional[Path] = None,
    program: bool = True,
    graph=None,
) -> List[Finding]:
    """Lint every ``.py`` under ``paths`` (files or directories).

    Runs the per-file AST rules (STA001-STA008) plus — unless
    ``program=False`` — the whole-program call-graph rules
    (STA009-STA014, concurrency.py + protocol.py) and the
    stale-suppression audit (STA015) over the same path set as one
    analysis unit. Pass ``graph`` (a prebuilt ``CallGraph`` over the
    same paths) to skip the rebuild — the CLI constructs one graph per
    run and shares it across commands. Ordering is stable:
    (path, line, col, rule)."""
    root = Path(root) if root else Path.cwd()
    # materialize once: a generator argument would be exhausted by the
    # per-file loop and silently hand check_program an EMPTY path set
    paths = [Path(p) for p in paths]
    findings: List[Finding] = []
    seen_files: List[Path] = []
    for p in paths:
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(lint_file(f, root))
            seen_files.append(f)
    if program:
        from .concurrency import check_program

        findings.extend(check_program(paths, root=root, graph=graph))
        # stale-disable audit LAST: it needs the complete finding set
        # (per-file + whole-program) to judge what a comment suppresses
        findings.extend(_stale_disables(seen_files, root, findings))
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings
