"""graftlint rule catalog: the TPU-training failure modes worth machine-checking.

Each rule is a small static pass with a narrow jurisdiction (see the class
docstrings for exactly what is and is not flagged — precision beats recall
here: a lint that cries wolf gets suppressed wholesale). The registry at the
bottom is what the CLI and the test suite enumerate.
"""
from __future__ import annotations

import ast
import fnmatch
import token
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .engine import (FileContext, Rule, Violation, dotted_name, is_literal,
                     walk_functions)


def register(cls):
    REGISTRY[cls.name] = cls
    return cls


REGISTRY: Dict[str, type] = {}


# ---------------------------------------------------------------------------
@register
class BarePrint(Rule):
    """No bare ``print(`` in library code (tokenize-based, so strings and
    docstrings mentioning print don't false-positive).

    Library output must flow through logging or the listener pipeline so it
    is routable and rate-limitable — and so bench.py's one-JSON-line stdout
    contract can't be broken by a stray debug print. CLI entry points are
    scoped out: their stdout IS the product.
    """

    name = "bare-print"
    description = ("bare print() in library code; use logging or a "
                   "listener (stdout is bench.py's JSON channel)")
    exclude = ("*/deeplearning4j_tpu/cli.py",
               "*/deeplearning4j_tpu/lint/__main__.py")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        toks = ctx.tokens
        for i, t in enumerate(toks):
            if t.type != token.NAME or t.string != "print":
                continue
            # skip attribute access (x.print) and keyword-arg (print=...)
            if i and toks[i - 1].type == token.OP and \
                    toks[i - 1].string == ".":
                continue
            nxt = next((n for n in toks[i + 1:]
                        if n.type not in (token.NL, token.NEWLINE,
                                          token.COMMENT)), None)
            if nxt is not None and nxt.type == token.OP and nxt.string == "(":
                yield self.violation(
                    ctx, t.start[0],
                    "bare print() in library code (use logging or a "
                    "listener)")


# ---------------------------------------------------------------------------
#: function names treated as hot-path (fit loops / jit dispatch seams).
#: Nested defs inherit hotness: staging closures defined inside a fit loop
#: run per batch on the producer thread.
_HOT_EXACT = frozenset({"fit", "fit_iterator", "execute_training"})
_HOT_PREFIXES = ("_fit", "_dispatch")
_HOT_SUFFIXES = ("_step",)


def _is_hot_name(name: str) -> bool:
    return (name in _HOT_EXACT
            or any(name.startswith(p) for p in _HOT_PREFIXES)
            or any(name.endswith(s) for s in _HOT_SUFFIXES))


@register
class HostSyncInHotLoop(Rule):
    """No host<->device synchronization inside fit/step/dispatch code paths.

    ``float(loss)``, ``.item()``, ``np.asarray(device_array)``,
    ``block_until_ready()`` and ``jax.device_get`` each block the host on
    the device stream: a full round-trip per call, and it serializes the
    dispatch pipeline the K-step
    and prefetch machinery exist to keep full. The ONE trusted sync point is
    ``LazyScore.score_value`` (cached, listener-driven, measured by
    telemetry); everything else in a hot path must stay device-resident.

    Host-side staging of *iterator* output (numpy in, numpy out) is the
    documented exception — suppress those lines with the reason spelling
    out why no device array can reach them.
    """

    name = "host-sync-in-hot-loop"
    description = ("host/device sync (float/.item/np.asarray/"
                   "block_until_ready) inside a fit/step/dispatch path")

    _SYNC_ATTRS = ("item", "block_until_ready")
    _SYNC_DOTTED = ("np.asarray", "numpy.asarray", "jax.device_get")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        for fn in walk_functions(tree):
            if not _is_hot_name(fn.name):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._sync_call(node)
                if msg:
                    yield self.violation(
                        ctx, node.lineno,
                        f"{msg} inside hot path {fn.name!r} — keep the hot "
                        "loop device-resident (trusted sync point: "
                        "LazyScore.score_value)")

    def _sync_call(self, call: ast.Call) -> Optional[str]:
        f = call.func
        if isinstance(f, ast.Name) and f.id == "float":
            if call.args and not is_literal(call.args[0]):
                return "float() host round-trip"
            return None
        if isinstance(f, ast.Attribute) and f.attr in self._SYNC_ATTRS \
                and not call.args:
            return f".{f.attr}() device sync"
        d = dotted_name(f)
        if d in self._SYNC_DOTTED:
            return f"{d}() host materialization"
        return None


# ---------------------------------------------------------------------------
#: name globs for functions that run under jax tracing by convention even
#: when the jit wrapping happens elsewhere (factory-returned step functions,
#: shard_map bodies). Factories themselves (make_*/_make_*) are host code.
_TRACED_NAME_GLOBS = ("*_step", "*_sharded", "*_local")
_FACTORY_PREFIXES = ("make_", "_make_")


class _TracedFunctions(ast.NodeVisitor):
    """Collect functions that (statically) run under jax tracing in a module:
    decorated with jax.jit / partial(jax.jit, ...), wrapped by name in a
    ``x = jax.jit(f, ...)`` assignment, or matching the step/shard-map
    naming convention."""

    def __init__(self, methods: Optional[Set[ast.AST]] = None):
        self.defs: Dict[str, List[ast.AST]] = {}
        self.traced: Set[ast.AST] = set()
        #: direct class-body function defs — host-side APIs like
        #: rnn_time_step, exempt from the *_step naming convention (a
        #: function nested INSIDE a method is still eligible: factory
        #: methods build trace bodies)
        self._methods = methods or set()

    @staticmethod
    def _is_jit_expr(node: ast.AST) -> bool:
        d = dotted_name(node)
        if d in ("jax.jit", "jit", "pjit", "jax.pjit"):
            return True
        if isinstance(node, ast.Call):
            # partial(jax.jit, ...) / functools.partial(jax.jit, ...)
            fd = dotted_name(node.func)
            if fd in ("functools.partial", "partial") and node.args:
                return _TracedFunctions._is_jit_expr(node.args[0])
            return _TracedFunctions._is_jit_expr(node.func)
        return False

    def visit_FunctionDef(self, node: ast.FunctionDef):
        self.defs.setdefault(node.name, []).append(node)
        if any(self._is_jit_expr(dec) for dec in node.decorator_list):
            self.traced.add(node)
        elif (node not in self._methods
              and not any(node.name.startswith(p)
                          for p in _FACTORY_PREFIXES)
              and any(fnmatch.fnmatch(node.name, g)
                      for g in _TRACED_NAME_GLOBS)):
            self.traced.add(node)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node: ast.Assign):
        # x = jax.jit(f, ...) marks the def of f (same module) as traced
        v = node.value
        if isinstance(v, ast.Call) and self._is_jit_expr(v.func) and v.args:
            inner = v.args[0]
            if isinstance(inner, ast.Name):
                for d in self.defs.get(inner.id, ()):
                    self.traced.add(d)
        self.generic_visit(node)


@register
class RecompileHazard(Rule):
    """Patterns inside jit-traced functions that cause avoidable retraces
    (or silent constant rebuilds) on TPU:

    * ``jnp.array(<python literal>)`` / ``jnp.asarray(<literal>)`` — the
      constant is re-materialized and re-staged on every trace; hoist it to
      module scope (or keep it a Python scalar and let weak types work).
    * Python ``if`` branching on trace-time shapes (``.shape`` / ``.ndim``,
      directly or through locally shape-derived names) — every distinct
      shape takes a different branch and therefore a different compile.
      Intentional shape *specialization* (static guards that raise, fixed
      chunking) is fine — suppress with the reason naming the invariant.
    * Mutable (list/dict/set) parameter defaults on a traced function —
      non-hashable under ``static_argnums`` and aliased across traces.

    Traced functions are found statically: ``@jax.jit`` (bare or through
    ``partial``), ``x = jax.jit(f)`` same-module wrapping, and the framework
    naming convention for factory-built step functions and shard_map bodies
    (``*_step``, ``*_sharded``, ``*_local``).
    """

    name = "recompile-hazard"
    description = ("trace-unstable pattern (literal jnp.array, shape "
                   "branching, mutable default) inside a jitted function")

    _ARRAY_CTORS = ("jnp.array", "jnp.asarray", "jax.numpy.array",
                    "jax.numpy.asarray")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        methods = {f for c in ast.walk(tree) if isinstance(c, ast.ClassDef)
                   for f in c.body
                   if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))}
        finder = _TracedFunctions(methods)
        finder.visit(tree)
        for fn in sorted(finder.traced, key=lambda f: f.lineno):
            yield from self._check_traced(ctx, fn)

    def _check_traced(self, ctx: FileContext, fn) -> Iterator[Violation]:
        # mutable defaults on the traced signature
        for default in list(fn.args.defaults) + \
                [d for d in fn.args.kw_defaults if d is not None]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set,
                                    ast.ListComp, ast.DictComp,
                                    ast.SetComp)) or (
                    isinstance(default, ast.Call)
                    and dotted_name(default.func) in ("list", "dict", "set")):
                yield self.violation(
                    ctx, default.lineno,
                    f"mutable default on traced function {fn.name!r} — "
                    "non-hashable under static_argnums and shared across "
                    "traces")
        tainted = self._shape_tainted(fn)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = dotted_name(node.func)
                if d in self._ARRAY_CTORS and node.args \
                        and is_literal(node.args[0]):
                    yield self.violation(
                        ctx, node.lineno,
                        f"{d}() on a Python literal inside traced "
                        f"{fn.name!r} — re-materialized every trace; hoist "
                        "to module scope")
            elif isinstance(node, ast.If):
                if self._mentions_shape(node.test, tainted):
                    yield self.violation(
                        ctx, node.lineno,
                        f"Python branch on trace-time shape inside traced "
                        f"{fn.name!r} — each distinct shape recompiles")

    @staticmethod
    def _shape_tainted(fn) -> Set[str]:
        """Names assigned (transitively) from ``.shape``/``.ndim`` inside
        the function — cheap fixpoint, function-local only."""
        tainted: Set[str] = set()

        def expr_tainted(e: ast.AST) -> bool:
            for n in ast.walk(e):
                if isinstance(n, ast.Attribute) and n.attr in ("shape",
                                                               "ndim"):
                    return True
                if isinstance(n, ast.Name) and n.id in tainted:
                    return True
            return False

        def target_names(t: ast.AST):
            # plain local names only: tainting `self` through an attribute
            # target would smear taint over every method attribute read
            if isinstance(t, ast.Name):
                yield t.id
            elif isinstance(t, (ast.Tuple, ast.List)):
                for e in t.elts:
                    yield from target_names(e)

        changed = True
        while changed:
            changed = False
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and expr_tainted(node.value):
                    for t in node.targets:
                        for name in target_names(t):
                            if name not in tainted:
                                tainted.add(name)
                                changed = True
        return tainted

    @staticmethod
    def _mentions_shape(test: ast.AST, tainted: Set[str]) -> bool:
        for n in ast.walk(test):
            if isinstance(n, ast.Attribute) and n.attr in ("shape", "ndim"):
                return True
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
        return False


# ---------------------------------------------------------------------------
@register
class DonationAlias(Rule):
    """No reuse of a buffer after passing it to a donating jit seam.

    ``donate_argnums`` lets XLA update parameters in place (no 2x-params HBM
    spike per step), at the price that the Python-side array is consumed at
    the call — later reads hit a deleted buffer (loud on TPU, silently *not*
    donated on CPU, so tests won't catch it). The safe idiom is rebinding
    the donated names from the call's results in the same statement:
    ``params, ... = step(params, ...)``.

    Donating seams are found statically in each module: ``jax.jit(f,
    donate_argnums=...)`` assignments, ``@partial(jax.jit,
    donate_argnums=...)`` decorators, and the framework's
    ``self._jit(name, fn, donate=...)`` cache (nn/multilayer.py).
    """

    name = "donation-alias"
    description = ("argument used again after being passed at a donated "
                   "position of a donating jit seam")

    @staticmethod
    def _donated_positions(kw_value: ast.AST) -> Tuple[int, ...]:
        if isinstance(kw_value, ast.Constant) and \
                isinstance(kw_value.value, int):
            return (kw_value.value,)
        if isinstance(kw_value, (ast.Tuple, ast.List)):
            out = []
            for e in kw_value.elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    out.append(e.value)
            return tuple(out)
        return ()

    def _donating_callables(self, tree: ast.Module) -> Dict[str, Tuple[int, ...]]:
        """Map local callable name -> donated positional indices."""
        seams: Dict[str, Tuple[int, ...]] = {}

        def jit_donation(call: ast.Call) -> Tuple[int, ...]:
            d = dotted_name(call.func)
            if d in ("jax.jit", "jit", "functools.partial", "partial"):
                for kw in call.keywords:
                    if kw.arg in ("donate_argnums", "donate") and kw.value:
                        return self._donated_positions(kw.value)
            return ()

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call):
                        pos = jit_donation(dec)
                        if pos:
                            seams[node.name] = pos
            elif isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Call):
                call = node.value
                pos = jit_donation(call)
                if not pos:
                    # self._jit("name", fn, donate=(0, 1, 2))
                    d = dotted_name(call.func)
                    if d is not None and d.split(".")[-1] == "_jit":
                        for kw in call.keywords:
                            if kw.arg == "donate" and kw.value is not None:
                                pos = self._donated_positions(kw.value)
                if pos:
                    for t in node.targets:
                        td = dotted_name(t)
                        if td:
                            seams[td.split(".")[-1]] = pos
        return seams

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        seams = self._donating_callables(tree)
        if not seams:
            return
        for fn in walk_functions(tree):
            yield from self._check_fn(ctx, fn, seams)

    def _check_fn(self, ctx, fn, seams) -> Iterator[Violation]:
        # statement-level walk so a donated name rebound by the call's own
        # assignment (the safe idiom) is not flagged
        calls: List[Tuple[ast.Call, str, List[str]]] = []
        for stmt in ast.walk(fn):
            if not isinstance(stmt, (ast.Assign, ast.Expr, ast.AugAssign,
                                     ast.AnnAssign, ast.Return)):
                continue
            value = getattr(stmt, "value", None)
            if not isinstance(value, ast.Call):
                continue
            callee = dotted_name(value.func)
            if callee is None:
                continue
            short = callee.split(".")[-1]
            if short not in seams:
                continue
            donated = [dotted_name(value.args[i])
                       for i in seams[short] if i < len(value.args)]
            donated = [d for d in donated if d]
            if isinstance(stmt, ast.Assign):
                bound: Set[str] = set()
                for t in stmt.targets:
                    for n in ast.walk(t):
                        d = dotted_name(n)
                        if d:
                            bound.add(d)
                donated = [d for d in donated if d not in bound]
            if donated:
                calls.append((value, fn.name, donated))
        for call, fname, donated in calls:
            end = getattr(call, "end_lineno", call.lineno)
            rebound_at: Dict[str, int] = {}
            for node in ast.walk(fn):
                if isinstance(node, ast.Assign) and node.lineno > end:
                    for t in node.targets:
                        for n in ast.walk(t):
                            d = dotted_name(n)
                            if d in donated:
                                rebound_at[d] = min(
                                    rebound_at.get(d, node.lineno),
                                    node.lineno)
            for node in ast.walk(fn):
                if isinstance(node, (ast.Name, ast.Attribute)) and \
                        isinstance(getattr(node, "ctx", None), ast.Load):
                    d = dotted_name(node)
                    if d in donated and node.lineno > end and \
                            node.lineno < rebound_at.get(d, 10 ** 9):
                        yield self.violation(
                            ctx, node.lineno,
                            f"{d!r} is read after being donated to a jit "
                            f"seam in {fname!r} — the buffer is consumed "
                            "at the call (deleted-buffer error on TPU, "
                            "silent on CPU); rebind it from the call's "
                            "results")
                        break


# ---------------------------------------------------------------------------
@register
class UnseededRng(Rule):
    """Library code must not draw from process-global RNG state.

    ``np.random.*`` module functions and stdlib ``random.*`` share hidden
    global state: results depend on import order and thread timing, which
    breaks the prefetch-on/off bit-identical-params guarantee and makes
    multi-host runs diverge. Use ``np.random.default_rng(seed)`` (seeded!)
    or JAX PRNG keys. ``default_rng()`` / ``RandomState()`` with no seed is
    flagged too — a fresh OS-entropy generator is still nondeterministic.
    """

    name = "unseeded-rng"
    description = ("global/unseeded RNG (np.random.* module call or stdlib "
                   "random.*) in library code")

    _NP_CONSTRUCTORS = frozenset({"default_rng", "RandomState", "Generator",
                                  "SeedSequence", "PCG64", "PCG64DXSM",
                                  "Philox", "MT19937", "SFC64",
                                  "BitGenerator"})
    _PY_CONSTRUCTORS = frozenset({"Random", "SystemRandom"})

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        rand_aliases, from_random = self._random_bindings(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            d = dotted_name(node.func)
            if d is None:
                continue
            parts = d.split(".")
            if len(parts) >= 3 and parts[-2] == "random" and \
                    parts[0] in ("np", "numpy", "jax"):
                if parts[0] == "jax":
                    continue  # jax.random.* is explicit-key by construction
                last = parts[-1]
                if last not in self._NP_CONSTRUCTORS:
                    yield self.violation(
                        ctx, node.lineno,
                        f"{d}() draws from numpy's global RNG — use a "
                        "seeded np.random.default_rng(seed) Generator or a "
                        "JAX PRNG key")
                elif last in ("default_rng", "RandomState") and \
                        not node.args:
                    yield self.violation(
                        ctx, node.lineno,
                        f"{d}() with no seed — OS-entropy generator breaks "
                        "run-to-run determinism; thread a seed through")
            elif len(parts) == 2 and parts[0] in rand_aliases:
                last = parts[-1]
                if last not in self._PY_CONSTRUCTORS:
                    yield self.violation(
                        ctx, node.lineno,
                        f"stdlib {d}() uses hidden global RNG state — use "
                        "random.Random(seed) or a numpy Generator")
            elif len(parts) == 1 and parts[0] in from_random:
                yield self.violation(
                    ctx, node.lineno,
                    f"{parts[0]}() (imported from stdlib random) uses "
                    "hidden global RNG state — use random.Random(seed)")

    @staticmethod
    def _random_bindings(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
        aliases: Set[str] = set()
        names: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "random":
                        aliases.add(a.asname or "random")
            elif isinstance(node, ast.ImportFrom) and node.module == \
                    "random" and node.level == 0:
                for a in node.names:
                    if a.name not in UnseededRng._PY_CONSTRUCTORS:
                        names.add(a.asname or a.name)
        return aliases, names


# ---------------------------------------------------------------------------
@register
class MetricNameDrift(Rule):
    """Telemetry metric names are API: dashboards, the /metrics scraper and
    bench.py's log reinterpretation all key on them. Every name must (a)
    carry the ``dl4j_`` namespace prefix and (b) live as a constant in
    ``observability/names.py`` — registry call sites import the constant,
    so a rename is one diff line and grep-able, and two subsystems can't
    silently claim the same string with different meanings.

    Flagged at ``<receiver>.counter|gauge|histogram(<name>, ...)`` call
    sites: string literals (hardcoded name — import the constant instead),
    constants imported from the names module that the module doesn't define
    (stale import), and — inside names.py itself — constant values missing
    the ``dl4j_`` prefix. Receivers named np/numpy/jnp are ignored
    (``np.histogram`` is not a metrics registry), as are first arguments
    whose provenance the linter can't see (plain locals); the names-module
    import is the reviewable idiom.
    """

    name = "metric-name-drift"
    description = ("metric name not a dl4j_-prefixed constant imported "
                   "from observability/names.py")

    _METHODS = ("counter", "gauge", "histogram")
    _SKIP_RECEIVERS = frozenset({"np", "numpy", "jnp", "scipy", "cv2"})
    _NAMES_GLOB = "*/observability/names.py"

    def __init__(self, names: Optional[Dict[str, str]] = None):
        #: constant name -> metric string, parsed from the names module
        self._names = names
        self._names_found = names is not None

    # ------------------------------------------------------------- prepare
    def prepare(self, ctxs: Sequence[FileContext]) -> None:
        if self._names_found:
            return
        for ctx in ctxs:
            if fnmatch.fnmatch(ctx.path.as_posix(), self._NAMES_GLOB):
                self._names = self._parse_names(ctx)
                self._names_found = True
                return

    @staticmethod
    def _parse_names(ctx: FileContext) -> Dict[str, str]:
        out: Dict[str, str] = {}
        tree = ctx.tree
        if tree is None:
            return out
        for node in tree.body:
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Constant) and \
                    isinstance(node.value.value, str):
                for t in node.targets:
                    if isinstance(t, ast.Name) and not t.id.startswith("_"):
                        out[t.id] = node.value.value
        return out

    # --------------------------------------------------------------- check
    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        if fnmatch.fnmatch(ctx.path.as_posix(), self._NAMES_GLOB):
            yield from self._check_names_module(ctx, tree)
            return
        imported, module_aliases = self._names_imports(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or \
                    not isinstance(node.func, ast.Attribute):
                continue
            if node.func.attr not in self._METHODS or not node.args:
                continue
            recv = dotted_name(node.func.value)
            if recv is not None and \
                    recv.split(".")[0] in self._SKIP_RECEIVERS:
                continue
            arg0 = node.args[0]
            if isinstance(arg0, ast.Constant) and \
                    isinstance(arg0.value, str):
                yield from self._check_literal(ctx, node, arg0.value)
            elif isinstance(arg0, ast.Name) and arg0.id in imported:
                orig = imported[arg0.id]
                if self._names is not None and orig not in self._names:
                    yield self.violation(
                        ctx, node.lineno,
                        f"metric constant {orig!r} is imported from "
                        "observability.names but not defined there")
            elif isinstance(arg0, ast.Attribute):
                d = dotted_name(arg0.value)
                if d in module_aliases and self._names is not None and \
                        arg0.attr not in self._names:
                    yield self.violation(
                        ctx, node.lineno,
                        f"metric constant names.{arg0.attr} is not defined "
                        "in observability/names.py")

    def _check_literal(self, ctx, node, value: str) -> Iterator[Violation]:
        if not value.startswith("dl4j_"):
            yield self.violation(
                ctx, node.lineno,
                f"metric name {value!r} lacks the dl4j_ namespace prefix "
                "(/metrics stability contract)")
            return
        hint = ""
        if self._names is not None:
            const = next((k for k, v in self._names.items() if v == value),
                         None)
            hint = (f" (import {const} from observability.names)"
                    if const else " (register it in observability/names.py "
                    "first)")
        yield self.violation(
            ctx, node.lineno,
            f"hardcoded metric name {value!r} at a registry call site — "
            f"use the central constant{hint}")

    def _check_names_module(self, ctx, tree) -> Iterator[Violation]:
        for const, value in self._parse_names(ctx).items():
            if not value.startswith("dl4j_"):
                node_line = next(
                    (n.lineno for n in tree.body
                     if isinstance(n, ast.Assign) and any(
                         isinstance(t, ast.Name) and t.id == const
                         for t in n.targets)), 1)
                yield self.violation(
                    ctx, node_line,
                    f"registered metric {const} = {value!r} lacks the "
                    "dl4j_ namespace prefix")

    @staticmethod
    def _names_imports(tree: ast.Module) -> Tuple[Dict[str, str], Set[str]]:
        """(local alias -> original constant name imported from the names
        module, local aliases bound to the names module itself)."""
        consts: Dict[str, str] = {}
        mods: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "names" or mod.endswith(".names") or \
                        mod.endswith("observability.names") or \
                        (node.level > 0 and mod == "names"):
                    for a in node.names:
                        consts[a.asname or a.name] = a.name
                elif mod.endswith("observability") or mod == "observability":
                    for a in node.names:
                        if a.name == "names":
                            mods.add(a.asname or "names")
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.endswith("observability.names"):
                        mods.add(a.asname or a.name)
        return consts, mods


# ---------------------------------------------------------------------------
@register
class SwallowedException(Rule):
    """No silently-swallowed exceptions in library code.

    A bare ``except:`` catches KeyboardInterrupt/SystemExit and hides real
    bugs; an ``except X: pass`` with no logging erases the only evidence a
    fit/dispatch loop leaves when it mis-steps. Handlers that genuinely
    must stay silent (``__del__`` close guards, optional-API probes)
    document themselves with a suppression reason — which is the point.
    """

    name = "swallowed-exception"
    description = ("bare except, or handler whose entire body is `pass` "
                   "(exception evidence destroyed)")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                yield self.violation(
                    ctx, node.lineno,
                    "bare `except:` also catches KeyboardInterrupt/"
                    "SystemExit — name the exception type")
                continue
            if len(node.body) == 1 and isinstance(node.body[0], ast.Pass):
                yield self.violation(
                    ctx, node.lineno,
                    "exception swallowed with `pass` — log it (debug level "
                    "is fine) or suppress with the reason it must stay "
                    "silent")


# ---------------------------------------------------------------------------
@register
class AdhocSharding(Rule):
    """No ``NamedSharding(`` / ``PartitionSpec(`` / ``Mesh(`` construction
    outside the partition-rule engine (``parallel/partition.py`` +
    ``compile_seam.py``; ``Mesh`` additionally allows ``parallel/mesh.py``,
    its one constructor site).

    Hand-built shardings are how the framework ended up with four parallel
    fit paths that each wired their own layouts — and where the layout lives
    determines where it can be fixed. The engine is the one place layout
    decisions are made (rules -> specs), telemetered
    (``dl4j_sharding_spec_total``), and compile-tracked; call sites import
    ``partition.pspec`` for trace-level specs and
    ``partition.named_sharding``/``tree_shardings``/``device_put`` for
    placement, and build meshes through ``parallel.mesh.build_mesh``. That
    jurisdiction covers the serving tier too: a ReplicaSet's per-replica
    mesh slices and every sharded ``PredictFn`` pin route through the same
    engine as the fit paths. Jurisdiction: direct calls to the
    ``jax.sharding`` constructors (by from-import, alias, or dotted
    attribute). A staging path with a genuine reason to hand-place
    (datasets/prefetch producer threads) suppresses with that reason
    spelled out.
    """

    name = "adhoc-sharding"
    description = ("NamedSharding/PartitionSpec/Mesh constructed outside "
                   "parallel/partition.py + compile_seam.py + mesh.py (use "
                   "partition.pspec / partition.named_sharding / "
                   "mesh.build_mesh)")
    exclude = ("*/parallel/partition.py", "*/parallel/compile_seam.py")

    _CTORS = ("NamedSharding", "PartitionSpec", "Mesh")
    #: Mesh's one legitimate constructor site — NamedSharding/PartitionSpec
    #: stay forbidden there, so it is a per-ctor exclusion, not `exclude`
    _MESH_HOME = "parallel/mesh.py"
    _ORIGIN = "jax.sharding"

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        # local names bound to the jax.sharding constructors by from-import
        # (incl. aliases like `PartitionSpec as P`), and module aliases that
        # can reach them as attributes (import jax / import jax.sharding)
        ctor_names: Dict[str, str] = {}
        mod_aliases: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module == self._ORIGIN:
                for a in node.names:
                    if a.name in self._CTORS:
                        ctor_names[a.asname or a.name] = a.name
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in ("jax", "jax.sharding"):
                        mod_aliases.add((a.asname or a.name).split(".")[0])
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            kind = None
            if isinstance(f, ast.Name) and f.id in ctor_names:
                kind = ctor_names[f.id]
            else:
                d = dotted_name(f)
                if d and "." in d:
                    head, leaf = d.split(".", 1)[0], d.rsplit(".", 1)[-1]
                    if leaf in self._CTORS and head in mod_aliases:
                        kind = leaf
            if kind == "Mesh" and str(ctx.path).replace(
                    "\\", "/").endswith(self._MESH_HOME):
                continue
            if kind:
                yield self.violation(
                    ctx, node.lineno,
                    f"ad-hoc {kind}() construction — layouts come from the "
                    "partition-rule engine (partition.pspec / "
                    "partition.named_sharding / mesh.build_mesh / "
                    "compile_seam.compile_step)")


# ---------------------------------------------------------------------------
@register
class AdhocJit(Rule):
    """No ``jax.jit(`` / ``pjit(`` outside the compile seams
    (``LazyScore._jit`` in ``nn/multilayer.py``,
    ``parallel/compile_seam.py``, ``nn/compile_cache.py``).

    A raw jit call site is a program the compile plane can't see: it is
    not policy-keyed (a dtype flip silently pins the first policy), not
    compile-tracked (storm detection and MFU go blind), and not warm-
    startable (the persistent executable cache never learns about it — a
    respawn or hot swap recompiles it from scratch every time). The seams
    exist so every program inherits all three. Call sites route through
    ``net._jit`` / ``compile_seam.compile_step`` /
    ``compile_cache.build_program``; a site with a genuine reason to stay
    raw (float64 gradient checks outside every policy) suppresses with
    that reason spelled out. Jurisdiction: direct calls by from-import,
    alias, or dotted attribute.
    """

    name = "adhoc-jit"
    description = ("jax.jit/pjit called outside nn/multilayer.py "
                   "(LazyScore._jit) + parallel/compile_seam.py + "
                   "nn/compile_cache.py (use net._jit / "
                   "compile_seam.compile_step / "
                   "compile_cache.build_program)")
    exclude = ("*/nn/multilayer.py", "*/parallel/compile_seam.py",
               "*/nn/compile_cache.py")

    _CTORS = ("jit", "pjit")
    _ORIGINS = ("jax", "jax.experimental.pjit")

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        # local names bound by from-import (incl. aliases), and module
        # aliases that can reach jit/pjit as attributes
        ctor_names: Dict[str, str] = {}
        mod_aliases: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) \
                    and node.module in self._ORIGINS:
                for a in node.names:
                    if a.name in self._CTORS:
                        ctor_names[a.asname or a.name] = a.name
            elif isinstance(node, ast.Import):
                for a in node.names:
                    if a.name in ("jax", "jax.experimental",
                                  "jax.experimental.pjit"):
                        mod_aliases.add((a.asname or a.name).split(".")[0])
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            kind = None
            if isinstance(f, ast.Name) and f.id in ctor_names:
                kind = ctor_names[f.id]
            else:
                d = dotted_name(f)
                if d and "." in d:
                    head, leaf = d.split(".", 1)[0], d.rsplit(".", 1)[-1]
                    if leaf in self._CTORS and head in mod_aliases:
                        kind = leaf
            if kind:
                yield self.violation(
                    ctx, node.lineno,
                    f"ad-hoc {kind}() — programs compile through the seams "
                    "(net._jit / compile_seam.compile_step / "
                    "compile_cache.build_program) so they are policy-keyed, "
                    "compile-tracked and warm-startable")


# ---------------------------------------------------------------------------
@register
class HotPathCopy(Rule):
    """No full-buffer copies on the host data plane.

    The wire codec and the shm transport exist so tensor bytes move as
    memoryviews (``sendmsg`` scatter-gather, seqlock slot reads) — a single
    ``.tobytes()`` or ``np.frombuffer(...).copy()`` on those paths silently
    re-introduces the per-batch memcpy the whole plane was built to remove,
    and it never shows up in ``dl4j_wire_copy_bytes_total`` because it
    happens outside the billed fallbacks. Jurisdiction is the data plane
    only: ``streaming/`` and ``parallel/ps_*``. Copies that are genuinely
    required (a pull-slot vector that outlives the slot's reuse window)
    suppress with the lifetime reason spelled out.
    """

    name = "hot-path-copy"
    description = ("`.tobytes()` or `np.frombuffer(...).copy()` on the host "
                   "data plane (streaming/ + parallel/ps_*) — keep tensor "
                   "bytes as memoryviews")

    _JURISDICTION = ("*/streaming/*.py", "*/parallel/ps_*.py")

    def _in_jurisdiction(self, ctx: FileContext) -> bool:
        paths = (ctx.rel, ctx.path.as_posix())
        return any(fnmatch.fnmatch(p, pat)
                   for p in paths for pat in self._JURISDICTION)

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None or not self._in_jurisdiction(ctx):
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or node.args or node.keywords:
                continue
            f = node.func
            if not isinstance(f, ast.Attribute):
                continue
            if f.attr == "tobytes":
                yield self.violation(
                    ctx, node.lineno,
                    ".tobytes() materialises a full copy — pass the "
                    "memoryview (wire._byteview / pack_arrays) instead")
            elif f.attr == "copy":
                # only the precise np.frombuffer(...).copy() shape: copying
                # a freshly-decoded view is the canonical accidental memcpy
                v = f.value
                if (isinstance(v, ast.Call)
                        and (dotted_name(v.func) or "").endswith("frombuffer")):
                    yield self.violation(
                        ctx, node.lineno,
                        "np.frombuffer(...).copy() defeats the zero-copy "
                        "decode — keep the view, or suppress with the "
                        "lifetime reason the copy is required")


# ---------------------------------------------------------------------------
@register
class DenseKvAlloc(Rule):
    """No raw dense KV allocation outside the page allocator.

    The paged memory plane works only if ``keras_server/paging.py`` is the
    ONE place that sizes decode KV memory: a stray
    ``jnp.zeros(..., max_context, ...)`` anywhere else in ``keras_server/``
    silently re-introduces the per-slot dense preallocation the plane
    deleted — it compiles, it is bitwise-correct, and it quietly halves the
    session count per byte. Jurisdiction is ``keras_server/`` only (training
    code allocates sequence-length buffers legitimately); the allocator
    module itself is scoped out. Host scheduling arrays (``np.zeros`` with
    no context dimension) are not flagged.
    """

    name = "dense-kv-alloc"
    description = ("jnp.zeros sized by max_context under keras_server/ — "
                   "decode KV memory is allocated ONLY by "
                   "keras_server/paging.py (alloc_dense_kv / "
                   "alloc_page_pool)")
    exclude = ("*/keras_server/paging.py",)

    _JURISDICTION = ("*/keras_server/*.py",)

    def _in_jurisdiction(self, ctx: FileContext) -> bool:
        paths = (ctx.rel, ctx.path.as_posix())
        return any(fnmatch.fnmatch(p, pat)
                   for p in paths for pat in self._JURISDICTION)

    @staticmethod
    def _mentions_max_context(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and sub.id == "max_context":
                return True
            if isinstance(sub, ast.Attribute) and sub.attr == "max_context":
                return True
        return False

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None or not self._in_jurisdiction(ctx):
            return
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            if not name.endswith(".zeros") or name.startswith("np."):
                continue
            if any(self._mentions_max_context(a)
                   for a in list(node.args)
                   + [kw.value for kw in node.keywords]):
                yield self.violation(
                    ctx, node.lineno,
                    "raw dense KV alloc (jnp.zeros sized by max_context) — "
                    "route through keras_server/paging.py so the paged "
                    "plane stays the only decode memory owner")


# ---------------------------------------------------------------------------
@register
class OrphanSpan(Rule):
    """Every manually-started trace span must be finishable on ALL exits.

    ``start_span()`` (observability/tracing.py) exists for cross-thread
    spans whose owner finishes them later — which is exactly how spans
    leak: a local span finished only on the happy path pins its whole
    trace in the store's live table until the leak guard evicts it, and
    the trace is lost. Jurisdiction is the request-path packages
    (``keras_server/``, ``nn/``, ``observability/``); the rule flags:

    - a BARE ``start_span(...)`` statement, or a method chain on it not
      ending in ``.finish()`` — the span is unreachable forever (chain
      ``.finish()`` for an instant span);
    - ``sp = start_span(...)`` into a plain local where ``sp.finish()``
      never appears inside a ``finally`` block of the same function and
      ``sp`` is not returned — a conditional/early-exit path leaks it;
    - a flight-recorder ``record("span_enter", ...)`` with no
      ``record("span_exit", ...)`` anywhere in the same function — the
      pairing ``span()`` guarantees would silently break in crash bundles.

    Assigning to an attribute (``req.span = start_span(...)``) is exempt:
    ownership escapes to the object and its lifecycle (the batcher's
    dispatcher, the decode pump's evict path) finishes it. ``with
    start_span(...)`` is exempt (``__exit__`` finishes). ``tracing.py``
    (the factory) and ``spans.py`` (the pairing owner) are scoped out.
    """

    name = "orphan-span"
    description = ("start_span()/span_enter without a guaranteed "
                   "finish/span_exit on all exits (leaked trace span)")
    exclude = ("*/observability/tracing.py",
               "*/observability/spans.py")

    _JURISDICTION = ("*/keras_server/*.py", "*/nn/*.py",
                     "*/observability/*.py")

    def _in_jurisdiction(self, ctx: FileContext) -> bool:
        paths = (ctx.rel, ctx.path.as_posix())
        return any(fnmatch.fnmatch(p, pat)
                   for p in paths for pat in self._JURISDICTION)

    @staticmethod
    def _own_nodes(fn: ast.AST) -> Iterator[ast.AST]:
        """Nodes of ``fn`` excluding nested function bodies (a closure's
        spans are the closure's problem)."""
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            yield node
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                stack.extend(ast.iter_child_nodes(node))

    @staticmethod
    def _chain_root_tail(call: ast.Call) -> Tuple[ast.Call, Optional[str]]:
        """For ``start_span(...).set_status(...).finish()`` return the
        innermost call and the OUTERMOST chained method name (None when
        the call is unchained)."""
        tail: Optional[str] = None
        node = call
        while isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Call):
            if tail is None:
                tail = node.func.attr
            node = node.func.value
        return node, tail

    @staticmethod
    def _is_start_span(call: ast.Call) -> bool:
        name = dotted_name(call.func) or ""
        return name == "start_span" or name.endswith(".start_span")

    @staticmethod
    def _record_event(call: ast.Call) -> Optional[str]:
        name = dotted_name(call.func) or ""
        if not (name == "record" or name.endswith(".record")):
            return None
        if call.args and isinstance(call.args[0], ast.Constant) \
                and isinstance(call.args[0].value, str):
            return call.args[0].value
        return None

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None or not self._in_jurisdiction(ctx):
            return
        for fn in walk_functions(tree):
            nodes = list(self._own_nodes(fn))
            with_exprs = set()
            for node in nodes:
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    for item in node.items:
                        with_exprs.add(id(item.context_expr))
            finished_in_finally: Set[str] = set()
            for node in nodes:
                if not isinstance(node, ast.Try):
                    continue
                for stmt in node.finalbody:
                    for sub in ast.walk(stmt):
                        if isinstance(sub, ast.Call) \
                                and isinstance(sub.func, ast.Attribute) \
                                and sub.func.attr == "finish" \
                                and isinstance(sub.func.value, ast.Name):
                            finished_in_finally.add(sub.func.value.id)
            returned: Set[str] = {
                node.value.id for node in nodes
                if isinstance(node, ast.Return)
                and isinstance(node.value, ast.Name)}
            enter_lines: List[int] = []
            has_exit = False
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                ev = self._record_event(node)
                if ev == "span_enter":
                    enter_lines.append(node.lineno)
                elif ev == "span_exit":
                    has_exit = True
                if not self._is_start_span(node) or id(node) in with_exprs:
                    continue
                sink = self._span_sink(nodes, node)
                if sink in ("attribute", "escapes", "finish-chain"):
                    continue
                if sink is None:
                    yield self.violation(
                        ctx, node.lineno,
                        "start_span() result discarded — the span can "
                        "never finish; chain .finish() or own it on an "
                        "object/local")
                    continue
                if sink in finished_in_finally or sink in returned:
                    continue
                yield self.violation(
                    ctx, node.lineno,
                    f"span {sink!r} from start_span() has no "
                    f"{sink}.finish() in a finally block (and is not "
                    "returned) — an exception path leaks the trace")
            for line in enter_lines if not has_exit else ():
                yield self.violation(
                    ctx, line,
                    'record("span_enter") without a matching '
                    'record("span_exit") in this function — the flight-'
                    "recorder span timeline would dangle")

    @staticmethod
    def _span_sink(nodes: List[ast.AST],
                   call: ast.Call) -> Optional[str]:
        """Where the span value lands: a local name, ``'attribute'`` /
        ``'escapes'`` for exempt sinks, ``'finish-chain'`` when a method
        chain on the call ends in ``.finish()``, None when discarded.
        Assignment/return sinks win over intermediate chain calls (the
        node list is unordered DFS output)."""
        for node in nodes:
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                v = node.value
                root = OrphanSpan._chain_root_tail(v)[0] \
                    if isinstance(v, ast.Call) else None
                if v is call or root is call:
                    t = node.targets[0]
                    return t.id if isinstance(t, ast.Name) else "attribute"
            if isinstance(node, ast.Return) and node.value is call:
                return "escapes"
        for node in nodes:
            if isinstance(node, ast.Call) and node is not call:
                inner, tail = OrphanSpan._chain_root_tail(node)
                if inner is call and tail == "finish":
                    # the OUTERMOST chained call reports the final method;
                    # any chain ending .finish() lands here
                    return "finish-chain"
        return None


# ---------------------------------------------------------------------------
@register
class ReplicaLifecycle(Rule):
    """Replica lifecycle mutations only through the ReplicaSet public API.

    The autoscaling fleet's invariants — a replica is routable only after
    its warmup completes, removal drains without loss, indices are never
    reused, the fleet gauge and scale-event counters stay truthful, leases
    are registered/deregistered in step — all live inside
    ``ReplicaSet.add_replica()`` / ``remove_replica()`` /
    ``register()``. Direct surgery on ``ReplicaSet._replicas`` from
    anywhere else (an append, a ``del``, even a read that is then
    mutated) silently bypasses every one of them: the router can see a
    cold replica, a drain can be skipped, a zombie's lease outlives its
    process. Any ``._replicas`` attribute access outside ``replica.py``
    is flagged — readers have the ``replicas`` property and
    ``n_replicas``; mutators have the lifecycle API.
    """

    name = "replica-lifecycle"
    description = ("direct ReplicaSet._replicas access outside "
                   "keras_server/replica.py — use the replicas property "
                   "to read and add_replica()/remove_replica() to mutate")
    exclude = ("*/keras_server/replica.py",)

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and node.attr == "_replicas":
                yield self.violation(
                    ctx, node.lineno,
                    "ReplicaSet._replicas touched outside replica.py — "
                    "read via the replicas property, mutate via "
                    "add_replica()/remove_replica() so warmup-before-"
                    "routable, drain-without-loss and lease accounting "
                    "hold")


# ---------------------------------------------------------------------------
@register
class FleetTruth(Rule):
    """A ``/fleet/*`` surface must serve the FEDERATED view, never a
    process-local registry read dressed up as fleet-wide truth.

    The whole point of observability/federation.py is that every other
    process's counters are invisible to a local ``MetricsRegistry``;
    handing ``global_registry().snapshot()`` (or ``.prometheus_text()``)
    to a fleet route silently reports one process as if it were the
    fleet — totals look plausible and are wrong, which is worse than
    absent. Flagged are local-registry ``snapshot()``/``prometheus_text()``
    calls in fleet scope: inside a function whose name contains ``fleet``,
    or inside an ``if``/``elif`` branch whose test compares against a
    string starting with ``/fleet`` (the route-dispatcher shape). The
    local ``/metrics`` branch of the same dispatcher stays legal.
    ``observability/federation.py`` is scoped out — it is the one module
    allowed to fold the local registry into the merged view (labeled).
    """

    name = "fleet-truth"
    description = ("process-local registry snapshot()/prometheus_text() "
                   "served from a /fleet surface — merge through "
                   "observability/federation.py instead")
    exclude = ("*/observability/federation.py",)

    _READS = ("snapshot", "prometheus_text")

    @staticmethod
    def _is_local_registry_read(call: ast.Call) -> bool:
        if not isinstance(call.func, ast.Attribute) \
                or call.func.attr not in FleetTruth._READS:
            return False
        base = call.func.value
        if isinstance(base, ast.Call):
            name = dotted_name(base.func) or ""
            return name == "global_registry" \
                or name.endswith(".global_registry")
        name = (dotted_name(base) or "").lower()
        leaf = name.rsplit(".", 1)[-1]
        # a receiver that names the federation is the fix, not the bug
        return "registry" in leaf and "fed" not in name \
            and "fleet" not in name

    @staticmethod
    def _mentions_fleet_route(node: ast.AST) -> bool:
        return any(isinstance(n, ast.Constant)
                   and isinstance(n.value, str)
                   and n.value.startswith("/fleet")
                   for n in ast.walk(node))

    def check(self, ctx: FileContext) -> Iterator[Violation]:
        tree = ctx.tree
        if tree is None:
            return
        flagged = set()

        def flag(scope_nodes, why):
            for node in scope_nodes:
                for call in ast.walk(node):
                    if isinstance(call, ast.Call) \
                            and self._is_local_registry_read(call) \
                            and call.lineno not in flagged:
                        flagged.add(call.lineno)
                        yield self.violation(
                            ctx, call.lineno,
                            f"process-local registry "
                            f".{call.func.attr}() {why} — one process's "
                            "series served as fleet truth; go through "
                            "FederatedRegistry / fleet_metrics_text() "
                            "(observability/federation.py)")

        for fn in walk_functions(tree):
            if "fleet" in fn.name.lower():
                yield from flag(fn.body,
                                f"inside fleet-scoped {fn.name}()")
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.If) \
                        and self._mentions_fleet_route(node.test):
                    yield from flag(node.body,
                                    "inside a /fleet route branch")


def default_rules() -> List[Rule]:
    """Fresh instances of every registered rule, in registration order."""
    return [cls() for cls in REGISTRY.values()]


def rule_names() -> List[str]:
    return list(REGISTRY)
