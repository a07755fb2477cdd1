"""simlint rule set — simulator/JAX-specific hazard classes.

Each rule targets a failure mode this codebase has actually been bitten
by (or is one hand-audit away from): nondeterministic RNG, global x64
toggles, Python control flow on traced values, unordered iteration
feeding simulation state, in-place mutation of frozen trace columns,
``assert``-guarded accounting that ``python -O`` strips, unit-suffix
mix-ups, undocumented engine accuracy contracts, shared mutable
defaults, swallowed exceptions, and per-instance-leaking method caches.

Rules are intentionally syntactic and conservative: they flag the
*pattern*, and an inline ``# simlint: disable=SLxxx`` records a reviewed
exemption.  See :mod:`repro.analysis.engine` for the engine and
:mod:`tests.test_analysis` for one known-bad snippet per rule.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator

from .engine import Finding, ModuleContext, Rule

# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression (``jax.lax.scan`` ->
    ``"jax.lax.scan"``; non-name parts collapse to ``""``)."""

    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    return ""


def _walk_no_lambda(node: ast.AST) -> Iterator[ast.AST]:
    """``ast.walk`` that does not descend into nested lambdas (their
    params shadow the enclosing traced params)."""

    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if not isinstance(child, ast.Lambda):
                stack.append(child)


# ---------------------------------------------------------------------------
# SL101 — unseeded legacy numpy RNG
# ---------------------------------------------------------------------------


class UnseededRandomRule(Rule):
    id = "SL101"
    name = "unseeded-random"
    description = (
        "legacy np.random.* module-level calls draw from hidden global "
        "state; traces stop being a pure function of their seed. Use "
        "np.random.default_rng(seed)."
    )

    _ALLOWED = frozenset({
        "default_rng", "SeedSequence", "Generator", "BitGenerator",
        "PCG64", "PCG64DXSM", "Philox", "SFC64", "MT19937",
    })

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            func = node.func
            if func.attr in self._ALLOWED:
                continue
            if _dotted(func.value) in ("np.random", "numpy.random"):
                f = ctx.finding(
                    self, node,
                    f"np.random.{func.attr}() uses the hidden global RNG; "
                    "draw from np.random.default_rng(seed) instead",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL102 — 64-bit mode outside the one scoped helper
# ---------------------------------------------------------------------------


class UnscopedX64Rule(Rule):
    id = "SL102"
    name = "unscoped-x64"
    description = (
        "global jax_enable_x64 toggles leak float64 into every caller "
        "and invalidate jit caches; open 64-bit mode only as "
        "`with repro.runtime.x64():`, the one helper built on "
        "jax.enable_x64(True)."
    )

    _HELPER_MODULE = "runtime.py"
    _USE = " — use `with repro.runtime.x64():`"

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        with_items = {
            item.context_expr
            for node in ast.walk(ctx.tree) if isinstance(node, ast.With)
            for item in node.items
        }
        in_helper = ctx.rel.split("/")[-1] == self._HELPER_MODULE
        for node in ast.walk(ctx.tree):
            message = None
            if isinstance(node, ast.Call):
                callee = _dotted(node.func)
                last = callee.split(".")[-1]
                if callee.endswith("config.update") and node.args:
                    arg0 = node.args[0]
                    if (isinstance(arg0, ast.Constant)
                            and arg0.value == "jax_enable_x64"):
                        message = ("global jax.config.update("
                                   "'jax_enable_x64', ...)")
                elif last in ("enable_x64", "x64") and node not in with_items:
                    message = (f"{last}() called outside a `with` "
                               "statement — the toggle never scopes back")
                elif last == "enable_x64" and not in_helper:
                    message = "jax.enable_x64() outside the x64 helper"
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target]
                )
                if any(isinstance(t, ast.Attribute)
                       and t.attr == "jax_enable_x64" for t in targets):
                    message = "direct assignment to jax_enable_x64"
            if message:
                f = ctx.finding(self, node, message + self._USE)
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL103 — Python branches on traced values inside jit/scan/vmap bodies
# ---------------------------------------------------------------------------


_JIT_WRAPPERS = frozenset({
    "jit", "vmap", "pmap", "jax.jit", "jax.vmap", "jax.pmap",
    "checkify.checkify",
})
_SCAN_CALLS = frozenset({
    "scan", "lax.scan", "jax.lax.scan",
    "fori_loop", "lax.fori_loop", "jax.lax.fori_loop",
    "while_loop", "lax.while_loop", "jax.lax.while_loop",
})


class TracedBranchRule(Rule):
    id = "SL103"
    name = "traced-branch"
    description = (
        "Python if/while on a traced value inside a jit/scan/vmap body "
        "raises (or silently specializes) at trace time; use jnp.where / "
        "lax.cond / lax.select."
    )

    def _static_params(self, call: ast.Call, fn: ast.FunctionDef) -> set[str]:
        """Params named static via static_argnums/static_argnames on a
        ``partial(jax.jit, ...)``-style wrapper call."""

        static: set[str] = set()
        names = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for el in ast.walk(kw.value):
                    if isinstance(el, ast.Constant) and isinstance(el.value, str):
                        static.add(el.value)
            elif kw.arg == "static_argnums":
                for el in ast.walk(kw.value):
                    if (isinstance(el, ast.Constant)
                            and isinstance(el.value, int)
                            and 0 <= el.value < len(names)):
                        static.add(names[el.value])
        return static

    def _traced_functions(
        self, ctx: ModuleContext
    ) -> Iterator[tuple[ast.AST, set[str]]]:
        """(function node, traced param names) for every function that is
        jitted/vmapped (decorator) or passed to jit/vmap/scan (call)."""

        defs: dict[str, ast.FunctionDef] = {
            n.name: n for n in ast.walk(ctx.tree)
            if isinstance(n, ast.FunctionDef)
        }

        def params(fn: ast.FunctionDef | ast.Lambda) -> set[str]:
            a = fn.args
            return {
                x.arg
                for x in a.posonlyargs + a.args + a.kwonlyargs
                + ([a.vararg] if a.vararg else [])
                + ([a.kwarg] if a.kwarg else [])
            }

        for fn in defs.values():
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = _dotted(target)
                if name in _JIT_WRAPPERS:
                    yield fn, params(fn)
                elif (isinstance(dec, ast.Call) and name.endswith("partial")
                        and dec.args and _dotted(dec.args[0]) in _JIT_WRAPPERS):
                    yield fn, params(fn) - self._static_params(dec, fn)

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee in _JIT_WRAPPERS or callee in _SCAN_CALLS:
                fn_args = [a for a in node.args]
                if callee in _SCAN_CALLS and not fn_args:
                    continue
                cand = fn_args[0] if fn_args else None
                if isinstance(cand, ast.Lambda):
                    yield cand, params(cand)
                elif isinstance(cand, ast.Name) and cand.id in defs:
                    fn = defs[cand.id]
                    yield fn, params(fn) - self._static_params(node, fn)

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        seen: set[tuple[int, int]] = set()
        for fn, traced in self._traced_functions(ctx):
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for node in _walk_no_lambda(stmt):
                    if not isinstance(node, (ast.If, ast.While)):
                        continue
                    used = {
                        n.id for n in ast.walk(node.test)
                        if isinstance(n, ast.Name)
                    }
                    hit = used & traced
                    key = (node.lineno, node.col_offset)
                    if hit and key not in seen:
                        seen.add(key)
                        kind = "if" if isinstance(node, ast.If) else "while"
                        f = ctx.finding(
                            self, node,
                            f"Python `{kind}` on traced value(s) "
                            f"{sorted(hit)} inside a jit/scan/vmap body — "
                            "use jnp.where or lax.cond",
                        )
                        if f:
                            yield f


# ---------------------------------------------------------------------------
# SL104 — iteration over unordered sets feeding simulation state
# ---------------------------------------------------------------------------


class UnorderedIterationRule(Rule):
    id = "SL104"
    name = "unordered-iteration"
    description = (
        "iterating a set feeds hash-order nondeterminism into whatever "
        "consumes it; wrap in sorted() to pin the order."
    )

    _CONSUMERS = frozenset({"list", "tuple", "enumerate", "sum", "min", "max"})

    def _is_set_expr(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Set) or isinstance(node, ast.SetComp):
            return True
        if isinstance(node, ast.Call):
            return _dotted(node.func) in ("set", "frozenset")
        return False

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        iters: list[ast.AST] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.For, ast.AsyncFor)):
                iters.append(node.iter)
            elif isinstance(node, ast.comprehension):
                iters.append(node.iter)
            elif (isinstance(node, ast.Call)
                    and _dotted(node.func) in self._CONSUMERS and node.args):
                iters.append(node.args[0])
        for it in iters:
            if self._is_set_expr(it):
                f = ctx.finding(
                    self, it,
                    "iteration over a set is hash-ordered "
                    "(nondeterministic across runs/versions); "
                    "wrap in sorted()",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL105 — in-place mutation of frozen trace/tape columns
# ---------------------------------------------------------------------------


class TapeColumnMutationRule(Rule):
    id = "SL105"
    name = "tape-column-mutation"
    description = (
        "TraceBatch/StreamScores columns are shared, frozen-by-contract "
        "arrays (fixtures, tape caches, shards alias them); in-place "
        "stores corrupt every aliasing view. Copy, then mutate."
    )

    # the columnar fields of TraceBatch / StreamScores (core/trace.py)
    COLUMNS = frozenset({
        "offsets", "sizes", "file_ids", "app_ids", "times",
        "gap_positions", "gap_seconds",
        "rf_sum", "percentage", "seek_distance", "nbytes", "offset_sum",
    })
    _MUTATORS = frozenset({"sort", "fill", "resize", "partition", "put"})

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            targets: list[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = list(node.targets)
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for t in targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and t.value.attr in self.COLUMNS):
                    f = ctx.finding(
                        self, node,
                        f"in-place store into `.{t.value.attr}[...]` — "
                        "trace/tape columns are frozen by contract; "
                        "build a new array instead",
                    )
                    if f:
                        yield f
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._MUTATORS
                    and isinstance(node.func.value, ast.Attribute)
                    and node.func.value.attr in self.COLUMNS):
                f = ctx.finding(
                    self, node,
                    f"in-place `.{node.func.attr}()` on column "
                    f"`.{node.func.value.attr}` — trace/tape columns are "
                    "frozen by contract (use np.sort(...) etc.)",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL106 — load-bearing assert in library code
# ---------------------------------------------------------------------------


class LoadBearingAssertRule(Rule):
    id = "SL106"
    name = "load-bearing-assert"
    description = (
        "`assert` in library code vanishes under `python -O`; accounting "
        "and state-machine invariants must raise ValueError/RuntimeError "
        "(or go through the sanitizer) so optimization cannot disable "
        "them."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assert):
                f = ctx.finding(
                    self, node,
                    "assert is stripped under python -O; raise "
                    "ValueError/RuntimeError or use repro.analysis."
                    "sanitize.check",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL107 — unit-suffix mismatches
# ---------------------------------------------------------------------------


_SUFFIX_FAMILIES: dict[str, str] = {}
for _fam, _sufs in (
    ("bytes", ("_bytes",)),
    ("megabytes", ("_mb", "_mbs", "_mib")),
    ("seconds", ("_seconds", "_secs", "_sec")),
    ("milliseconds", ("_ms",)),
    ("microseconds", ("_us",)),
):
    for _s in _sufs:
        _SUFFIX_FAMILIES[_s] = _fam


def _unit_family(node: ast.AST) -> str | None:
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    else:
        return None
    lowered = name.lower()
    for suffix, family in _SUFFIX_FAMILIES.items():
        if lowered.endswith(suffix):
            return family
    return None


class UnitSuffixRule(Rule):
    id = "SL107"
    name = "unit-suffix-mismatch"
    description = (
        "a `*_bytes` name bound to (or added against) a `*_seconds`/"
        "`*_mb`/`*_us` name with no conversion is a unit bug waiting in "
        "the accounting."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tf = _unit_family(node.targets[0])
                vf = _unit_family(node.value)
                if tf and vf and tf != vf:
                    f = ctx.finding(
                        self, node,
                        f"{tf} name assigned directly from a {vf} name "
                        "with no conversion",
                    )
                    if f:
                        yield f
            elif (isinstance(node, ast.AugAssign)
                    and isinstance(node.op, (ast.Add, ast.Sub))):
                tf = _unit_family(node.target)
                vf = _unit_family(node.value)
                if tf and vf and tf != vf:
                    f = ctx.finding(
                        self, node,
                        f"{tf} name incremented by a {vf} name "
                        "with no conversion",
                    )
                    if f:
                        yield f
            elif (isinstance(node, ast.BinOp)
                    and isinstance(node.op, (ast.Add, ast.Sub))):
                lf = _unit_family(node.left)
                rf = _unit_family(node.right)
                if lf and rf and lf != rf:
                    f = ctx.finding(
                        self, node,
                        f"{lf} name added/subtracted against a {rf} name "
                        "with no conversion",
                    )
                    if f:
                        yield f


# ---------------------------------------------------------------------------
# SL108 — public engine entry points must state their accuracy contract
# ---------------------------------------------------------------------------


class EngineContractRule(Rule):
    id = "SL108"
    name = "missing-engine-contract"
    description = (
        "public run*/simulate*/replay* entry points in repro.core must "
        "say what accuracy they promise (bit-exact vs the oracle, or a "
        "documented tolerance tier) — that contract is what the golden "
        "fixtures enforce."
    )

    _PREFIXES = ("run", "simulate", "replay")
    _TOKENS = (
        "exact", "oracle", "tolerance", "accuracy contract",
        "bit-identical",
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        parts = ctx.rel.split("/")
        if "core" not in parts[:-1]:
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.FunctionDef):
                continue
            name = node.name
            if name.startswith("_") or not name.startswith(self._PREFIXES):
                continue
            doc = (ast.get_docstring(node) or "").lower()
            if not any(tok in doc for tok in self._TOKENS):
                f = ctx.finding(
                    self, node,
                    f"`{name}` is a public engine entry point but its "
                    "docstring states no accuracy contract "
                    "(bit-exact / oracle / tolerance)",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL109 — shared mutable default arguments
# ---------------------------------------------------------------------------


class MutableDefaultRule(Rule):
    id = "SL109"
    name = "mutable-default-arg"
    description = (
        "a mutable default is one object shared across every call — "
        "state leaks between runs; default to None and construct inside."
    )

    _CTORS = frozenset({
        "list", "dict", "set", "deque", "collections.deque",
        "np.array", "numpy.array", "np.zeros", "np.empty", "np.ones",
    })

    def _is_mutable(self, node: ast.AST) -> bool:
        if isinstance(node, (ast.List, ast.Dict, ast.Set)):
            return True
        return (isinstance(node, ast.Call)
                and _dotted(node.func) in self._CTORS)

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                continue
            defaults = list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None
            ]
            for d in defaults:
                if self._is_mutable(d):
                    f = ctx.finding(
                        self, d,
                        "mutable default argument is shared across calls; "
                        "use None and construct in the body",
                    )
                    if f:
                        yield f


# ---------------------------------------------------------------------------
# SL110 — silently swallowed exceptions
# ---------------------------------------------------------------------------


class SilentExceptionRule(Rule):
    id = "SL110"
    name = "silent-exception"
    description = (
        "a bare `except:` (or `except Exception: pass`) hides the "
        "accounting bug it catches; catch the specific error or at "
        "least record it."
    )

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if node.type is None:
                f = ctx.finding(
                    self, node,
                    "bare `except:` also swallows KeyboardInterrupt/"
                    "SystemExit; name the exception",
                )
                if f:
                    yield f
                continue
            broad = _dotted(node.type) in ("Exception", "BaseException")
            silent = all(
                isinstance(s, (ast.Pass, ast.Continue)) for s in node.body
            )
            if broad and silent:
                f = ctx.finding(
                    self, node,
                    "`except Exception` with an empty body silently "
                    "swallows every bug; narrow it or handle it",
                )
                if f:
                    yield f


# ---------------------------------------------------------------------------
# SL111 — lru_cache on methods leaks instances
# ---------------------------------------------------------------------------


class MethodLruCacheRule(Rule):
    id = "SL111"
    name = "method-lru-cache"
    description = (
        "functools.lru_cache on a method keys the cache on `self`: "
        "instances never free, and two simulators with equal args share "
        "nothing; cache at module level or on frozen keys."
    )

    _CACHES = frozenset({
        "lru_cache", "cache", "functools.lru_cache", "functools.cache",
    })

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        for cls in ast.walk(ctx.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for node in cls.body:
                if not isinstance(node, ast.FunctionDef):
                    continue
                args = node.args.posonlyargs + node.args.args
                if not args or args[0].arg not in ("self", "cls"):
                    continue
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    if _dotted(target) in self._CACHES:
                        f = ctx.finding(
                            self, dec,
                            f"lru_cache on method `{node.name}` pins every "
                            "instance in the cache key",
                        )
                        if f:
                            yield f


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


_REGISTRY: tuple[Rule, ...] = (
    UnseededRandomRule(),
    UnscopedX64Rule(),
    TracedBranchRule(),
    UnorderedIterationRule(),
    TapeColumnMutationRule(),
    LoadBearingAssertRule(),
    UnitSuffixRule(),
    EngineContractRule(),
    MutableDefaultRule(),
    SilentExceptionRule(),
    MethodLruCacheRule(),
)


def all_rules() -> tuple[Rule, ...]:
    """The full registry, id-ordered."""

    return _REGISTRY


def rules_by_id(ids: Iterable[str]) -> tuple[Rule, ...]:
    wanted = {i.strip().upper() for i in ids}
    known = {r.id for r in _REGISTRY}
    unknown = sorted(wanted - known)
    if unknown:
        raise ValueError(f"unknown rule id(s) {unknown}; known: {sorted(known)}")
    return tuple(r for r in _REGISTRY if r.id in wanted)
