"""fednc-lint: AST rules codifying the repo's hard-won invariants.

Each rule is a function over a :class:`ModuleContext` registered under
a stable id.  The ids are part of the repo's contract — suppressions
(``# fednc: ignore[FNC002] why``), the JSON report, and the docs all
refer to them:

``FNC001 raw-clock``
    Any ``time.perf_counter()`` / ``time.time()`` family call outside
    ``repro/obs``.  All wall timing flows through ``obs.timed`` /
    ``obs.clock`` so published numbers share one fenced idiom.
``FNC002 unfenced-timing``
    A ``with obs.timed(...)`` region that dispatches jax work but never
    fences (``sw.fence`` / ``obs.device_sync`` /
    ``jax.block_until_ready``) before the clock stops — it measures
    Python dispatch, not device time.  Spans (``obs.span`` /
    ``tracer.span``) are host phases by design and never fence: a
    fence inside one would make a traced run another program.
``FNC003 tracer-leak``
    Host conversions (``float()`` / ``int()`` / ``bool()`` /
    ``.item()`` / ``np.asarray``) or Python ``if``/``while`` on traced
    values inside functions reachable from ``@jax.jit`` or
    ``pl.pallas_call`` — a concretization error waiting to fire, or a
    silent recompile per call.
``FNC004 unseeded-rng``
    Global-state ``np.random.*`` / stdlib ``random.*`` draws in the
    determinism-critical paths (``sim``/``grid``/``serve``/``engine``)
    instead of an explicitly seeded ``np.random.default_rng``.
``FNC005 dtype-discipline``
    GF symbol buffers leaving uint8 (or packed lanes leaving int32)
    inside the GF kernel modules — field arithmetic on a promoted
    dtype is silently wrong, not slow.

Downstream projects add rules with :func:`register_rule`; the runner
applies every registered rule to every in-scope module.
"""
from __future__ import annotations

import ast
import dataclasses
import pathlib
from typing import Callable, Dict, Iterator, Optional

from .findings import Finding

__all__ = [
    "ModuleContext", "Rule", "RULES", "register_rule", "run_rules",
]


@dataclasses.dataclass
class ModuleContext:
    """One parsed module as seen by the rules."""

    rel: str                 # repo-relative posix path ("src/repro/...")
    source: str
    tree: ast.Module
    path: Optional[pathlib.Path] = None

    @classmethod
    def from_source(cls, rel: str, source: str,
                    path: Optional[pathlib.Path] = None
                    ) -> "ModuleContext":
        return cls(rel=rel, source=source,
                   tree=ast.parse(source, filename=rel), path=path)


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: str
    doc: str
    fn: Callable[[ModuleContext], Iterator[Finding]]


RULES: Dict[str, Rule] = {}


def register_rule(id: str, name: str, severity: str = "error",
                  doc: str = "", *, overwrite: bool = False):
    """Decorator: register ``fn(ctx) -> iterator of Finding``."""
    def deco(fn):
        if id in RULES and not overwrite:
            raise ValueError(f"rule {id!r} already registered")
        RULES[id] = Rule(id, name, severity, doc or (fn.__doc__ or ""),
                         fn)
        return fn
    return deco


def run_rules(ctx: ModuleContext,
              rules: Optional[Dict[str, Rule]] = None) -> list[Finding]:
    """Apply every rule to one module; returns raw (unsuppressed)
    findings sorted by line."""
    out: list[Finding] = []
    for rule in (rules or RULES).values():
        out.extend(rule.fn(ctx))
    return sorted(out, key=lambda f: (f.line, f.col, f.rule))


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted(node: ast.AST) -> Optional[str]:
    """'a.b.c' for nested Name/Attribute chains, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_map(tree: ast.Module) -> dict[str, str]:
    """Local binding -> absolute dotted target for module imports.

    ``import numpy as np`` -> {'np': 'numpy'};
    ``from jax import random`` -> {'random': 'jax.random'};
    ``from time import perf_counter as pc`` -> {'pc': 'time.perf_counter'}.
    """
    binds: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                binds[alias.asname or alias.name.split(".")[0]] = (
                    alias.name if alias.asname else
                    alias.name.split(".")[0])
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.level == 0:
            for alias in node.names:
                binds[alias.asname or alias.name] = (
                    f"{node.module}.{alias.name}")
    return binds


def resolve_call(func: ast.AST, binds: dict[str, str]) -> Optional[str]:
    """Absolute dotted name of a call target, through the import map."""
    name = dotted(func)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    base = binds.get(head, head)
    return f"{base}.{rest}" if rest else base


# ---------------------------------------------------------------------------
# FNC001 raw-clock
# ---------------------------------------------------------------------------

_CLOCK_FNS = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "time.process_time", "time.process_time_ns",
}

#: paths exempt from FNC001 — the one module allowed to own the clock
_OBS_PREFIX = "src/repro/obs/"


@register_rule("FNC001", "raw-clock", "error",
               "wall timing must flow through obs.timed / obs.clock")
def rule_raw_clock(ctx: ModuleContext) -> Iterator[Finding]:
    if ctx.rel.startswith(_OBS_PREFIX):
        return
    binds = import_map(ctx.tree)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target = resolve_call(node.func, binds)
        if target in _CLOCK_FNS:
            yield Finding(
                ctx.rel, node.lineno, node.col_offset, "FNC001",
                "error",
                f"raw clock call {target}() — use repro.obs.timed "
                f"(always-on stopwatch) or obs.clock() so the "
                f"measurement shares the repo-wide fenced idiom")


# ---------------------------------------------------------------------------
# FNC002 unfenced-timing
# ---------------------------------------------------------------------------

#: attribute roots whose calls dispatch device work under jax
_DISPATCH_ROOTS = {"jnp", "jax", "lax"}

#: repo hot-path entry points that dispatch jax work when called as
#: methods/functions inside a timed region (engine / stream / serve /
#: federation APIs)
_DISPATCH_CALLS = {
    "encode", "encode_seeded", "decode", "round", "multi_edge_round",
    "recode", "recode_with", "ingest", "ingest_seeded", "push",
    "tick", "drain", "train", "aggregate", "fednc_round",
    "fedavg_round", "gf_matmul",
}

_FENCE_CALLS = {"fence", "device_sync", "block_until_ready"}

#: jax.* calls that fence rather than dispatch
_SYNC_TARGETS = {"jax.block_until_ready", "jax.device_get"}


def _timed_withitem(item: ast.withitem) -> bool:
    call = item.context_expr
    if not isinstance(call, ast.Call):
        return False
    name = dotted(call.func)
    return name is not None and name.rsplit(".", 1)[-1] == "timed"


def _is_dispatch(call: ast.Call) -> bool:
    name = dotted(call.func)
    if name is None:
        return False
    if name in _SYNC_TARGETS:
        return False
    root, _, _ = name.partition(".")
    if root in _DISPATCH_ROOTS:
        return True
    return name.rsplit(".", 1)[-1] in _DISPATCH_CALLS


def _is_fence(call: ast.Call) -> bool:
    name = dotted(call.func)
    return (name is not None
            and name.rsplit(".", 1)[-1] in _FENCE_CALLS)


@register_rule("FNC002", "unfenced-timing", "warning",
               "timed regions that dispatch jax work must fence "
               "before the clock stops")
def rule_unfenced_timing(ctx: ModuleContext) -> Iterator[Finding]:
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        if not any(_timed_withitem(i) for i in node.items):
            continue
        dispatches = False
        fences = False
        for sub in ast.walk(ast.Module(body=node.body,
                                       type_ignores=[])):
            if isinstance(sub, ast.Call):
                if _is_fence(sub):
                    fences = True
                elif _is_dispatch(sub):
                    dispatches = True
        if dispatches and not fences:
            yield Finding(
                ctx.rel, node.lineno, node.col_offset, "FNC002",
                "warning",
                "timed region dispatches jax work but never fences "
                "(sw.fence(out) / obs.device_sync / "
                "jax.block_until_ready) before the clock stops — "
                "jax dispatch is async, so this measures dispatch "
                "time, not device time")


# ---------------------------------------------------------------------------
# FNC003 tracer-leak
# ---------------------------------------------------------------------------

_HOST_CASTS = {"float", "int", "bool"}
_STATIC_ATTRS = {"shape", "ndim", "size", "dtype"}
_NP_HOST_FNS = {"numpy.asarray", "numpy.array"}


def _decorator_jit_static(dec: ast.AST) -> Optional[tuple[str, ...]]:
    """static_argnames if `dec` is a jit decorator, else None."""
    name = dotted(dec)
    if name is not None and name.rsplit(".", 1)[-1] == "jit":
        return ()
    if isinstance(dec, ast.Call):
        cname = dotted(dec.func)
        if cname is None:
            return None
        leaf = cname.rsplit(".", 1)[-1]
        if leaf == "jit":                       # @jax.jit(...) form
            return _static_argnames_kwarg(dec)
        if leaf == "partial" and dec.args:      # @partial(jax.jit, ...)
            inner = dotted(dec.args[0])
            if inner and inner.rsplit(".", 1)[-1] == "jit":
                return _static_argnames_kwarg(dec)
    return None


def _static_argnames_kwarg(call: ast.Call) -> tuple[str, ...]:
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            names: list[str] = []
            for elt in ast.walk(kw.value):
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, str):
                    names.append(elt.value)
            return tuple(names)
    return ()


def _collect_functions(tree: ast.Module) -> dict[str, ast.AST]:
    funcs: dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            funcs.setdefault(node.name, node)
    return funcs


def _jit_roots(tree: ast.Module,
               funcs: dict[str, ast.AST]) -> dict[str, tuple[str, ...]]:
    """{function name: static param names} for every jit/pallas root."""
    roots: dict[str, tuple[str, ...]] = {}
    for name, node in funcs.items():
        for dec in getattr(node, "decorator_list", []):
            static = _decorator_jit_static(dec)
            if static is not None:
                roots[name] = static
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        cname = dotted(node.func)
        if cname is None:
            continue
        leaf = cname.rsplit(".", 1)[-1]
        if leaf == "jit":
            # jax.jit(f) / jax.jit(jax.vmap(f)): f becomes a root
            static = _static_argnames_kwarg(node)
            for arg in node.args:
                for ref in ast.walk(arg):
                    if isinstance(ref, ast.Name) and ref.id in funcs:
                        roots.setdefault(ref.id, static)
        elif leaf == "pallas_call" and node.args:
            # the kernel body: keyword-only params are partial-bound
            # compile-time constants, positional params are refs
            for ref in ast.walk(node.args[0]):
                if isinstance(ref, ast.Name) and ref.id in funcs:
                    fn = funcs[ref.id]
                    kwonly = tuple(a.arg for a in fn.args.kwonlyargs)
                    roots.setdefault(ref.id, kwonly)
    return roots


def _reachable(funcs: dict[str, ast.AST],
               roots: dict[str, tuple[str, ...]]) -> set[str]:
    """Names reachable from the roots via same-module references."""
    seen = set(roots)
    frontier = list(roots)
    while frontier:
        fn = funcs[frontier.pop()]
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and node.id in funcs \
                    and node.id not in seen:
                seen.add(node.id)
                frontier.append(node.id)
    return seen


def _expr_tainted(expr: ast.AST, tainted: set[str]) -> bool:
    """True if `expr` reads a traced value.

    ``.shape`` / ``.ndim`` / ``.size`` / ``.dtype`` subtrees are
    trace-static regardless of what they are read from, so Python
    control flow on them is jit-safe and never flagged."""
    if isinstance(expr, ast.Attribute) and expr.attr in _STATIC_ATTRS:
        return False
    if isinstance(expr, ast.Name):
        return expr.id in tainted
    return any(_expr_tainted(child, tainted)
               for child in ast.iter_child_nodes(expr))


def _check_function(ctx: ModuleContext, fn: ast.AST,
                    static: tuple[str, ...],
                    binds: dict[str, str]) -> Iterator[Finding]:
    args = fn.args
    params = [a.arg for a in (args.posonlyargs + args.args
                              + args.kwonlyargs)]
    if args.vararg:
        params.append(args.vararg.arg)
    if args.kwarg:
        params.append(args.kwarg.arg)
    tainted = {p for p in params if p not in static}

    # forward taint through simple assignments, two passes for
    # use-before-def within loops
    body_nodes = list(ast.walk(fn))
    for _ in range(2):
        for node in body_nodes:
            if isinstance(node, (ast.Assign, ast.AugAssign,
                                 ast.AnnAssign)):
                value = getattr(node, "value", None)
                if value is None:
                    continue
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                if _expr_tainted(value, tainted):
                    for t in targets:
                        for leaf in ast.walk(t):
                            if isinstance(leaf, ast.Name):
                                tainted.add(leaf.id)

    for node in body_nodes:
        if isinstance(node, (ast.If, ast.While)) \
                and _expr_tainted(node.test, tainted):
            yield Finding(
                ctx.rel, node.lineno, node.col_offset, "FNC003",
                "error",
                f"Python {'if' if isinstance(node, ast.If) else 'while'}"
                f" on a traced value inside jit-reachable "
                f"'{fn.name}' — use lax.cond/lax.while_loop or hoist "
                f"the value to a static argument")
        elif isinstance(node, ast.Call):
            cname = dotted(node.func)
            if cname is None:
                continue
            resolved = resolve_call(node.func, binds)
            leaf = cname.rsplit(".", 1)[-1]
            if cname in _HOST_CASTS and node.args \
                    and any(_expr_tainted(a, tainted)
                            for a in node.args):
                yield Finding(
                    ctx.rel, node.lineno, node.col_offset, "FNC003",
                    "error",
                    f"host conversion {cname}() of a traced value "
                    f"inside jit-reachable '{fn.name}' — forces a "
                    f"device sync / concretization error under trace")
            elif leaf == "item" and isinstance(node.func, ast.Attribute) \
                    and _expr_tainted(node.func.value, tainted):
                yield Finding(
                    ctx.rel, node.lineno, node.col_offset, "FNC003",
                    "error",
                    f".item() on a traced value inside jit-reachable "
                    f"'{fn.name}'")
            elif resolved in _NP_HOST_FNS and node.args \
                    and any(_expr_tainted(a, tainted)
                            for a in node.args):
                yield Finding(
                    ctx.rel, node.lineno, node.col_offset, "FNC003",
                    "error",
                    f"{resolved}() materializes a traced value on "
                    f"host inside jit-reachable '{fn.name}' — use "
                    f"jnp.asarray, or move the conversion outside "
                    f"the jitted region")


@register_rule("FNC003", "tracer-leak", "error",
               "host conversions / Python control flow on traced "
               "values inside jit-reachable functions")
def rule_tracer_leak(ctx: ModuleContext) -> Iterator[Finding]:
    funcs = _collect_functions(ctx.tree)
    roots = _jit_roots(ctx.tree, funcs)
    if not roots:
        return
    binds = import_map(ctx.tree)
    for name in sorted(_reachable(funcs, roots)):
        static = roots.get(name, ())
        yield from _check_function(ctx, funcs[name], static, binds)


# ---------------------------------------------------------------------------
# FNC004 unseeded-rng
# ---------------------------------------------------------------------------

#: the determinism-critical package paths
_RNG_SCOPES = ("src/repro/sim/", "src/repro/grid/", "src/repro/serve/",
               "src/repro/engine/")

#: constructors of explicitly seeded generators — the sanctioned API
_SEEDED_CTORS = {"default_rng", "Generator", "SeedSequence", "PCG64",
                 "Philox", "SFC64", "BitGenerator"}


@register_rule("FNC004", "unseeded-rng", "error",
               "global-state RNG in determinism-critical paths")
def rule_unseeded_rng(ctx: ModuleContext) -> Iterator[Finding]:
    if not ctx.rel.startswith(_RNG_SCOPES):
        return
    binds = import_map(ctx.tree)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        target = resolve_call(node.func, binds)
        if target is None:
            continue
        if target.startswith("numpy.random."):
            leaf = target.rsplit(".", 1)[-1]
            if leaf not in _SEEDED_CTORS:
                yield Finding(
                    ctx.rel, node.lineno, node.col_offset, "FNC004",
                    "error",
                    f"global-state numpy RNG {target}() — every draw "
                    f"in {ctx.rel.split('/')[2]} must flow from an "
                    f"explicitly seeded np.random.default_rng(seed)")
        elif target.startswith("random.") \
                and target.count(".") == 1 \
                and target.rsplit(".", 1)[-1] != "Random":
            yield Finding(
                ctx.rel, node.lineno, node.col_offset, "FNC004",
                "error",
                f"global-state stdlib RNG {target}() — use an "
                f"explicitly seeded np.random.default_rng(seed) "
                f"(or random.Random(seed))")


# ---------------------------------------------------------------------------
# FNC005 dtype-discipline
# ---------------------------------------------------------------------------

#: dtypes GF symbol / packed-lane buffers are allowed to take
_GF_DTYPES = {"uint8", "int32", "uint32", "bool_"}

#: positional index of the dtype argument for known constructors
_DTYPE_POS = {"zeros": 1, "ones": 1, "empty": 1, "asarray": 1,
              "array": 1, "full": 2, "ShapeDtypeStruct": 1,
              "bitcast_convert_type": 1}


def _gf_kernel_module(rel: str) -> bool:
    if not rel.startswith("src/repro/kernels/"):
        return False
    base = rel.rsplit("/", 1)[-1]
    return base.startswith("gf") or base == "ref.py"


def _dtype_name(node: ast.AST,
                consts: dict[str, str]) -> Optional[str]:
    """The dtype leaf name of an expression, if recognizable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    name = dotted(node)
    if name is None:
        return None
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "dtype":          # mirror casts (.astype(ref.dtype))
        return None
    return consts.get(leaf, leaf) if "." not in name else leaf


def _module_dtype_consts(tree: ast.Module) -> dict[str, str]:
    """Module-level NAME = jnp.<dtype> constants (e.g. _COMPUTE_DTYPE)."""
    consts: dict[str, str] = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            value = dotted(node.value)
            if value is not None:
                consts[node.targets[0].id] = value.rsplit(".", 1)[-1]
    return consts


@register_rule("FNC005", "dtype-discipline", "error",
               "GF buffers must stay uint8 / packed lanes int32 "
               "inside the GF kernel modules")
def rule_dtype_discipline(ctx: ModuleContext) -> Iterator[Finding]:
    if not _gf_kernel_module(ctx.rel):
        return
    consts = _module_dtype_consts(ctx.tree)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dtype_exprs: list[ast.AST] = []
        cname = dotted(node.func)
        leaf = cname.rsplit(".", 1)[-1] if cname else ""
        if leaf == "astype" and node.args:
            dtype_exprs.append(node.args[0])
        pos = _DTYPE_POS.get(leaf)
        if pos is not None and len(node.args) > pos:
            dtype_exprs.append(node.args[pos])
        for kw in node.keywords:
            if kw.arg == "dtype":
                dtype_exprs.append(kw.value)
        for expr in dtype_exprs:
            dname = _dtype_name(expr, consts)
            if dname is None:
                continue
            if dname not in _GF_DTYPES:
                yield Finding(
                    ctx.rel, node.lineno, node.col_offset, "FNC005",
                    "error",
                    f"GF buffer cast to {dname!r} in a GF kernel "
                    f"module — symbols must stay uint8 and packed "
                    f"lanes int32; field arithmetic on a promoted "
                    f"dtype is silently wrong")
