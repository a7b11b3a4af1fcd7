"""spmdlint rules: the repo's SPMD source invariants as visitor classes.

Rule IDs are stable (they appear in suppression comments and CI output):

  RPR001  raw shard_map / make_mesh / AxisType outside repro.runtime
  RPR002  raw jax.lax collective-addressing APIs outside repro.runtime
  RPR003  legacy generator entry points outside src/ (front door only)
  RPR004  nondeterminism in generator device code (unseeded RNG, wall clock)
  RPR005  unguarded int32 casts of edge-count products (overflow seams)
  RPR006  hardcoded interpret= at Pallas kernel call sites
  RPR007  pl.pallas_call outside src/repro/kernels/ (pallascheck seam)

Each rule declares the repo-relative directory prefixes it polices
(``include``) and carve-outs (``exclude``); scopes are invariant
definitions, not configuration. A rule's :meth:`check` receives a
:class:`~repro.analysis.linter.LintContext` and yields
:class:`~repro.analysis.linter.Violation`.
"""
from __future__ import annotations

import ast
import re
from typing import Iterable, Iterator, Optional

from repro.analysis.linter import LintContext, Violation

INT32_MAX = 2**31 - 1


class Rule:
    id: str = "RPR000"
    title: str = ""
    include: tuple = ()
    exclude: tuple = ()

    def applies(self, relpath: str) -> bool:
        def under(prefix: str) -> bool:
            p = prefix.rstrip("/")
            return relpath == p or relpath.startswith(p + "/")
        if any(under(e) for e in self.exclude):
            return False
        return any(under(i) for i in self.include)

    def check(self, ctx: LintContext) -> Iterable[Violation]:
        raise NotImplementedError

    def violation(self, ctx: LintContext, node: ast.AST, message: str
                  ) -> Violation:
        return Violation(self.id, ctx.relpath, getattr(node, "lineno", 1),
                         getattr(node, "col_offset", 0), message)


def _imported_paths(node: ast.AST) -> Iterator[str]:
    """Fully-qualified paths an Import/ImportFrom statement binds."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            yield alias.name
    elif isinstance(node, ast.ImportFrom) and not node.level:
        base = node.module or ""
        for alias in node.names:
            if alias.name != "*":
                yield f"{base}.{alias.name}" if base else alias.name


class BannedPathRule(Rule):
    """Shared machinery: flag imports and uses resolving to banned dotted
    paths, through any aliasing the import table can see."""

    def banned(self, path: str) -> Optional[str]:
        """Message when ``path`` is banned, else None."""
        raise NotImplementedError

    def check(self, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                for full in _imported_paths(node):
                    msg = self.banned(full)
                    if msg:
                        yield self.violation(ctx, node,
                                             f"import of {full}: {msg}")
        for node in ctx.outermost_attributes():
            path = ctx.imports.resolve(node)
            if path is None:
                continue
            msg = self.banned(path)
            if msg:
                yield self.violation(ctx, node, f"{path}: {msg}")


def _matches(path: str, targets: Iterable[str]) -> bool:
    return any(path == t or path.startswith(t + ".") for t in targets)


class RawShardMapRule(BannedPathRule):
    """RPR001: only repro.runtime may touch the version-drifting mesh APIs
    (spmd.py is the compatibility shim; everything else routes through it)."""

    id = "RPR001"
    title = "raw shard_map/mesh APIs outside repro.runtime"
    include = ("src", "examples", "benchmarks", "scripts")
    exclude = ("src/repro/runtime",)
    TARGETS = ("jax.shard_map", "jax.experimental.shard_map",
               "jax.make_mesh", "jax.sharding.AxisType")

    def banned(self, path: str) -> Optional[str]:
        if _matches(path, self.TARGETS):
            return ("raw shard_map/mesh API outside repro.runtime — route "
                    "through repro.runtime.spmd")
        return None


class RawCollectiveRule(BannedPathRule):
    """RPR002: collective addressing is the runtime layer's job — a raw
    jax.lax collective sidesteps the Topology contract (blocked transposes,
    psum over the topology's axes, hierarchical two-hop routing)."""

    id = "RPR002"
    title = "raw jax.lax collectives outside repro.runtime"
    include = ("src", "examples", "benchmarks", "scripts")
    exclude = ("src/repro/runtime",)
    NAMES = ("all_to_all", "axis_index", "psum", "psum_scatter",
             "all_gather", "ppermute", "pmax", "pmin", "pshuffle")
    TARGETS = tuple(f"jax.lax.{n}" for n in NAMES)

    def banned(self, path: str) -> Optional[str]:
        if _matches(path, self.TARGETS):
            return ("raw collective outside repro.runtime — route through "
                    "repro.runtime.blocking / spmd")
        return None


class FrontDoorRule(BannedPathRule):
    """RPR003: examples/, benchmarks/ and scripts/ must enter through
    repro.api (GraphSpec -> plan -> generate); the per-model entry points
    and stream drivers are internal executors."""

    id = "RPR003"
    title = "legacy generator entry points outside src/"
    include = ("examples", "benchmarks", "scripts")
    LEGACY = frozenset({"generate_pba", "generate_pk_host", "PBAStream",
                        "PKStream", "stream_to_shards"})

    def banned(self, path: str) -> Optional[str]:
        parts = path.split(".")
        if parts[0] == "repro" and parts[-1] in self.LEGACY:
            return ("legacy entry point — build a repro.api.GraphSpec and "
                    "go through plan()/generate()")
        return None


class DeterminismRule(Rule):
    """RPR004: generator device code must be reproducible from the config
    seed alone — no unseeded RNG, no wall clock. The repo's own discipline
    is np.random.default_rng(seed) on hosts and repro.core.rng device keys
    on devices."""

    id = "RPR004"
    title = "nondeterminism in generator paths"
    include = ("src/repro/core", "src/repro/runtime")
    SEEDED_OK = frozenset({"numpy.random.default_rng",
                           "numpy.random.Generator",
                           "numpy.random.SeedSequence",
                           "numpy.random.PCG64", "numpy.random.Philox"})

    def check(self, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            path = ctx.imports.resolve(node.func)
            if path is None:
                continue
            n_args = len(node.args) + len(node.keywords)
            if path in ("time.time", "time.time_ns"):
                yield self.violation(
                    ctx, node, f"{path}() in generator code — wall clock "
                    "breaks run-to-run determinism")
            elif path == "random" or path.startswith("random."):
                yield self.violation(
                    ctx, node, f"{path}(): stdlib global RNG is unseeded "
                    "process state — use numpy.random.default_rng(seed)")
            elif path.startswith("numpy.random."):
                if path in self.SEEDED_OK and n_args >= 1:
                    continue
                if path in self.SEEDED_OK:
                    yield self.violation(
                        ctx, node, f"{path}() without a seed — pass the "
                        "config seed explicitly")
                else:
                    yield self.violation(
                        ctx, node, f"{path}(): legacy global-state numpy "
                        "RNG — use numpy.random.default_rng(seed)")


_EDGE_NAME_RE = re.compile(
    r"(?:^|_)(?:e|edges?|num_edges|requested|requested_edges|total_edges|"
    r"edges_per_vertex|edges_per_proc|k|degree)(?:_|$)")
_INT32_CTORS = ("numpy.int32", "jax.numpy.int32")
_ARRAY_CTORS = ("numpy.asarray", "numpy.array", "numpy.full",
                "jax.numpy.asarray", "jax.numpy.array", "jax.numpy.full")


def _identifier_texts(node: ast.AST) -> Iterator[str]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id
        elif isinstance(sub, ast.Attribute):
            yield sub.attr


def _edge_count_product(node: ast.AST) -> Optional[str]:
    """A `*`/`**` BinOp over edge-count-named identifiers inside ``node``
    (the overflow shape: P * vpp * k style products), or None."""
    for sub in ast.walk(node):
        if (isinstance(sub, ast.BinOp)
                and isinstance(sub.op, (ast.Mult, ast.Pow))):
            names = [t for t in _identifier_texts(sub)
                     if _EDGE_NAME_RE.search(t)]
            if names:
                return " * ".join(dict.fromkeys(names))
    return None


def _has_overflow_guard(scope_nodes: Iterable[ast.AST]) -> bool:
    for scope in scope_nodes:
        for sub in ast.walk(scope):
            if isinstance(sub, ast.Constant) and sub.value == INT32_MAX:
                return True
            if (isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Pow)
                    and isinstance(sub.left, ast.Constant)
                    and sub.left.value == 2
                    and isinstance(sub.right, ast.Constant)
                    and sub.right.value == 31):
                return True
            if isinstance(sub, ast.Compare):
                # comparison against a named int32 bound (INT32_MAX etc.)
                sides = [sub.left, *sub.comparators]
                if any("int32" in t.lower() or t.lower() == "imax"
                       for side in sides
                       for t in _identifier_texts(side)):
                    return True
            if isinstance(sub, ast.Call):
                fn = sub.func
                name = (fn.attr if isinstance(fn, ast.Attribute)
                        else fn.id if isinstance(fn, ast.Name) else "")
                if "check_int32" in name or name == "iinfo":
                    return True
    return False


class Int32OverflowRule(Rule):
    """RPR005: a Python-int edge-count product silently truncates when cast
    to int32 (1B vertices x 5 edges overflows at P*vpp*k ~ 2.1e9) — every
    such cast must sit in a scope that range-checks against 2**31 - 1
    (or calls a *check_int32* helper / np.iinfo bound)."""

    id = "RPR005"
    title = "unguarded int32 cast of an edge-count product"
    include = ("src",)

    def _cast_subject(self, ctx: LintContext, node: ast.Call
                      ) -> Optional[ast.AST]:
        path = ctx.imports.resolve(node.func)
        if path in _INT32_CTORS and node.args:
            return node.args[0]
        if path in _ARRAY_CTORS and node.args:
            dtype = next((kw.value for kw in node.keywords
                          if kw.arg == "dtype"),
                         node.args[1] if len(node.args) > 1 else None)
            if dtype is not None and (
                    ctx.imports.resolve(dtype) in _INT32_CTORS
                    or (isinstance(dtype, ast.Constant)
                        and dtype.value == "int32")):
                return node.args[0]
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype" and node.args):
            dtype = node.args[0]
            if (ctx.imports.resolve(dtype) in _INT32_CTORS
                    or (isinstance(dtype, ast.Constant)
                        and dtype.value == "int32")):
                return node.func.value
        return None

    def check(self, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            subject = self._cast_subject(ctx, node)
            if subject is None:
                continue
            product = _edge_count_product(subject)
            if product is None:
                continue
            scopes = ctx.enclosing_functions(node) or [ctx.tree]
            if _has_overflow_guard(scopes):
                continue
            yield self.violation(
                ctx, node, f"int32 cast of edge-count product ({product}) "
                "without an overflow guard — check against 2**31 - 1 first")


class HardcodedInterpretRule(Rule):
    """RPR006: Pallas kernel call sites must not pin interpret= to a
    literal — execution mode is the REPRO_PALLAS probe's decision
    (repro.kernels.dispatch), so the same call site works on TPU and in
    interpret-mode CI."""

    id = "RPR006"
    title = "hardcoded interpret= at a Pallas kernel call site"
    include = ("src", "examples", "benchmarks", "scripts")
    exclude = ("src/repro/kernels",)

    def check(self, ctx: LintContext) -> Iterable[Violation]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            kw = next((k for k in node.keywords if k.arg == "interpret"),
                      None)
            if kw is None or not isinstance(kw.value, ast.Constant):
                continue
            if not isinstance(kw.value.value, bool):
                continue
            path = ctx.imports.resolve(node.func) or ""
            terminal = (node.func.attr if isinstance(node.func, ast.Attribute)
                        else node.func.id if isinstance(node.func, ast.Name)
                        else "")
            if (path.startswith("repro.kernels")
                    or terminal.endswith("_pallas")):
                yield self.violation(
                    ctx, node, f"interpret={kw.value.value} hardcoded at a "
                    "kernel call site — leave it unset so "
                    "repro.kernels.dispatch resolves the probed mode")


class PallasCallSeamRule(BannedPathRule):
    """RPR007: every pl.pallas_call lives in src/repro/kernels/ — that is
    the seam pallascheck's registry certifies (grid/BlockSpec race, VMEM
    budget, ref parity). A pallas_call elsewhere is invisible to the
    static verifier and to the kernel-inventory drift gate."""

    id = "RPR007"
    title = "pl.pallas_call outside src/repro/kernels/"
    include = ("src", "examples", "benchmarks", "scripts")
    exclude = ("src/repro/kernels",)
    TARGETS = ("jax.experimental.pallas.pallas_call",)

    def banned(self, path: str) -> Optional[str]:
        if _matches(path, self.TARGETS):
            return ("pallas_call outside src/repro/kernels — kernels live "
                    "behind the registry so pallascheck "
                    "(python -m repro.analysis kernels) can certify them")
        return None


def all_rules() -> list[Rule]:
    return [RawShardMapRule(), RawCollectiveRule(), FrontDoorRule(),
            DeterminismRule(), Int32OverflowRule(), HardcodedInterpretRule(),
            PallasCallSeamRule()]


def rules_by_id(ids: Iterable[str]) -> list[Rule]:
    table = {r.id: r for r in all_rules()}
    return [table[i] for i in ids]
