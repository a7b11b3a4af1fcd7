"""flowcheck: jaxpr dataflow verifier for the repo's three flow contracts.

Fourth layer of the analysis subsystem (``python -m repro.analysis flow``).
spmdlint pins source invariants, the collective auditor pins compiled
collective *structure*, pallascheck pins kernel grids — this module pins
the *dataflow* the bit-parity guarantees rest on, by abstract
interpretation over the jaxprs of the real front-door programs
(single-shot exchange, streamed exchange, sharded stream setup/round, and
any future communication-free executor registered via
:func:`register_programs`). Three passes:

  FC001 RNG lineage      every ``random_*``/``threefry2x32`` primitive is
                         sliced to its input leaves (implemented as the
                         equivalent forward taint pass); the slice may
                         touch only the declared determinism roots
                         (``core.spec.DETERMINISM_ROOTS``: the seed
                         literal, axis_index/iota rank identity, static
                         budgets). A draw reachable from runtime data —
                         faction rows, counts, demand, carried state — is
                         flagged, including draws issued under a
                         data-dependent cond/while. This is the static
                         form of the phase-2 pool contract
                         (pool = f(seed, rank, budget)) that the
                         communication-free generator family is defined
                         by.
  FC002 axis-role typing logical-role tags from the annotated
                         ``runtime/blocking.py`` entry points
                         (``blocking.AXIS_ROLES``) are propagated through
                         every reshape/transpose/broadcast/all_to_all
                         equation of the traced blocked transpose, per
                         gate topology; each ``all_to_all`` must split
                         exactly the ``dev_dst:<axis>`` role its
                         :class:`Topology` mesh axis claims (the pods
                         two-hop is checked hop-by-hop) and the output
                         must carry the declared post-transpose roles.
                         Every front-door program's all_to_all signatures
                         must then be in the verified set — sound because
                         spmdlint RPR002 already bans raw collectives
                         outside the runtime layer.
  FC003 digest soundness each GraphSpec field is perturbed and the
                         program suite re-traced under ``jax.make_jaxpr``
                         (nothing executes); a field that changes the
                         jaxpr/inputs but not ``spec_digest`` — or vice
                         versa — is flagged, against the field classes
                         declared on :class:`GraphSpec` (identity /
                         routing / sink / runtime-only / model-owned).

Findings carry the same fixture-corpus discipline as spmdlint and
pallascheck (exact ``{(kind, where)}`` identity in
``tests/flow_fixtures/``), and :func:`run_flow`'s inventory JSON is
drift-gated by ``scripts/collective_gate.py`` against the committed
``results/flow_audit_baseline.json``. Imports JAX lazily, on first use.
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Iterable, Optional

KIND_TITLES = {
    "FC000": "trace error",
    "FC001": "RNG draw depends on runtime data",
    "FC002": "blocked-layout axis role violated",
    "FC003": "spec_digest unsound for field",
}

#: Primitives whose outputs are rank identity — a declared determinism
#: root ("rank" in core.spec.DETERMINISM_ROOTS), never tainted.
_ROOT_PRIMS = frozenset({"axis_index", "iota"})

#: Elementwise-ish unary primitives that preserve axis roles exactly.
_ROLE_PRESERVING = frozenset({
    "convert_element_type", "copy", "stop_gradient", "neg", "not",
})


def _is_rng_prim(name: str) -> bool:
    return name.startswith("random_") or name == "threefry2x32"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One verified dataflow defect, addressed by (kind, program, where)."""

    kind: str          # FC000..FC003
    program: str       # program/fixture label, e.g. "flat_1x8/exchange"
    where: str         # primitive name, "out", or the GraphSpec field
    message: str

    def format(self) -> str:
        return (f"{self.program}[{self.where}]: {self.kind} "
                f"{KIND_TITLES.get(self.kind, '')} — {self.message}")

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# --- jaxpr plumbing ----------------------------------------------------------

def _closed(j):
    """The bare Jaxpr of a param that may be Closed or bare."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _is_literal(var) -> bool:
    return hasattr(var, "val")


def _shard_map_body(closed_jaxpr):
    """The innermost shard_map body jaxpr of a traced jit(shard_map(f)),
    or the top jaxpr itself when no shard_map equation exists (host/
    fixture programs). Single-pjit wrappers are descended transparently."""
    from repro.analysis.audit import iter_eqns
    for eqn in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name == "shard_map":
            body = _closed(eqn.params["jaxpr"])
            while len(body.eqns) == 1 \
                    and body.eqns[0].primitive.name == "pjit" \
                    and list(body.eqns[0].invars) == list(body.invars):
                body = _closed(body.eqns[0].params["jaxpr"])
            return body
    return closed_jaxpr.jaxpr


def all_to_all_signatures(jaxpr) -> list:
    """(axis_name, split_axis, concat_axis, tiled) of every all_to_all
    equation reachable from ``jaxpr`` (primitive-level parameters)."""
    from repro.analysis.audit import iter_eqns
    sigs = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name == "all_to_all":
            axis = eqn.params.get("axis_name")
            if isinstance(axis, (tuple, list)) and len(axis) == 1:
                axis = axis[0]
            sigs.append((axis, int(eqn.params.get("split_axis")),
                         int(eqn.params.get("concat_axis")),
                         bool(eqn.params.get("tiled", False))))
    return sigs


def rng_prim_counts(jaxpr) -> dict:
    from repro.analysis.audit import iter_eqns
    c: Counter = Counter()
    for eqn in iter_eqns(jaxpr):
        if _is_rng_prim(eqn.primitive.name):
            c[eqn.primitive.name] += 1
    return dict(c)


# --- FC001: RNG lineage (forward taint) --------------------------------------

class _Taint:
    """Forward taint interpreter: a var is tainted when its value can
    depend on runtime data (any top-level invar). The dual of the issue's
    backward slice — every RNG primitive with a tainted operand has a
    slice leaf outside the declared determinism roots. Literals,
    closed-over trace constants, and axis_index/iota are roots."""

    def __init__(self, label: str):
        self.label = label
        self.flagged: dict = {}          # (kind, where) -> message

    def taint_of(self, env: dict, var) -> bool:
        if _is_literal(var):
            return False
        return env.get(id(var), False)

    def run(self, jaxpr, invar_taints: Iterable[bool],
            ctx_tainted: bool = False) -> list:
        env: dict = {}
        for var, t in zip(jaxpr.invars, invar_taints):
            env[id(var)] = bool(t)
        for var in jaxpr.constvars:
            env[id(var)] = False
        self._eqns(jaxpr, env, ctx_tainted)
        return [self.taint_of(env, v) for v in jaxpr.outvars]

    # -- equation walk -------------------------------------------------------

    def _eqns(self, jaxpr, env: dict, ctx: bool) -> None:
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env, ctx)

    def _sub(self, sub_jaxpr, in_taints, env_out_vars, ctx) -> list:
        sub = _closed(sub_jaxpr)
        sub_env: dict = {}
        for var, t in zip(sub.invars, in_taints):
            sub_env[id(var)] = bool(t)
        for var in sub.constvars:
            sub_env[id(var)] = False
        self._eqns(sub, sub_env, ctx)
        return [self.taint_of(sub_env, v) for v in sub.outvars]

    def _flag(self, eqn, ops_tainted: list, ctx: bool) -> None:
        name = eqn.primitive.name
        if ops_tainted:
            msg = (f"operand(s) {ops_tainted} of {name} are reachable "
                   "from runtime data — draws must derive from "
                   "(seed, rank, static budgets) only")
        else:
            msg = (f"{name} is issued under a data-dependent branch or "
                   "trip count — the draw schedule itself leaks runtime "
                   "data into the lineage")
        self.flagged.setdefault(("FC001", name), msg)

    def _eqn(self, eqn, env: dict, ctx: bool) -> None:
        name = eqn.primitive.name
        in_t = [self.taint_of(env, v) for v in eqn.invars]
        any_in = any(in_t)

        if _is_rng_prim(name):
            ops = [i for i, t in enumerate(in_t) if t]
            if ops or ctx:
                self._flag(eqn, ops, ctx)
            for ov in eqn.outvars:
                env[id(ov)] = any_in
            return
        if name in _ROOT_PRIMS:
            for ov in eqn.outvars:
                env[id(ov)] = False
            return

        if name in ("pjit", "closed_call", "core_call", "xla_call",
                    "custom_jvp_call", "custom_vjp_call", "shard_map",
                    "remat", "checkpoint"):
            sub = eqn.params.get("jaxpr", eqn.params.get("call_jaxpr"))
            if sub is not None \
                    and len(_closed(sub).invars) == len(eqn.invars):
                out_t = self._sub(sub, in_t, eqn.outvars, ctx)
                for ov, t in zip(eqn.outvars, out_t):
                    env[id(ov)] = t
                return
        elif name == "while":
            self._while(eqn, in_t, env, ctx)
            return
        elif name == "scan":
            self._scan(eqn, in_t, env, ctx)
            return
        elif name == "cond":
            self._cond(eqn, in_t, env, ctx)
            return
        else:
            # unknown higher-order primitive: recurse conservatively with
            # every sub-invar carrying the join of the operand taints
            from repro.analysis.audit import _sub_jaxprs
            for sub in _sub_jaxprs(eqn.params):
                self._sub(sub, [any_in] * len(sub.invars), (), ctx)

        for ov in eqn.outvars:
            env[id(ov)] = any_in

    def _while(self, eqn, in_t, env, ctx) -> None:
        cn = eqn.params["cond_nconsts"]
        bn = eqn.params["body_nconsts"]
        cond_c, body_c = in_t[:cn], in_t[cn:cn + bn]
        carry = list(in_t[cn + bn:])
        body = eqn.params["body_jaxpr"]
        cond = eqn.params["cond_jaxpr"]
        # fixed point on the carry taints (monotone join, terminates)
        for _ in range(len(carry) + 1):
            pred_t = any(self._sub(cond, cond_c + carry, (), ctx))
            nxt = self._sub(body, body_c + carry, (),
                            ctx or pred_t)
            joined = [a or b for a, b in zip(carry, nxt)]
            if joined == carry:
                break
            carry = joined
        for ov, t in zip(eqn.outvars, carry):
            env[id(ov)] = t

    def _scan(self, eqn, in_t, env, ctx) -> None:
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        consts, carry = in_t[:nc], list(in_t[nc:nc + ncar])
        xs = in_t[nc + ncar:]
        body = eqn.params["jaxpr"]
        ys: list = []
        for _ in range(len(carry) + 1):
            out = self._sub(body, consts + carry + xs, (), ctx)
            nxt, ys = out[:ncar], out[ncar:]
            joined = [a or b for a, b in zip(carry, nxt)]
            if joined == carry:
                break
            carry = joined
        for ov, t in zip(eqn.outvars, carry + ys):
            env[id(ov)] = t

    def _cond(self, eqn, in_t, env, ctx) -> None:
        pred_t, ops = in_t[0], in_t[1:]
        outs: Optional[list] = None
        for branch in eqn.params["branches"]:
            out = self._sub(branch, ops, (), ctx or pred_t)
            outs = out if outs is None else [a or b
                                             for a, b in zip(outs, out)]
        for ov, t in zip(eqn.outvars, outs or []):
            env[id(ov)] = t or pred_t


def rng_lineage_findings(closed_jaxpr, label: str) -> list:
    """FC001 pass over one traced program: every top-level invar is
    runtime data (tainted); trace constants and literals are roots."""
    interp = _Taint(label)
    jaxpr = closed_jaxpr.jaxpr
    interp.run(jaxpr, [True] * len(jaxpr.invars))
    return [Finding(kind, label, where, msg)
            for (kind, where), msg in sorted(interp.flagged.items())]


# --- FC002: axis-role typing -------------------------------------------------

def _roles_of(env: dict, var) -> tuple:
    if _is_literal(var):
        nd = getattr(getattr(var, "val", None), "ndim", 0)
        return ("?",) * nd
    r = env.get(id(var))
    if r is None:
        nd = len(getattr(var.aval, "shape", ()))
        return ("?",) * nd
    return r


def _reshape_roles(in_roles, in_shape, out_shape, topo, problems) -> tuple:
    """Segment-aligned role transfer through a reshape. The only
    structured transitions are the blocked-layout ones: splitting the
    destination-rank axis ``P`` into the topology's device factorization
    (pod-major: q = (linear device index)*lp + i) and merging the
    received ``(dev_src..., lp)`` group back into the source-rank axis
    ``P_src``. Anything else keeps scalar-matched roles or degrades to
    derived tags that the output contract then rejects."""
    import math

    out = [None] * len(out_shape)
    i = j = 0
    dst_split = topo.device_axis_roles("dst") + ("lp_dst",)
    src_merge = topo.device_axis_roles("src") + ("lp",)
    while i < len(in_shape) or j < len(out_shape):
        # The two blocked-layout transitions take priority over the
        # greedy scalar matching: with any size-1 mesh axis the generic
        # rules would pair the device axis up differently and lose the
        # roles (the d=1 degenerate case must type like the d=8 one).
        k = len(dst_split)
        if i < len(in_shape) and in_roles[i] == "P" \
                and j + k <= len(out_shape) \
                and tuple(out_shape[j:j + k]) \
                == tuple(topo.axis_sizes) + (out_shape[j + k - 1],) \
                and math.prod(out_shape[j:j + k]) == in_shape[i]:
            out[j:j + k] = list(dst_split)
            i += 1
            j += k
            continue
        if j < len(out_shape) and i + k <= len(in_shape) \
                and tuple(in_roles[i:i + k]) == src_merge \
                and math.prod(in_shape[i:i + k]) == out_shape[j]:
            out[j] = "P_src"
            i += k
            j += 1
            continue
        if i < len(in_shape) and j < len(out_shape) \
                and in_shape[i] == out_shape[j]:
            out[j] = in_roles[i]
            i += 1
            j += 1
            continue
        if j < len(out_shape) and out_shape[j] == 1 \
                and (i >= len(in_shape) or in_shape[i] != 1):
            out[j] = "unit"
            j += 1
            continue
        if i < len(in_shape) and in_shape[i] == 1 \
                and (j >= len(out_shape) or out_shape[j] != 1):
            i += 1
            continue
        if i < len(in_shape) and j < len(out_shape) \
                and in_shape[i] > out_shape[j]:
            # split in_shape[i] into out axes j..k
            k, prod = j, 1
            while k < len(out_shape) and prod < in_shape[i]:
                prod *= out_shape[k]
                k += 1
            if prod != in_shape[i]:
                problems.append(
                    f"reshape {tuple(in_shape)} -> {tuple(out_shape)} "
                    "does not factor axis-wise")
                return ("?",) * len(out_shape)
            sizes = tuple(out_shape[j:k])
            if in_roles[i] == "P" \
                    and sizes == tuple(topo.axis_sizes) + (sizes[-1],):
                out[j:k] = list(dst_split)
            else:
                out[j:k] = [f"{in_roles[i]}[{t}]" for t in range(k - j)]
            i += 1
            j = k
            continue
        if i < len(in_shape) and j < len(out_shape):
            # merge in axes i..k into out_shape[j]
            k, prod = i, 1
            while k < len(in_shape) and prod < out_shape[j]:
                prod *= in_shape[k]
                k += 1
            if prod != out_shape[j]:
                problems.append(
                    f"reshape {tuple(in_shape)} -> {tuple(out_shape)} "
                    "does not factor axis-wise")
                return ("?",) * len(out_shape)
            group = tuple(in_roles[i:k])
            if group == src_merge:
                out[j] = "P_src"
            elif len(set(group)) == 1:
                out[j] = group[0]
            else:
                out[j] = "+".join(group)
            i = k
            j += 1
            continue
        problems.append(
            f"reshape {tuple(in_shape)} -> {tuple(out_shape)}: "
            "unmatched trailing axes")
        return ("?",) * len(out_shape)
    return tuple(out)


class _Roles:
    """Axis-role abstract interpreter over a transpose body jaxpr."""

    def __init__(self, topo, label: str):
        self.topo = topo
        self.label = label
        self.findings: list = []
        self.signatures: list = []
        self.axis_sizes = dict(zip(topo.axis_names, topo.axis_sizes))

    def run(self, jaxpr, in_roles: Iterable[tuple]) -> list:
        env: dict = {}
        for var, roles in zip(jaxpr.invars, in_roles):
            env[id(var)] = tuple(roles)
        self._eqns(jaxpr, env)
        return [_roles_of(env, v) for v in jaxpr.outvars]

    def _eqns(self, jaxpr, env: dict) -> None:
        for eqn in jaxpr.eqns:
            self._eqn(eqn, env)

    def _eqn(self, eqn, env: dict) -> None:
        name = eqn.primitive.name
        out_shape = tuple(getattr(eqn.outvars[0].aval, "shape", ()))

        if name == "pjit" or name == "closed_call":
            sub = _closed(eqn.params["jaxpr"])
            if len(sub.invars) == len(eqn.invars):
                sub_env: dict = {}
                for var, op in zip(sub.invars, eqn.invars):
                    sub_env[id(var)] = _roles_of(env, op)
                self._eqns(sub, sub_env)
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    env[id(ov)] = _roles_of(sub_env, sv)
                return

        r = _roles_of(env, eqn.invars[0]) if eqn.invars else ()
        in_shape = (tuple(getattr(eqn.invars[0].aval, "shape", ()))
                    if eqn.invars else ())

        if name == "reshape" and eqn.params.get("dimensions") is None:
            problems: list = []
            out = _reshape_roles(r, in_shape, out_shape, self.topo,
                                 problems)
            for p in problems:
                self.findings.append(Finding("FC002", self.label,
                                             "reshape", p))
            env[id(eqn.outvars[0])] = out
            return
        if name == "transpose":
            perm = eqn.params["permutation"]
            env[id(eqn.outvars[0])] = tuple(r[p] for p in perm)
            return
        if name == "broadcast_in_dim":
            bdims = eqn.params["broadcast_dimensions"]
            out = ["unit" if s == 1 else "?" for s in out_shape]
            for k, ax in enumerate(bdims):
                if k < len(r) and in_shape[k] == out_shape[ax]:
                    out[ax] = r[k]
            env[id(eqn.outvars[0])] = tuple(out)
            return
        if name == "squeeze":
            dims = set(eqn.params["dimensions"])
            env[id(eqn.outvars[0])] = tuple(
                role for k, role in enumerate(r) if k not in dims)
            return
        if name == "slice":
            out = [role if in_shape[k] == out_shape[k]
                   else ("unit" if out_shape[k] == 1 else "?")
                   for k, role in enumerate(r)]
            env[id(eqn.outvars[0])] = tuple(out)
            return
        if name == "all_to_all":
            self._all_to_all(eqn, r, env)
            return
        if name in _ROLE_PRESERVING:
            env[id(eqn.outvars[0])] = r
            return
        # structural default: any same-shaped operand donates its roles
        for op in eqn.invars:
            if not _is_literal(op) \
                    and tuple(getattr(op.aval, "shape", ())) == out_shape:
                env[id(eqn.outvars[0])] = _roles_of(env, op)
                return
        for ov in eqn.outvars:
            env[id(ov)] = ("?",) * len(getattr(ov.aval, "shape", ()))

    def _all_to_all(self, eqn, r, env) -> None:
        axis = eqn.params.get("axis_name")
        if isinstance(axis, (tuple, list)) and len(axis) == 1:
            axis = axis[0]
        split = int(eqn.params["split_axis"])
        concat = int(eqn.params["concat_axis"])
        tiled = bool(eqn.params.get("tiled", False))
        self.signatures.append((axis, split, concat, tiled))
        in_shape = tuple(eqn.invars[0].aval.shape)
        size = self.axis_sizes.get(axis)
        problems = []
        want = f"dev_dst:{axis}"
        got = r[split] if split < len(r) else "?"
        if got != want:
            problems.append(
                f"all_to_all over mesh axis {axis!r} splits axis {split} "
                f"carrying role {got!r}, not the destination-device role "
                f"{want!r} — the collective permutes the wrong logical "
                "axis")
        elif size is not None and in_shape[split] != size:
            problems.append(
                f"split axis {split} has size {in_shape[split]}, mesh "
                f"axis {axis!r} has {size}")
        cgot = r[concat] if concat < len(r) else "?"
        if cgot != "unit":
            problems.append(
                f"concat axis {concat} carries role {cgot!r} — expected "
                "the wrapper's fresh unit axis; received slabs would "
                "interleave into a live logical axis")
        if problems:
            self.findings.append(Finding(
                "FC002", self.label, "all_to_all", "; ".join(problems)))
            env[id(eqn.outvars[0])] = ("?",) * len(r)
            return
        out = list(r)
        out[split] = "unit"
        out[concat] = f"dev_src:{axis}"
        env[id(eqn.outvars[0])] = tuple(out)


def _expand_payload(roles: tuple, ndim: int) -> tuple:
    """Expand a trailing '...' role to payload0..payloadN for ndim axes."""
    if roles and roles[-1] == "...":
        base = roles[:-1]
        extra = ndim - len(base)
        return base + tuple(f"payload{k}" for k in range(max(extra, 0)))
    return roles


def check_transpose_roles(fn, args, topo, in_roles, out_roles,
                          label: str) -> tuple:
    """FC002 part (a): run the axis-role interpreter over one traced
    blocked-transpose harness. Returns (findings, signatures)."""
    import jax

    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:                      # pragma: no cover
        return [Finding("FC000", label, "trace", f"{exc}")], []
    body = _shard_map_body(closed)
    ndim = len(body.invars[0].aval.shape)
    seeded = ("unit",) + _expand_payload(tuple(in_roles), ndim - 1)
    interp = _Roles(topo, label)
    got = interp.run(body, [seeded])
    want = ("unit",) + _expand_payload(tuple(out_roles), ndim - 1)
    findings = list(interp.findings)
    if tuple(got[0]) != want:
        findings.append(Finding(
            "FC002", label, "out",
            f"transpose output carries roles {tuple(got[0])}, contract "
            f"requires {want} — the blocked layout does not survive"))
    return findings, interp.signatures


def verified_transpose_signatures(topo) -> tuple:
    """Trace blocking's annotated transpose entry points over ``topo``
    and return (findings, signature set, per-entry report)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec

    from repro.runtime import blocking, spmd

    d = topo.num_devices
    lp = 2
    p = lp * d
    mesh = topo.build_mesh()
    spec = topo.spec_axes
    findings: list = []
    sigs: set = set()
    report: dict = {}
    for entry, roles in sorted(blocking.AXIS_ROLES.items()):
        entry_fn = getattr(blocking, entry)
        payload = (3,) if "..." in roles["in"] else ()
        nones = (None,) * (2 + len(payload))

        def body(x, _fn=entry_fn):
            return _fn(x[0], topo)[None]

        fn = jax.jit(spmd.shard_map(
            body, mesh=mesh, in_specs=(PartitionSpec(spec, *nones),),
            out_specs=PartitionSpec(spec, *nones), check_vma=False))
        x = jnp.zeros((d, lp, p) + payload, jnp.int32)
        label = f"{topo.label}/{entry}"
        f, s = check_transpose_roles(fn, (x,), topo, roles["in"],
                                     roles["out"], label)
        findings.extend(f)
        sigs.update(s)
        report[entry] = {"signatures": sorted(map(list, s)),
                         "ok": not f}
    return findings, sigs, report


# --- FC003: digest soundness -------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FieldRule:
    """How one spec field is classified and perturbed for FC003."""

    name: str
    cls: str                      # identity | routing | sink | runtime
    perturb: Callable             # spec -> perturbed spec


def fingerprint_program(fn, args) -> str:
    """Content fingerprint of a traced program: canonical jaxpr text
    (literals included), closed-over constants, and example-arg contents.
    Two specs whose programs and inputs fingerprint identically generate
    the same bits."""
    import jax
    import numpy as np

    from repro.core.spec import spec_digest

    closed = jax.make_jaxpr(fn)(*args)
    consts = [np.asarray(c) for c in closed.consts]
    leaves = [np.asarray(a) for a in jax.tree_util.tree_leaves(args)]
    return spec_digest(str(closed.jaxpr), consts, leaves)


def digest_soundness_findings(base, rules: Iterable[FieldRule],
                              digest_fn: Callable, suite_fn: Callable,
                              label: str = "spec") -> tuple:
    """Generic FC003 engine: perturb each field, compare digest movement
    against program-suite fingerprint movement per the field's class.
    Returns (findings, per-field report). ``suite_fn(obj) -> {name: fp}``
    traces the full program suite; it is only invoked for classes whose
    contract constrains the trace (identity, sink)."""
    findings: list = []
    report: dict = {}
    base_digest = digest_fn(base)
    base_suite: Optional[dict] = None

    def suite(obj):
        nonlocal base_suite
        if base_suite is None:
            base_suite = suite_fn(base)
        return suite_fn(obj)

    for rule in sorted(rules, key=lambda r: r.name):
        try:
            pert = rule.perturb(base)
            digest_changed = digest_fn(pert) != base_digest
            trace_changed: Optional[bool] = None
            if rule.cls in ("identity", "sink"):
                trace_changed = suite(pert) != base_suite
        except Exception as exc:
            findings.append(Finding(
                "FC000", label, rule.name,
                f"perturbation failed to plan/trace: {exc}"))
            report[rule.name] = {"class": rule.cls, "error": str(exc)}
            continue
        report[rule.name] = {"class": rule.cls,
                             "digest_changed": digest_changed,
                             "trace_changed": trace_changed}
        if rule.cls == "identity":
            if trace_changed and not digest_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "perturbing it changes a traced program but not "
                    "spec_digest — resumes could interleave two "
                    "different graphs under one fingerprint"))
            elif digest_changed and not trace_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "spec_digest covers it but no traced program "
                    "depends on it — either a dead field or a missing "
                    "non-identity declaration"))
            elif not digest_changed and not trace_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "neither spec_digest nor any traced program moves "
                    "when it is perturbed — dead identity field"))
        elif rule.cls == "routing":
            if digest_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "routing field leaked into spec_digest — identical "
                    "graphs generated over different topologies would "
                    "refuse to resume each other's shards"))
        elif rule.cls == "sink":
            if digest_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "sink field leaked into spec_digest"))
            if trace_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "sink field reaches a traced program — where edges "
                    "land must never change what is generated"))
        elif rule.cls == "runtime":
            if not digest_changed:
                findings.append(Finding(
                    "FC003", label, rule.name,
                    "runtime-binding identity field is missing from "
                    "spec_digest"))
    return findings, report


def _graphspec_rules(spec) -> tuple:
    """FieldRules for every GraphSpec field, derived from the classes
    declared on the dataclass. Unclassifiable fields produce an FC003
    finding via the returned ``unclassified`` list."""
    from repro.core.spec import GraphSpec
    from repro.runtime.topology import Topology

    routing = set(GraphSpec._ROUTING_FIELDS)
    sink = set(GraphSpec._SINK_FIELDS)
    runtime = set(GraphSpec._RUNTIME_ONLY_FIELDS)
    non_identity = set(GraphSpec._NON_IDENTITY_FIELDS)
    other_model = set()
    for model, fields in GraphSpec._MODEL_OWNED_FIELDS.items():
        if model != spec.model:
            other_model.update(fields)

    perturbs = {
        "procs": lambda s: s.replace(procs=s.procs * 2),
        "vertices_per_proc":
            lambda s: s.replace(vertices_per_proc=s.vertices_per_proc + 1),
        "edges_per_vertex":
            lambda s: s.replace(edges_per_vertex=s.edges_per_vertex + 1),
        "factions": lambda s: s.replace(
            factions=dataclasses.replace(s.factions,
                                         seed=s.factions.seed + 1)),
        "interfaction_prob":
            lambda s: s.replace(
                interfaction_prob=s.interfaction_prob + 0.01),
        "pair_capacity": lambda s: s.replace(
            pair_capacity=(s.pair_capacity or 16) * 2),
        "exchange_rounds": lambda s: s.replace(
            exchange_rounds=(s.exchange_rounds or 1) + 1),
        "total_capacity_factor": lambda s: s.replace(
            total_capacity_factor=s.total_capacity_factor + 1),
        "auto_capacity":
            lambda s: s.replace(auto_capacity=not s.auto_capacity),
        "seed": lambda s: s.replace(seed=s.seed + 1),
        "topology": lambda s: s.replace(
            topology=Topology.pods(1, s.topology.num_devices)),
        "execution": lambda s: s.replace(execution="auto"),
        "overlap": lambda s: s.replace(overlap=not s.overlap),
        "sink": lambda s: s.replace(sink="shards", out_dir="/tmp/fc003"),
        "out_dir": lambda s: s.replace(out_dir="/tmp/fc003-elsewhere"),
        "num_shards": lambda s: s.replace(num_shards=s.num_shards + 1),
    }

    rules: list = []
    unclassified: list = []
    for f in dataclasses.fields(spec):
        name = f.name
        if name == "model" or name in other_model:
            # model selection swaps the whole program registry; fields
            # owned by the other model never reach this model's programs
            continue
        if name in runtime:
            cls = "runtime"
        elif name in routing:
            cls = "routing"
        elif name in sink:
            cls = "sink"
        elif name in non_identity:
            unclassified.append(name)
            continue
        else:
            cls = "identity"
        if name not in perturbs:
            unclassified.append(name)
            continue
        rules.append(FieldRule(name, cls, perturbs[name]))
    return rules, unclassified


# --- front-door program registry ---------------------------------------------

@dataclasses.dataclass(frozen=True)
class FlowProgram:
    """One traced front-door program flowcheck verifies."""

    label: str
    program: str        # exchange | stream_setup | stream_round |
                        # cfree_words | cfree
    topology: object
    build: Callable               # () -> (fn, example_args)
    rng_expected: bool = True


_EXTRA_BUILDERS: list = []


def register_programs(builder: Callable) -> None:
    """Register extra front-door programs (e.g. a future
    communication-free executor): ``builder(n_dev) -> [FlowProgram]`` is
    invoked by :func:`front_door_programs` on every run, so new executors
    inherit all three passes without touching this module."""
    _EXTRA_BUILDERS.append(builder)


def _base_spec(n_dev: int):
    from repro.core import FactionSpec
    from repro.core.spec import GraphSpec
    from repro.runtime import Topology

    procs = 4 * n_dev if n_dev > 2 else 8
    return GraphSpec(
        model="pba", procs=procs, vertices_per_proc=20,
        edges_per_vertex=2, seed=7, pair_capacity=16, exchange_rounds=2,
        factions=FactionSpec(max(procs // 2, 1), 2, max(procs // 2, 2),
                             seed=1),
        topology=Topology.flat(n_dev), execution="sharded")


def front_door_programs(n_dev: int) -> list:
    """Every registered front-door SPMD program over the gate
    topologies, as lazily-built FlowPrograms."""
    from repro import api
    from repro.launch import bench
    from repro.runtime import Topology

    topos = [Topology.flat(n_dev)]
    if n_dev >= 4 and n_dev % 2 == 0:
        topos.append(Topology.pods(2, n_dev // 2))

    programs: list = []
    for topo in topos:
        spec = _base_spec(n_dev).replace(topology=topo)

        def build_x(s=spec):
            return bench.compile_sharded_pba(api.plan(s))

        def build_xr(s=spec):
            return bench.compile_sharded_pba(
                api.plan(s.replace(exchange_rounds=4)))

        streamed = spec.replace(execution="streamed", exchange_rounds=4)

        def build_setup(s=streamed):
            return bench.compile_sharded_stream_setup(api.plan(s))

        def build_round(s=streamed):
            return bench.compile_sharded_stream_round(api.plan(s))

        programs += [
            FlowProgram(f"{topo.label}/exchange", "exchange", topo,
                        build_x),
            FlowProgram(f"{topo.label}/exchange_r4", "exchange", topo,
                        build_xr),
            FlowProgram(f"{topo.label}/stream_setup", "stream_setup",
                        topo, build_setup),
            FlowProgram(f"{topo.label}/stream_round", "stream_round",
                        topo, build_round, rng_expected=False),
        ]

        # Communication-free family: same front door, zero collectives —
        # FC002 holds trivially (no all_to_all signatures to verify) and
        # FC001 binds on the stream-words program, whose lineage is the
        # seed literal alone by construction; the expansion program takes
        # the words as runtime data and must draw nothing.
        for model, kw in (("ba_cfree", {"cfree_vertices": 16 * n_dev,
                                        "ba_degree": 2}),
                          ("rmat", {"cfree_vertices": 256,
                                    "cfree_edges": 64 * n_dev}),
                          ("er", {"cfree_vertices": 101,
                                  "cfree_edges": 64 * n_dev})):
            cspec = api.GraphSpec(model=model, seed=7, topology=topo,
                                  execution="sharded", **kw)

            def build_words(s=cspec):
                return bench.compile_cfree_words(api.plan(s))

            def build_cfree(s=cspec):
                return bench.compile_sharded_cfree(api.plan(s))

            programs += [
                FlowProgram(f"{topo.label}/cfree_words_{model}",
                            "cfree_words", topo, build_words),
                FlowProgram(f"{topo.label}/cfree_{model}", "cfree", topo,
                            build_cfree, rng_expected=False),
            ]
    for builder in _EXTRA_BUILDERS:
        programs.extend(builder(n_dev))
    return programs


# --- top-level driver --------------------------------------------------------

def check_program(prog: FlowProgram, verified_sigs: dict) -> tuple:
    """FC001 + FC002(b) over one front-door program. Returns
    (findings, report)."""
    import jax

    try:
        fn, args = prog.build()
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:
        return ([Finding("FC000", prog.label, "trace",
                         f"failed to build/trace: {exc}")],
                {"error": str(exc)})
    findings = rng_lineage_findings(closed, prog.label)
    sigs = all_to_all_signatures(closed.jaxpr)
    allowed = verified_sigs.get(prog.topology.label, set())
    for sig in sorted(set(sigs)):
        if sig not in allowed:
            findings.append(Finding(
                "FC002", prog.label, "all_to_all",
                f"all_to_all signature {sig} is not in the role-verified "
                f"set for {prog.topology.label} "
                f"({sorted(allowed)}) — an unreviewed collective route"))
    rng = rng_prim_counts(closed.jaxpr)
    if prog.rng_expected and not rng:
        findings.append(Finding(
            "FC000", prog.label, "rng",
            "program was expected to draw randomness but traces none — "
            "the RNG-lineage pass is checking the wrong program"))
    report = {
        "program": prog.program,
        "topology": prog.topology.label,
        "rng_prims": rng,
        "all_to_all": sorted(map(list, set(sigs))),
        "invars": len(closed.jaxpr.invars),
        "ok": not findings,
    }
    return findings, report


def run_flow(n_dev: Optional[int] = None,
             digest: bool = True) -> tuple:
    """All three passes over the registered front-door programs.
    Returns (findings, inventory)."""
    import jax

    from repro import api
    from repro.core.spec import DETERMINISM_ROOTS
    from repro.launch import bench

    n_dev = len(jax.devices()) if n_dev is None else n_dev
    findings: list = []

    # FC002 part (a): role-verify the annotated transposes per topology
    verified: dict = {}
    transposes: dict = {}
    for prog in front_door_programs(n_dev):
        topo = prog.topology
        if topo.label in verified or topo.is_host:
            continue
        f, sigs, report = verified_transpose_signatures(topo)
        findings.extend(f)
        verified[topo.label] = sigs
        transposes[topo.label] = report

    # FC001 + FC002 part (b) per program
    programs: dict = {}
    for prog in front_door_programs(n_dev):
        f, programs[prog.label] = check_program(prog, verified)
        findings.extend(f)

    # FC003 over the GraphSpec fields
    digest_report: dict = {}
    if digest:
        spec = _base_spec(n_dev)
        rules, unclassified = _graphspec_rules(spec)
        for name in unclassified:
            findings.append(Finding(
                "FC003", "spec", name,
                "GraphSpec field has no flowcheck classification "
                "(identity perturbation / routing / sink / runtime / "
                "model-owned) — declare it in core/spec.py and here"))

        def suite(s):
            fps = {}
            fn, args = bench.compile_sharded_pba(
                api.plan(s.replace(execution="sharded")))
            fps["exchange"] = fingerprint_program(fn, args)
            pl = api.plan(s.replace(execution="streamed"))
            fn, args = bench.compile_sharded_stream_setup(pl)
            fps["stream_setup"] = fingerprint_program(fn, args)
            fn, args = bench.compile_sharded_stream_round(pl)
            fps["stream_round"] = fingerprint_program(fn, args)
            return fps

        f, digest_report = digest_soundness_findings(
            spec, rules, lambda s: s.digest(), suite)
        findings.extend(f)

    inv = {
        "schema": 1,
        "jax_version": jax.__version__,
        "devices": n_dev,
        "roots": list(DETERMINISM_ROOTS),
        "transposes": transposes,
        "programs": programs,
        "digest_fields": digest_report,
        "findings": [f.to_json() for f in findings],
        "ok": not findings,
    }
    return findings, inv


# --- baseline plumbing (same contract as kernelcheck) ------------------------

def structural_view(inv: dict) -> dict:
    """The gate-comparable subtree: verified transpose signatures, each
    program's RNG-primitive multiset and collective routes, and the
    digest field classification/movement — everything that should only
    change via a reviewed baseline re-commit. Drops volatile fields
    (jax_version, findings, ok flags)."""
    return {
        "roots": inv.get("roots", []),
        "transposes": inv.get("transposes", {}),
        "programs": {
            label: {"program": p.get("program"),
                    "topology": p.get("topology"),
                    "rng_prims": p.get("rng_prims", {}),
                    "all_to_all": p.get("all_to_all", []),
                    "invars": p.get("invars")}
            for label, p in inv.get("programs", {}).items()},
        "digest_fields": inv.get("digest_fields", {}),
    }


def diff_paths(base: dict, new: dict) -> list:
    from repro.analysis.kernelcheck import diff_paths as _dp
    return _dp(base, new)
