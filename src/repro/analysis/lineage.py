"""Input-lineage certification of the paper's algorithm class.

The lower bounds only bind algorithms in a restricted class: each
machine's local computation may read only its own feature block, and
every cross-machine combination must flow through the communicator
primitives (Arjevani–Shamir's formalization; Theorem 4 adds a per-round
payload restriction for incremental methods).  Under the local
placement a per-machine value is an array whose leading *machine axis*
has size ``m``; the communicators are the only code allowed to collapse
that axis.  This module runs an abstract interpretation over a traced
step jaxpr tracking, for every intermediate value, **which of its axes
are machine axes**:

* combining values along a machine axis (``reduce_sum`` over it, a
  ``dot_general`` contracting it, a cumulative/sort op along it)
  outside a comm scope is an out-of-band transfer (``class-oob``);
* slicing/gathering a machine axis down to a subset outside a comm
  scope reads another machine's partition (``class-leak``);
* a primitive whose machine-axis flow the interpreter cannot model is
  ``class-unknown`` — certification refuses to guess.

Inside a communicator's scope (``core.comm`` wraps every wire message
in a named scope) the same operations are precisely what a metered
message performs, so they are exempt and their results demote to
machine-independent ("global") values.

The audit instance pins ``m`` distinct from every other dimension
(``m=3`` against ``d=12``/``d_max=4``/``n=12``), so "an axis of size
m" identifies the machine axis unambiguously.
"""
from __future__ import annotations

from typing import Any, Dict, FrozenSet, List, Tuple

import jax.extend.core as jex

from .extract import TracedStep, comm_token, format_eqn, iter_eqns
from .findings import Finding

Dims = FrozenSet[int]
_EMPTY: Dims = frozenset()

# shape-preserving / elementwise primitives: output machine dims are the
# union of the (rank-aligned) operand machine dims
_ELEMENTWISE = {
    "add", "sub", "mul", "div", "rem", "pow", "atan2", "max", "min",
    "and", "or", "xor", "not", "neg", "sign", "floor", "ceil", "round",
    "abs", "exp", "exp2", "log", "log1p", "expm1", "sqrt", "rsqrt",
    "cbrt", "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh",
    "tanh", "asinh", "acosh", "atanh", "erf", "erfc", "erf_inv",
    "logistic", "integer_pow", "is_finite", "eq", "ne", "lt", "le",
    "gt", "ge", "select_n", "clamp", "nextafter", "square",
    "shift_left", "shift_right_logical", "shift_right_arithmetic",
    "population_count", "clz", "real", "imag", "conj",
}

# unary layout-preserving: out dims == in dims
_PASSTHROUGH = {
    "convert_element_type", "copy", "stop_gradient", "device_put",
    "reduce_precision", "rev",
}

_REDUCES = {"reduce_sum", "reduce_prod", "reduce_max", "reduce_min",
            "reduce_and", "reduce_or", "reduce_xor",
            "argmax", "argmin"}

# ordered/cumulative ops: along the machine axis they mix machines
_AXIS_OPS = {"cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
             "sort"}

# explicit cross-machine collectives (legal only inside comm scopes)
_COLLECTIVES = {"psum", "pmax", "pmin", "all_gather", "all_to_all",
                "ppermute", "psum_scatter", "pbroadcast", "axis_index",
                "reduce_scatter"}

_GLOBAL_SOURCES = {"iota", "rng_bit_generator", "threefry2x32",
                   "random_seed", "random_wrap", "random_bits",
                   "random_fold_in", "random_split"}

_CALL_JAXPR_PARAMS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


class _Unmodeled(Exception):
    pass


def _rank(v) -> int:
    return len(getattr(v.aval, "shape", ()))


def _shape(v) -> Tuple[int, ...]:
    return tuple(getattr(v.aval, "shape", ()))


class _Env:
    """Var -> machine-dim set (Literals are always global)."""

    def __init__(self) -> None:
        self._d: Dict[Any, Dims] = {}

    def read(self, v) -> Dims:
        if isinstance(v, jex.Literal):
            return _EMPTY
        return self._d.get(v, _EMPTY)

    def write(self, v, dims: Dims) -> bool:
        old = self._d.get(v)
        if old == dims:
            return False
        # joining states across fixpoint passes: union
        self._d[v] = dims if old is None else (old | dims)
        return True


def _union_elementwise(env: _Env, eqn) -> Dims:
    out_rank = _rank(eqn.outvars[0])
    dims: Dims = _EMPTY
    for v in eqn.invars:
        d = env.read(v)
        if not d:
            continue
        if _rank(v) != out_rank:
            raise _Unmodeled("rank-mismatched machine operand in "
                             "elementwise op")
        dims = dims | d
    return dims


def _dot_general(env: _Env, eqn, in_scope: bool) -> Tuple[Dims, str]:
    """Returns (out machine dims, violation kind or '')."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = eqn.invars
    ld, rd = env.read(lhs), env.read(rhs)
    if any(a in ld for a in lc) or any(a in rd for a in rc):
        if not in_scope:
            return _EMPTY, "contract"
        return _EMPTY, ""
    # output layout: batch dims, then lhs free, then rhs free
    out: set = set()
    for pos, (a, _) in enumerate(zip(lb, rb)):
        if a in ld or rb[pos] in rd:
            out.add(pos)
    nb = len(lb)
    lfree = [a for a in range(_rank(lhs)) if a not in lc and a not in lb]
    rfree = [a for a in range(_rank(rhs)) if a not in rc and a not in rb]
    for i, a in enumerate(lfree):
        if a in ld:
            out.add(nb + i)
    for i, a in enumerate(rfree):
        if a in rd:
            out.add(nb + len(lfree) + i)
    return frozenset(out), ""


def _remap_removed(dims: Dims, removed) -> Dims:
    rm = sorted(removed)
    out = set()
    for a in dims:
        if a in rm:
            continue
        out.add(a - sum(1 for r in rm if r < a))
    return frozenset(out)


def _reshape_dims(dims: Dims, shp_in, shp_out, m: int) -> Dims:
    """A machine dim survives a reshape iff an output axis of size m
    sits at the same flattened offset with the same surrounding
    products; otherwise the reshape merged machine data — unmodeled."""
    out = set()
    for a in dims:
        pre = 1
        for s in shp_in[:a]:
            pre *= s
        hit = None
        acc = 1
        for j, s in enumerate(shp_out):
            if acc == pre and s == m:
                hit = j
                break
            acc *= s
        if hit is None:
            raise _Unmodeled("reshape folds a machine axis into "
                             "neighboring dimensions")
        out.add(hit)
    return frozenset(out)


def _gather_dims(env: _Env, eqn, m: int) -> Tuple[Dims, str]:
    operand = eqn.invars[0]
    od = env.read(operand)
    if not od:
        return _EMPTY, ""
    dn = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    collapsed = set(dn.collapsed_slice_dims)
    batching = set(getattr(dn, "operand_batching_dims", ()))
    offset_dims = tuple(dn.offset_dims)
    # operand dims that survive into the output as offset dims, in order
    kept = [a for a in range(_rank(operand))
            if a not in collapsed and a not in batching]
    out = set()
    for a in sorted(od):
        if a in collapsed or slice_sizes[a] < m:
            return _EMPTY, "slice"
        if a in batching:
            raise _Unmodeled("gather batches over a machine axis")
        out.add(offset_dims[kept.index(a)])
    return frozenset(out), ""


def _call_jaxprs(eqn):
    for name in _CALL_JAXPR_PARAMS:
        if name in eqn.params:
            cj = eqn.params[name]
            return cj.jaxpr if hasattr(cj, "jaxpr") else cj
    return None


class ClassCertifier:
    """One abstract-interpretation pass over a traced step."""

    def __init__(self, m: int, algorithm: str = "",
                 placement: str = "local", channel: str = ""):
        self.m = m
        self.coords = dict(algorithm=algorithm, placement=placement,
                           channel=channel)
        self.findings: List[Finding] = []

    def _flag(self, code: str, msg: str, eqn, path: str) -> None:
        self.findings.append(Finding(
            code, "error", msg, eqn=format_eqn(eqn), path=path,
            **self.coords))

    # ---- the transfer function ------------------------------------------
    def _apply(self, env: _Env, eqn, path: str,
               ambient: bool = False) -> bool:
        prim = eqn.primitive.name
        # sub-jaxpr equations (cond branches, scan bodies) carry a name
        # stack relative to their caller, so a scope on the calling
        # equation covers everything nested under it (``ambient``)
        in_scope = ambient or comm_token(eqn) is not None
        changed = False

        def write_all(dims: Dims) -> None:
            nonlocal changed
            for ov in eqn.outvars:
                changed |= env.write(ov, dims)

        in_dims = [env.read(v) for v in eqn.invars]
        any_machine = any(in_dims)

        if prim in _COLLECTIVES:
            if not in_scope:
                self._flag("class-oob",
                           f"collective '{prim}' outside a communicator "
                           f"scope — cross-machine information flow the "
                           f"ledger never priced", eqn, path)
            write_all(_EMPTY)
            return changed
        if not any_machine:
            # machine data neither read nor fabricated (sources are
            # global): outputs are global; still recurse into sub-jaxprs
            # to catch scoped violations of nested machine values
            sub = _call_jaxprs(eqn)
            if sub is None and prim not in ("scan", "while", "cond"):
                write_all(_EMPTY)
                return changed

        try:
            if prim in _ELEMENTWISE:
                write_all(_union_elementwise(env, eqn))
            elif prim in _PASSTHROUGH:
                write_all(in_dims[0])
            elif prim in _GLOBAL_SOURCES:
                write_all(_EMPTY)
            elif prim in _REDUCES:
                axes = eqn.params.get("axes", ())
                dims = in_dims[0]
                hit = [a for a in axes if a in dims]
                if hit and not in_scope:
                    self._flag("class-oob",
                               f"'{prim}' collapses machine axis "
                               f"{hit[0]} outside a communicator scope",
                               eqn, path)
                write_all(_remap_removed(dims - frozenset(axes),
                                         axes))
            elif prim in _AXIS_OPS:
                ax = eqn.params.get("axis",
                                    eqn.params.get("dimension", None))
                dims = in_dims[0]
                if ax is not None and ax in dims and not in_scope:
                    self._flag("class-oob",
                               f"'{prim}' mixes values along machine "
                               f"axis {ax} outside a communicator "
                               f"scope", eqn, path)
                write_all(dims)
            elif prim == "dot_general":
                dims, viol = _dot_general(env, eqn, in_scope)
                if viol:
                    self._flag("class-oob",
                               "dot_general contracts a machine axis "
                               "outside a communicator scope", eqn,
                               path)
                write_all(dims)
            elif prim == "broadcast_in_dim":
                bd = eqn.params["broadcast_dimensions"]
                write_all(frozenset(bd[a] for a in in_dims[0]))
            elif prim == "reshape":
                write_all(_reshape_dims(in_dims[0], _shape(eqn.invars[0]),
                                        _shape(eqn.outvars[0]), self.m))
            elif prim == "transpose":
                perm = eqn.params["permutation"]
                write_all(frozenset(perm.index(a) for a in in_dims[0]))
            elif prim == "squeeze":
                write_all(_remap_removed(in_dims[0],
                                         eqn.params["dimensions"]))
            elif prim == "slice":
                dims = in_dims[0]
                starts = eqn.params["start_indices"]
                limits = eqn.params["limit_indices"]
                strides = eqn.params["strides"] or \
                    (1,) * len(starts)
                for a in sorted(dims):
                    kept = len(range(starts[a], limits[a], strides[a]))
                    if kept < self.m and not in_scope:
                        self._flag(
                            "class-leak",
                            f"slice keeps {kept} of {self.m} machines "
                            f"on axis {a} — local compute reading "
                            f"another machine's feature block", eqn,
                            path)
                write_all(dims)
            elif prim == "dynamic_slice":
                dims = in_dims[0]
                sizes = eqn.params["slice_sizes"]
                for a in sorted(dims):
                    if sizes[a] < self.m and not in_scope:
                        self._flag(
                            "class-leak",
                            f"dynamic_slice keeps {sizes[a]} of "
                            f"{self.m} machines on axis {a} — local "
                            f"compute reading another machine's "
                            f"feature block", eqn, path)
                write_all(dims)
            elif prim == "dynamic_update_slice":
                write_all(in_dims[0] | (in_dims[1]
                                        if _rank(eqn.invars[1])
                                        == _rank(eqn.invars[0])
                                        else _EMPTY))
            elif prim == "gather":
                dims, viol = _gather_dims(env, eqn, self.m)
                if viol and not in_scope:
                    self._flag("class-leak",
                               "gather selects a machine-axis subset — "
                               "local compute reading another "
                               "machine's feature block", eqn, path)
                write_all(dims)
            elif prim == "concatenate":
                ax = eqn.params["dimension"]
                dims: Dims = _EMPTY
                for d in in_dims:
                    if ax in d:
                        raise _Unmodeled("concatenate along a machine "
                                         "axis")
                    dims = dims | d
                write_all(dims)
            elif prim == "pad":
                dims = in_dims[0]
                cfg = eqn.params["padding_config"]
                for a in dims:
                    lo, hi, interior = cfg[a]
                    if lo or hi or interior:
                        raise _Unmodeled("pad alters a machine axis")
                write_all(dims)
            elif prim == "optimization_barrier":
                for iv, ov in zip(eqn.invars, eqn.outvars):
                    changed |= env.write(ov, env.read(iv))
            elif prim == "while":
                cn = eqn.params["cond_nconsts"]
                bn = eqn.params["body_nconsts"]
                changed |= self._fixpoint_call(
                    env, eqn, eqn.params["body_jaxpr"].jaxpr,
                    list(eqn.invars[cn:]), list(eqn.outvars), path,
                    skip_in=bn, ambient=in_scope)
                cond_j = eqn.params["cond_jaxpr"].jaxpr
                cond_dims = ([env.read(v) for v in eqn.invars[:cn]]
                             + [env.read(v)
                                for v in eqn.invars[cn + bn:]])
                self._run(_Env(), cond_j, path + ".cond.", cond_dims,
                          ambient=in_scope)
            elif prim == "cond":
                branches = eqn.params["branches"]
                op_dims = in_dims[1:]
                out_dims = [_EMPTY] * len(eqn.outvars)
                for bi, br in enumerate(branches):
                    sub = br.jaxpr if hasattr(br, "jaxpr") else br
                    outs = self._run(_Env(), sub,
                                     f"{path}.branches[{bi}].", op_dims,
                                     ambient=in_scope)
                    out_dims = [a | b for a, b in zip(out_dims, outs)]
                for ov, d in zip(eqn.outvars, out_dims):
                    changed |= env.write(ov, d)
            elif prim == "scan":
                changed |= self._scan(env, eqn, path, in_scope)
            else:
                sub = _call_jaxprs(eqn)
                if sub is not None:
                    outs = self._run(_Env(), sub, f"{path}.{prim}.",
                                     in_dims, ambient=in_scope)
                    for ov, d in zip(eqn.outvars, outs):
                        changed |= env.write(ov, d)
                elif any_machine:
                    raise _Unmodeled(f"no machine-axis rule for "
                                     f"primitive '{prim}'")
                else:
                    write_all(_EMPTY)
        except _Unmodeled as e:
            if in_scope:
                # inside a communicator scope the ops ARE the metered
                # message transform (e.g. the int8 quantizer's bitcast);
                # the whole scope is priced, so its values demote to
                # global rather than blocking certification
                write_all(_EMPTY)
            else:
                self._flag("class-unknown",
                           f"cannot certify past this equation: {e}",
                           eqn, path)
                write_all(_EMPTY)
        return changed

    def _scan(self, env: _Env, eqn, path: str,
              ambient: bool = False) -> bool:
        body = eqn.params["jaxpr"].jaxpr
        nc = eqn.params["num_consts"]
        ncar = eqn.params["num_carry"]
        in_dims = []
        for i, v in enumerate(eqn.invars):
            d = env.read(v)
            if i >= nc + ncar:
                if 0 in d:
                    raise _Unmodeled("scan iterates over a machine "
                                     "axis")
                d = frozenset(a - 1 for a in d)
            in_dims.append(d)
        # fixpoint over the carry
        for _ in range(4):
            outs = self._run(_Env(), body, f"{path}.body.", in_dims,
                             quiet=True, ambient=ambient)
            new_carry = [a | b for a, b in
                         zip(in_dims[nc:nc + ncar], outs[:ncar])]
            if new_carry == in_dims[nc:nc + ncar]:
                break
            in_dims[nc:nc + ncar] = new_carry
        outs = self._run(_Env(), body, f"{path}.body.", in_dims,
                         ambient=ambient)
        changed = False
        for i, ov in enumerate(eqn.outvars):
            if i < ncar:
                d = outs[i]
            else:
                d = frozenset(a + 1 for a in outs[i])
            changed |= env.write(ov, d)
        return changed

    def _fixpoint_call(self, env: _Env, eqn, body, invars, outvars,
                       path: str, skip_in: int,
                       ambient: bool = False) -> bool:
        in_dims = [env.read(v) for v in invars]
        for _ in range(4):
            outs = self._run(_Env(), body, f"{path}.body.", in_dims,
                             quiet=True, ambient=ambient)
            new_state = [a | b for a, b in
                         zip(in_dims[skip_in:], outs)]
            if new_state == in_dims[skip_in:]:
                break
            in_dims[skip_in:] = new_state
        outs = self._run(_Env(), body, f"{path}.body.", in_dims,
                         ambient=ambient)
        changed = False
        for ov, d in zip(outvars, outs):
            changed |= env.write(ov, d)
        return changed

    def _run(self, env: _Env, jaxpr, path: str,
             in_dims: List[Dims], quiet: bool = False,
             ambient: bool = False) -> List[Dims]:
        if quiet:
            saved = self.findings
            self.findings = []
        for v, d in zip(jaxpr.invars, in_dims):
            env.write(v, d)
        for i, eqn in enumerate(jaxpr.eqns):
            self._apply(env, eqn, f"{path}eqns[{i}]", ambient=ambient)
        outs = [env.read(v) for v in jaxpr.outvars]
        if quiet:
            self.findings = saved
        return outs

    # ---- entry point ----------------------------------------------------
    def certify_step(self, ts: TracedStep) -> List[Finding]:
        """Certify one traced step: consts/carry/xs classified by the
        audit-instance shape convention (leading axis of size m is the
        machine axis), then propagate."""
        jaxpr = ts.closed.jaxpr
        env = _Env()
        for cv, c in zip(jaxpr.constvars, ts.consts):
            shp = tuple(getattr(c, "shape", ()))
            env.write(cv, frozenset({0}) if shp and shp[0] == self.m
                      else _EMPTY)
        in_dims = []
        for v in jaxpr.invars:
            shp = _shape(v)
            in_dims.append(frozenset({0})
                           if shp and shp[0] == self.m else _EMPTY)
        n0 = len(self.findings)
        for v, d in zip(jaxpr.invars, in_dims):
            env.write(v, d)
        for i, eqn in enumerate(jaxpr.eqns):
            self._apply(env, eqn, f"eqns[{i}]")
        return self.findings[n0:]


def certify_sharded_class(closed, algorithm: str = "",
                          channel: str = "") -> List[Finding]:
    """Under the sharded placement machines are mesh shards, so the
    class boundary is syntactic: every collective primitive must sit
    inside a communicator scope.  The one exception is the in-scan
    measure of f(w_k) - f* (scope ``repro.gap``): its psums compute the
    gap series, which leaves the program as the scan's per-round output
    and never reaches the iterate, so they are measurement, not
    communication; each is reported as a ``class-measure`` info."""
    from ..core.engine import GAP_SCOPE
    out: List[Finding] = []
    for eqn, path in iter_eqns(closed.jaxpr):
        if eqn.primitive.name not in _COLLECTIVES \
                or comm_token(eqn) is not None:
            continue
        if GAP_SCOPE in str(eqn.source_info.name_stack).split("/"):
            out.append(Finding(
                "class-measure", "info",
                f"collective '{eqn.primitive.name}' of the in-scan "
                f"measure ({GAP_SCOPE}): measurement, not metered",
                eqn=format_eqn(eqn), path=path, algorithm=algorithm,
                placement="sharded", channel=channel))
            continue
        out.append(Finding(
            "class-oob", "error",
            f"collective '{eqn.primitive.name}' outside a "
            f"communicator scope — cross-machine information flow "
            f"the ledger never priced", eqn=format_eqn(eqn),
            path=path, algorithm=algorithm, placement="sharded",
            channel=channel))
    return out


def thm4_payload_findings(steps: List[TracedStep], program,
                          algorithm: str = "",
                          channel: str = "") -> List[Finding]:
    """Theorem 4's restriction on incremental algorithms: repeated
    (inner, count > 1) segments may ship only O(1) scalars per round —
    a vector payload in an inner round breaks the bound's premise."""
    out: List[Finding] = []
    for s, seg in enumerate(program.segments):
        if int(seg.count) <= 1:
            continue   # snapshot/full rounds may carry R^n payloads
        for ts in steps:
            if s not in ts.segments:
                continue
            for rec in ts.records:
                if tuple(rec.shape) != ():
                    out.append(Finding(
                        "thm4-payload", "error",
                        f"incremental inner segment {s} (count "
                        f"{seg.count}) ships a {rec.dtype}"
                        f"{tuple(rec.shape)} payload ({rec.tag!r}); "
                        f"Theorem 4 prices inner rounds as O(1) "
                        f"scalars", algorithm=algorithm,
                        placement="local", channel=channel))
            break
    return out


__all__ = ["ClassCertifier", "certify_sharded_class",
           "thm4_payload_findings"]
