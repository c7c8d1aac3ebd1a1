"""graftlint-dep: abstract row-dependence certification over kernel jaxprs.

ROADMAP item 2 (the incremental dirty-row solve) rests on one property:
per-row kernel outputs depend only on that row's inputs plus replicated
state, so untouched rows can be replayed instead of re-solved. This tier
makes that property machine-checked. For every entry point in the IR
tier's ``ENTRY_POINTS`` registry it runs an abstract interpretation over
the jaxpr the IR tier already traces, propagating which batch-axis rows
of which inputs each value depends on — through element-wise ops,
per-row gathers, reshapes and nested jits — and flagging the cross-row
couplers (sorts, cumulative scans, global reductions, row-axis
contractions, data-dependent scatters).

The per-value lattice (``RowDep.kind``):

- ``repl``    — no dependence on any row of any row-arg (replicated
  state, constants, iota).
- ``row``     — element at row *i* depends only on row *i + off* of the
  row-args (``off`` 0 for the aligned case; a non-zero static offset is
  a PROVEN delta-safety violation at an output).
- ``mixed``   — row-dependent but alignment is lost (data-dependent row
  selection, windowed scans, row-axis concatenation). Not a proof in
  either direction: a ``mixed`` output neither certifies independence
  nor convicts coupling.
- ``coupled`` — PROVEN cross-row information flow (a sort/cumsum/global
  reduction along the row axis, a row-axis contraction, a data-dependent
  scatter). ``reasons`` names the couplers.

Findings only ever come from PROOFS (IR006 fires on a contradicted
declaration, never on ``mixed``), so unknown primitives degrade to
``mixed`` — conservative, sound both directions.

Two rule families consume the analysis (deprules.py): IR006
row-independence certification against the explicit ``row_coupled``
declarations every registered kernel must carry, and IR007 replicated-
scan discipline over the sharded spec variants (the PR 9 CPU-SPMD
miscompile class: a cross-row coupler consuming operands that were not
re-replicated).

Run it:

    python -m tools.graftlint --dep                  # full registry
    python -m tools.graftlint --dep divide_replicas  # one family
    python -m tools.graftlint --all                  # AST + IR + dep
    python -m tools.graftlint.dep                    # debug verdict dump

Like the IR tier, tracing is abstract (``jax.make_jaxpr`` over
``ShapeDtypeStruct``s, no compiles) and the analysis itself is pure
Python over the jaxpr — the full grid runs in seconds and is a tier-1
gate (tests/test_graftlint_dep.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from . import deprules  # noqa: F401 — registers the IR006/IR007 analyzers
from .core import DEP_RULES, apply_baseline, default_config

# --------------------------------------------------------------------------
# the lattice
# --------------------------------------------------------------------------

_ORDER = {"repl": 0, "row": 1, "mixed": 2, "coupled": 3}


@dataclass(frozen=True)
class RowDep:
    """Abstract row-dependence of one jaxpr value (see module docstring).

    ``plane`` carries the flat input positions of declared plane-state
    args the value depends on (any kind — the first_fit_group cohort
    channel); ``repl_ok`` is the IR007 mark: True while every row-
    dependent ancestor has been re-replicated (or never sharded)."""

    kind: str = "repl"
    axis: int = -1
    off: object = 0
    reasons: frozenset = frozenset()
    plane: frozenset = frozenset()
    repl_ok: bool = True

    @property
    def row_dependent(self) -> bool:
        return self.kind != "repl"


REPL = RowDep()


def row(axis: int, off: object = 0, *, plane=frozenset(), ok=True) -> RowDep:
    return RowDep("row", axis, off, plane=frozenset(plane), repl_ok=ok)


def mixed(src: RowDep = REPL, *more: RowDep) -> RowDep:
    """Row-dependent with alignment lost; keeps coupling + plane/mark."""
    states = (src,) + more
    if any(s.kind == "coupled" for s in states):
        return join(*states)
    return RowDep(
        "mixed",
        reasons=frozenset().union(*(s.reasons for s in states)),
        plane=frozenset().union(*(s.plane for s in states)),
        repl_ok=all(s.repl_ok for s in states),
    )


def coupled(reason: str, *srcs: RowDep) -> RowDep:
    return RowDep(
        "coupled",
        reasons=frozenset({reason}).union(*(s.reasons for s in srcs)),
        plane=frozenset().union(*(s.plane for s in srcs)),
        repl_ok=all(s.repl_ok for s in srcs) if srcs else True,
    )


def _offs_compat(a: object, b: object) -> Optional[bool]:
    """True = provably equal, False = provably different (both static
    ints), None = cannot tell (at least one symbolic token)."""
    if a == b:
        return True
    if isinstance(a, int) and isinstance(b, int):
        return False
    return None


def join(*states: RowDep, combine: bool = False) -> RowDep:
    """Least upper bound. ``combine=True`` is the element-wise dataflow
    product: two row-aligned operands with provably DIFFERENT static
    offsets couple neighbouring rows (the ``a[1:] - a[:-1]`` class),
    which a pure control-flow merge (select branches) does not."""
    states = [s for s in states if s is not None]
    if not states:
        return REPL
    plane = frozenset().union(*(s.plane for s in states))
    reasons = frozenset().union(*(s.reasons for s in states))
    ok = all(s.repl_ok for s in states)
    top = max(states, key=lambda s: _ORDER[s.kind])
    if top.kind == "coupled":
        return RowDep("coupled", reasons=reasons, plane=plane, repl_ok=ok)
    rows = [s for s in states if s.kind == "row"]
    if top.kind == "row":
        axes = {s.axis for s in rows}
        if len(axes) == 1:
            offs = {s.off for s in rows}
            if len(offs) == 1:
                return RowDep("row", rows[0].axis, rows[0].off,
                              reasons=reasons, plane=plane, repl_ok=ok)
            compat = None
            for s in rows[1:]:
                compat = _offs_compat(rows[0].off, s.off)
                if compat is False:
                    break
            if compat is False and combine:
                return RowDep("coupled",
                              reasons=reasons | {"shifted-combine"},
                              plane=plane, repl_ok=ok)
        return RowDep("mixed", reasons=reasons, plane=plane, repl_ok=ok)
    if top.kind == "mixed":
        return RowDep("mixed", reasons=reasons, plane=plane, repl_ok=ok)
    return RowDep("repl", plane=plane, repl_ok=ok) if (plane or not ok) \
        else REPL


def _shift_off(off: object, delta: int) -> object:
    if delta == 0:
        return off
    if isinstance(off, int):
        return off + delta
    return ("add", off, delta)


# --------------------------------------------------------------------------
# coupler events (IR007 inputs)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class CouplerEvent:
    """One cross-row coupler the analysis walked through: ``proven``
    marks a definite row-axis coupler (vs a coupler-class op over a
    ``mixed`` value that MIGHT span rows); ``replicated_ok`` is False
    when a row-sharded, never-re-replicated value feeds it (the PR 9
    miscompile precondition IR007 fires on)."""

    prim: str
    reason: str
    proven: bool
    replicated_ok: bool


# --------------------------------------------------------------------------
# the abstract interpreter
# --------------------------------------------------------------------------

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "and", "or", "xor",
    "not", "neg", "sign", "abs", "eq", "ne", "ge", "gt", "le", "lt",
    "select_n", "convert_element_type", "shift_left",
    "shift_right_arithmetic", "shift_right_logical", "clamp", "pow",
    "integer_pow", "exp", "log", "sqrt", "rsqrt", "floor", "ceil",
    "round", "logistic", "tanh", "erf", "erf_inv", "is_finite",
    "nextafter", "copy", "stop_gradient", "real", "imag",
    "population_count", "clz", "le_to", "lt_to", "square", "atan2",
    "expm1", "log1p", "rev_dummy",
})

_CUMULATIVE = frozenset({
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
})

_REDUCES = frozenset({
    "reduce_sum", "reduce_prod", "reduce_max", "reduce_min", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
})

_SCATTERS = frozenset({
    "scatter", "scatter-add", "scatter-mul", "scatter-min", "scatter-max",
})


def _aval(v):
    return getattr(v, "aval", None)


def _shape(v) -> tuple:
    aval = _aval(v)
    return tuple(getattr(aval, "shape", ()) or ())


def _is_literal(v) -> bool:
    return not hasattr(v, "count") and hasattr(v, "val")


class _Analyzer:
    """One jaxpr walk. ``env`` maps jaxpr Vars to RowDep states; ``vn``
    value-numbers scalar index computations so two eqns computing the
    same start offset (``i * chunk`` twice) share one symbolic token."""

    def __init__(self, events: list, sharded: bool, depth: int = 0):
        self.events = events
        self.sharded = sharded
        self.depth = depth
        self.env: dict = {}
        self.vn: dict = {}
        self._vn_next = 0

    # -- environment -------------------------------------------------------

    def read(self, v) -> RowDep:
        if _is_literal(v):
            return REPL
        return self.env.get(v, REPL)

    def write(self, v, state: RowDep) -> None:
        if not _shape(v) and state.kind == "row":
            # a scalar has no row axis: a row-state reduced to rank 0
            # means one row was selected data-dependently
            state = mixed(state)
        self.env[v] = state

    def token(self, v) -> object:
        """Value number of a (scalar) var: literals by value, vars by a
        structural hash of the producing eqn so CSE-equivalent index
        arithmetic compares equal."""
        if _is_literal(v):
            val = v.val
            try:
                return int(val)
            except (TypeError, ValueError):
                return ("lit", repr(val))
        if v in self.vn:
            return self.vn[v]
        self._vn_next += 1
        tok = ("var", self.depth, self._vn_next)
        self.vn[v] = tok
        return tok

    def _number_eqn(self, eqn) -> None:
        """Forward value numbering: outvars of structurally identical
        eqns over identically-numbered operands share a token."""
        try:
            params = tuple(sorted(
                (k, repr(val)) for k, val in eqn.params.items()
                if not hasattr(val, "jaxpr")
                and not isinstance(val, (tuple, list))
            ))
        except Exception:  # noqa: BLE001 — numbering is best-effort
            return
        key = (eqn.primitive.name, params,
               tuple(self.token(v) for v in eqn.invars))
        for i, ov in enumerate(eqn.outvars):
            self.vn[ov] = ("eqn", key, i)

    def event(self, prim: str, reason: str, proven: bool, *srcs: RowDep):
        ok = all(s.repl_ok or not s.row_dependent for s in srcs)
        self.events.append(CouplerEvent(prim, reason, proven, ok))

    # -- the walk ----------------------------------------------------------

    def run(self, jaxpr, in_states: list) -> list:
        for cv in jaxpr.constvars:
            self.env[cv] = REPL
        for v, s in zip(jaxpr.invars, in_states):
            self.env[v] = s
        for eqn in jaxpr.eqns:
            self._number_eqn(eqn)
            self.eqn(eqn)
        return [self.read(v) for v in jaxpr.outvars]

    def sub(self, closed, in_states: list) -> list:
        """Recurse into a closed subjaxpr sharing events + numbering
        scope (tokens are depth-tagged, so inner vars never alias)."""
        inner = _Analyzer(self.events, self.sharded, self.depth + 1)
        inner.vn = self.vn
        inner._vn_next = self._vn_next
        out = inner.run(closed.jaxpr, in_states)
        self._vn_next = inner._vn_next
        return out

    def eqn(self, eqn) -> None:
        name = eqn.primitive.name
        handler = getattr(self, "_p_" + name.replace("-", "_"), None)
        states = [self.read(v) for v in eqn.invars]
        if handler is not None:
            handler(eqn, states)
        elif name in _ELEMENTWISE:
            self._write_all(eqn, join(*states, combine=True))
        elif name in _CUMULATIVE:
            self._cumulative(eqn, states)
        elif name in _REDUCES:
            self._reduce(eqn, states)
        elif name in _SCATTERS:
            self._scatter(eqn, states)
        else:
            # unknown primitive: recurse into any subjaxpr params, else
            # degrade row-dependent inputs to mixed (sound: proofs never
            # come from unknowns)
            subs = [val for val in eqn.params.values()
                    if hasattr(val, "jaxpr")]
            if len(subs) == 1 and len(subs[0].jaxpr.invars) == len(states):
                out = self.sub(subs[0], states)
                for v, s in zip(eqn.outvars, out):
                    self.write(v, s)
                return
            self._write_all(eqn, self._conservative(states))

    def _write_all(self, eqn, state: RowDep) -> None:
        for v in eqn.outvars:
            self.write(v, state)

    @staticmethod
    def _conservative(states: list) -> RowDep:
        st = join(*states)
        return mixed(st) if st.kind == "row" else st

    # -- structural primitives ---------------------------------------------

    def _p_iota(self, eqn, states):
        self._write_all(eqn, REPL)

    def _p_broadcast_in_dim(self, eqn, states):
        st = states[0]
        if st.kind == "row":
            bd = tuple(eqn.params.get("broadcast_dimensions", ()))
            if st.axis < len(bd):
                st = RowDep("row", bd[st.axis], st.off, st.reasons,
                            st.plane, st.repl_ok)
            else:
                st = mixed(st)
        self._write_all(eqn, st)

    def _p_reshape(self, eqn, states):
        st = states[0]
        if eqn.params.get("dimensions") is not None:
            st = mixed(st) if st.kind == "row" else st
        elif st.kind == "row":
            old = _shape(eqn.invars[0])
            new = _shape(eqn.outvars[0])
            st = self._remap_reshape(st, old, new)
        self._write_all(eqn, st)

    @staticmethod
    def _remap_reshape(st: RowDep, old: tuple, new: tuple) -> RowDep:
        """The row axis survives a reshape iff an output axis has the
        same extent at the same leading-stride position (prefix products
        match) — merging the row axis with a neighbour loses it."""
        if st.axis >= len(old):
            return mixed(st)
        prefix = 1
        for d in old[:st.axis]:
            prefix *= d
        extent = old[st.axis]
        acc = 1
        for i, d in enumerate(new):
            if acc == prefix and d == extent:
                # the dims after must also multiply out (always true
                # when total sizes agree, which reshape guarantees)
                return RowDep("row", i, st.off, st.reasons, st.plane,
                              st.repl_ok)
            acc *= d
            if acc > prefix:
                break
        return mixed(st)

    def _p_squeeze(self, eqn, states):
        st = states[0]
        if st.kind == "row":
            dims = sorted(eqn.params.get("dimensions", ()))
            if st.axis in dims:
                st = mixed(st)  # size-1 row axis squeezed away
            else:
                shift = sum(1 for d in dims if d < st.axis)
                st = RowDep("row", st.axis - shift, st.off, st.reasons,
                            st.plane, st.repl_ok)
        self._write_all(eqn, st)

    def _p_expand_dims(self, eqn, states):
        st = states[0]
        if st.kind == "row":
            dims = sorted(eqn.params.get("dimensions", ()))
            ax = st.axis
            for d in dims:
                if d <= ax:
                    ax += 1
            st = RowDep("row", ax, st.off, st.reasons, st.plane,
                        st.repl_ok)
        self._write_all(eqn, st)

    def _p_transpose(self, eqn, states):
        st = states[0]
        if st.kind == "row":
            perm = tuple(eqn.params.get("permutation", ()))
            if st.axis in perm:
                st = RowDep("row", perm.index(st.axis), st.off,
                            st.reasons, st.plane, st.repl_ok)
            else:
                st = mixed(st)
        self._write_all(eqn, st)

    def _p_slice(self, eqn, states):
        st = states[0]
        if st.kind == "row":
            starts = tuple(eqn.params.get("start_indices", ()))
            strides = eqn.params.get("strides") or (1,) * len(starts)
            if st.axis < len(starts):
                if strides[st.axis] != 1:
                    st = mixed(st)
                elif starts[st.axis]:
                    st = RowDep("row", st.axis,
                                _shift_off(st.off, int(starts[st.axis])),
                                st.reasons, st.plane, st.repl_ok)
        self._write_all(eqn, st)

    def _p_pad(self, eqn, states):
        st = join(states[0], states[1] if len(states) > 1 else REPL)
        base = states[0]
        if base.kind == "row":
            cfg = tuple(eqn.params.get("padding_config", ()))
            if base.axis < len(cfg):
                lo, _hi, interior = cfg[base.axis]
                if interior:
                    st = mixed(base)
                elif lo:
                    st = RowDep("row", base.axis,
                                _shift_off(base.off, -int(lo)),
                                base.reasons, base.plane, base.repl_ok)
                else:
                    st = base
            else:
                st = base
        self._write_all(eqn, st)

    def _p_concatenate(self, eqn, states):
        dim = eqn.params.get("dimension", 0)
        st = join(*states)
        if any(s.kind == "row" and s.axis == dim for s in states):
            st = mixed(*states)  # rows re-indexed by the stacking
        self._write_all(eqn, st)

    def _p_rev(self, eqn, states):
        st = states[0]
        if st.kind == "row" and st.axis in tuple(
            eqn.params.get("dimensions", ())
        ):
            st = mixed(st)
        self._write_all(eqn, st)

    # -- couplers ----------------------------------------------------------

    def _cumulative(self, eqn, states):
        axis = eqn.params.get("axis", 0)
        st = states[0]
        name = eqn.primitive.name
        if st.kind == "row" and st.axis == axis:
            self.event(name, f"{name}[axis={axis}]", True, st)
            self._write_all(eqn, coupled(name, st))
        elif st.kind == "mixed":
            self.event(name, f"{name}[axis={axis}] over mixed", False, st)
            self._write_all(eqn, st)
        else:
            self._write_all(eqn, st)

    def _reduce(self, eqn, states):
        axes = tuple(eqn.params.get("axes", ()))
        st = states[0]
        name = eqn.primitive.name
        if st.kind == "row":
            if st.axis in axes:
                self.event(name, f"{name}[axes={axes}]", True, st)
                self._write_all(eqn, coupled(name, st))
            else:
                shift = sum(1 for a in axes if a < st.axis)
                self._write_all(eqn, RowDep(
                    "row", st.axis - shift, st.off, st.reasons, st.plane,
                    st.repl_ok,
                ))
        else:
            self._write_all(eqn, st)

    def _p_sort(self, eqn, states):
        dim = eqn.params.get("dimension", -1)
        st = join(*states)
        rowish = [s for s in states if s.kind == "row" and s.axis == dim]
        if rowish:
            self.event("sort", f"sort[dimension={dim}]", True, *states)
            st = coupled("sort", *states)
        elif st.kind == "mixed":
            self.event("sort", f"sort[dimension={dim}] over mixed",
                       False, *states)
        self._write_all(eqn, st)

    def _p_top_k(self, eqn, states):
        st = states[0]
        last = len(_shape(eqn.invars[0])) - 1
        if st.kind == "row" and st.axis == last:
            self.event("top_k", "top_k over the row axis", True, st)
            st = coupled("top_k", st)
        elif st.kind == "mixed":
            self.event("top_k", "top_k over mixed", False, st)
        self._write_all(eqn, st)

    def _p_dot_general(self, eqn, states):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lc, rc, lb, rb = tuple(lc), tuple(rc), tuple(lb), tuple(rb)
        lhs, rhs = states[0], states[1]
        for st, contract in ((lhs, lc), (rhs, rc)):
            if st.kind == "row" and st.axis in contract:
                self.event("dot_general", "contraction over the row axis",
                           True, lhs, rhs)
                self._write_all(eqn, coupled("dot_general", lhs, rhs))
                return
        # output layout: batch dims, then lhs free dims, then rhs free
        # dims. A row axis on exactly one side's batch/free dims keeps
        # alignment; row axes on BOTH sides is an outer product of rows
        # our single-axis state cannot represent — degrade to mixed.
        lhs_free = [d for d in range(len(_shape(eqn.invars[0])))
                    if d not in lc and d not in lb]
        rhs_free = [d for d in range(len(_shape(eqn.invars[1])))
                    if d not in rc and d not in rb]
        out = []
        for st, batch, free, base in (
            (lhs, lb, lhs_free, len(lb)),
            (rhs, rb, rhs_free, len(lb) + len(lhs_free)),
        ):
            if st.kind != "row":
                out.append(st)
            elif st.axis in batch:
                out.append(RowDep("row", batch.index(st.axis), st.off,
                                  st.reasons, st.plane, st.repl_ok))
            elif st.axis in free:
                out.append(RowDep("row", base + free.index(st.axis),
                                  st.off, st.reasons, st.plane,
                                  st.repl_ok))
            else:
                out.append(mixed(st))
        if all(s.kind == "row" for s in out) and \
                out[0].axis != out[1].axis:
            out = [mixed(*out)]
        self._write_all(eqn, join(*out, combine=True))

    def _p_gather(self, eqn, states):
        operand, indices = states[0], states[1]
        dn = eqn.params.get("dimension_numbers")
        out_rank = len(_shape(eqn.outvars[0]))
        offset_dims = tuple(getattr(dn, "offset_dims", ()))
        start_map = tuple(getattr(dn, "start_index_map", ()))
        op_batch = tuple(getattr(dn, "operand_batching_dims", ()))
        collapsed = tuple(getattr(dn, "collapsed_slice_dims", ()))
        slice_sizes = tuple(eqn.params.get("slice_sizes", ()))
        batch_out = [d for d in range(out_rank) if d not in offset_dims]

        def idx_out_state(idx_st: RowDep) -> RowDep:
            # indices row axis -> the matching output batch dim (index
            # axes map to output batch dims in order, minus the trailing
            # index-vector axis)
            if idx_st.kind != "row":
                return idx_st if idx_st.kind != "repl" else REPL
            if idx_st.axis < len(batch_out):
                return RowDep("row", batch_out[idx_st.axis], idx_st.off,
                              idx_st.reasons, idx_st.plane,
                              idx_st.repl_ok)
            return mixed(idx_st)

        if operand.kind == "repl":
            self._write_all(eqn, join(idx_out_state(indices), RowDep(
                "repl", plane=operand.plane, repl_ok=operand.repl_ok,
            )))
            return
        if operand.kind == "coupled" or indices.kind == "coupled":
            self._write_all(eqn, join(operand, indices))
            return
        if operand.kind == "row":
            ax = operand.axis
            if ax in op_batch:
                # per-row gather (the vmap form): operand row axis is a
                # batching dim — row identity carried by the indices'
                # own batching axis; output stays row-aligned when the
                # indices are row-aligned or replicated
                ib = idx_out_state(indices)
                pos = op_batch.index(ax)
                tgt = batch_out[pos] if pos < len(batch_out) else None
                base = RowDep("row", tgt, operand.off, operand.reasons,
                              operand.plane, operand.repl_ok) \
                    if tgt is not None else mixed(operand)
                self._write_all(eqn, join(base, ib))
                return
            if ax in start_map:
                # gathering ACROSS rows: data-dependent row selection
                self._write_all(eqn, mixed(operand, indices))
                return
            if ax not in collapsed and ax < len(slice_sizes) and \
                    slice_sizes[ax] == _shape(eqn.invars[0])[ax]:
                # full slice along the row axis: row axis maps into the
                # offset dims (its rank among non-collapsed slice dims)
                kept = [d for d in range(len(slice_sizes))
                        if d not in collapsed and d not in op_batch]
                if ax in kept and kept.index(ax) < len(offset_dims):
                    tgt = offset_dims[kept.index(ax)]
                    self._write_all(eqn, join(
                        RowDep("row", tgt, operand.off, operand.reasons,
                               operand.plane, operand.repl_ok),
                        idx_out_state(indices),
                    ))
                    return
            self._write_all(eqn, mixed(operand, indices))
            return
        self._write_all(eqn, mixed(operand, indices))

    def _scatter(self, eqn, states):
        operand, indices, updates = states[0], states[1], states[2]
        name = eqn.primitive.name
        dn = eqn.params.get("dimension_numbers")
        addressed = tuple(
            getattr(dn, "scatter_dims_to_operand_dims", ())
        )
        if indices.row_dependent and operand.kind == "row" and \
                operand.axis in addressed:
            # data-dependent placement INTO the row axis of existing
            # row state: changing one row of the index input moves
            # another row's data — proven cross-row flow (scatter_rows)
            self.event(name, "data-dependent scatter into the row axis",
                       True, *states)
            self._write_all(eqn, coupled("scatter", *states))
            return
        if indices.row_dependent:
            # data-dependent placement into a fresh/replicated buffer:
            # usually per-row via an iota index component, but the
            # component structure is lost in the fused index array —
            # alignment unprovable either way
            self._write_all(eqn, mixed(operand, indices, updates))
            return
        self._write_all(eqn, self._conservative(states))

    # -- dynamic slicing ---------------------------------------------------

    def _p_dynamic_slice(self, eqn, states):
        operand = states[0]
        starts = eqn.invars[1:]
        start_states = states[1:]
        st = operand
        if operand.kind == "row":
            shape = _shape(eqn.invars[0])
            sizes = tuple(eqn.params.get("slice_sizes",
                                         _shape(eqn.outvars[0])))
            ax = operand.axis
            sv = starts[ax] if ax < len(starts) else None
            tok = self.token(sv) if sv is not None else 0
            if tok == 0 and ax < len(sizes) and sizes[ax] == shape[ax]:
                pass  # identity along the row axis
            else:
                st = RowDep("row", ax, _shift_off(operand.off, 0)
                            if tok == 0 else ("dyn", tok, operand.off),
                            operand.reasons, operand.plane,
                            operand.repl_ok)
        taint = join(*start_states) if start_states else REPL
        if taint.row_dependent:
            st = mixed(st, taint)
        else:
            st = join(st, taint) if taint.plane or not taint.repl_ok \
                else st
        self._write_all(eqn, st)

    def _p_dynamic_update_slice(self, eqn, states):
        operand, update = states[0], states[1]
        start_states = states[2:]
        starts = eqn.invars[2:]
        taint = join(*start_states) if start_states else REPL
        same_shape = _shape(eqn.invars[0]) == _shape(eqn.invars[1])
        all_zero = all(
            self.token(s) == 0 for s in starts
        ) if starts else True
        if taint.row_dependent:
            st = mixed(operand, update, taint)
        elif same_shape and all_zero:
            st = join(operand, update, combine=True)
        else:
            st = self._conservative([operand, update, taint])
        self._write_all(eqn, st)

    # -- sharding / control flow -------------------------------------------

    def _p_sharding_constraint(self, eqn, states):
        st = states[0]
        sharding = eqn.params.get("sharding")
        fully_repl = bool(getattr(sharding, "is_fully_replicated", False))
        self._write_all(eqn, RowDep(
            st.kind, st.axis, st.off, st.reasons, st.plane, fully_repl,
        ))

    def _p_jit(self, eqn, states):
        closed = eqn.params.get("jaxpr")
        if closed is None:
            self._write_all(eqn, self._conservative(states))
            return
        out = self.sub(closed, states)
        for v, s in zip(eqn.outvars, out):
            self.write(v, s)

    _p_closed_call = _p_jit
    _p_core_call = _p_jit
    _p_remat = _p_jit

    def _p_custom_jvp_call(self, eqn, states):
        closed = eqn.params.get("call_jaxpr") or eqn.params.get("jaxpr")
        if closed is None or not hasattr(closed, "jaxpr"):
            self._write_all(eqn, self._conservative(states))
            return
        out = self.sub(closed, states)
        for v, s in zip(eqn.outvars, out):
            self.write(v, s)

    _p_custom_vjp_call = _p_custom_jvp_call
    _p_custom_vjp_call_jaxpr = _p_custom_jvp_call

    def _p_cond(self, eqn, states):
        branches = eqn.params.get("branches", ())
        idx_state, op_states = states[0], states[1:]
        outs = None
        for br in branches:
            bout = self.sub(br, list(op_states))
            outs = bout if outs is None else [
                join(a, b) for a, b in zip(outs, bout)
            ]
        if outs is None:
            self._write_all(eqn, self._conservative(states))
            return
        for v, s in zip(eqn.outvars, outs):
            self.write(v, join(s, idx_state) if idx_state.row_dependent
                       or idx_state.plane or not idx_state.repl_ok else s)

    def _p_while(self, eqn, states):
        body = eqn.params.get("body_jaxpr")
        cond = eqn.params.get("cond_jaxpr")
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        if body is None:
            self._write_all(eqn, self._conservative(states))
            return
        cconsts = states[:cn]
        bconsts = states[cn:cn + bn]
        carry = list(states[cn + bn:])
        # the join is monotone in every dimension (kind climbs, offset
        # divergence climbs to mixed, plane/reasons grow, repl_ok only
        # drops), so the fixpoint terminates; the cap is defensive
        for _ in range(32):
            out = self.sub(body, bconsts + carry)
            nxt = [join(a, b) for a, b in zip(carry, out)]
            if nxt == carry:
                break
            carry = nxt
        else:
            carry = [mixed(s) if s.kind == "row" else s for s in carry]
        cond_taint = join(*(cconsts or [REPL]))
        for v, s in zip(eqn.outvars, carry):
            self.write(v, mixed(s, cond_taint)
                       if cond_taint.row_dependent else s)

    def _p_scan(self, eqn, states):
        closed = eqn.params.get("jaxpr")
        n_consts = eqn.params.get("num_consts", 0)
        n_carry = eqn.params.get("num_carry", 0)
        if closed is None:
            self._write_all(eqn, self._conservative(states))
            return
        consts = states[:n_consts]
        carry = list(states[n_consts:n_consts + n_carry])
        xs = states[n_consts + n_carry:]
        # per-iteration slices of the xs: scanning over a row axis feeds
        # one row per step — inside the body that value is row-blind,
        # but any flow into the carry is a sequential cross-row
        # accumulation (the prefix-scan pattern), which we prove by
        # tainting the body-level x states and watching the carry.
        xs_body = []
        scanned_rows = False
        for s in xs:
            if s.kind == "row" and s.axis == 0:
                scanned_rows = True
                xs_body.append(RowDep("row", -2, s.off, s.reasons,
                                      s.plane, s.repl_ok))
            elif s.kind == "row":
                xs_body.append(RowDep("row", s.axis - 1, s.off, s.reasons,
                                      s.plane, s.repl_ok))
            else:
                xs_body.append(s)
        for _ in range(32):  # monotone join: terminates (see _p_while)
            out = self.sub(closed, consts + carry + xs_body)
            carry_out = out[:n_carry]
            nxt = [join(a, b) for a, b in zip(carry, carry_out)]
            if nxt == carry:
                break
            carry = nxt
        out = self.sub(closed, consts + carry + xs_body)
        carry_out, ys = out[:n_carry], out[n_carry:]
        if scanned_rows:
            # row data flowing into the carry = proven sequential
            # coupling across rows
            carry_final = []
            for s in carry_out:
                if s.row_dependent:
                    self.event("scan", "row data accumulated through the "
                               "scan carry", True, s)
                    carry_final.append(coupled("scan-carry", s))
                else:
                    carry_final.append(s)
            ys_final = []
            for s in ys:
                if s.kind == "row" and s.axis == -2:
                    # purely per-iteration output of a row scan: stacked
                    # back along the leading axis, row-aligned
                    ys_final.append(RowDep("row", 0, s.off, s.reasons,
                                           s.plane, s.repl_ok))
                elif s.row_dependent:
                    ys_final.append(mixed(s))
                else:
                    ys_final.append(s)
        else:
            # a non-row scan (fori_loop-style iteration): a FIXPOINT-
            # stable row carry is provably aligned at every step, so it
            # passes through; ys gain a leading iteration axis, shifting
            # a body-level row axis by one
            carry_final = list(carry_out)
            ys_final = [
                RowDep("row", s.axis + 1, s.off, s.reasons, s.plane,
                       s.repl_ok) if s.kind == "row" else s
                for s in ys
            ]
        for v, s in zip(eqn.outvars, carry_final + ys_final):
            self.write(v, s)


# --------------------------------------------------------------------------
# per-trace analysis + driver
# --------------------------------------------------------------------------


@dataclass
class DepAnalysis:
    """The dep tier's per-trace result the IR006/IR007 rules consume."""

    traced: object  # ir.TracedKernel
    out_states: list
    events: list
    sharded: bool
    error: Optional[str] = None

    @property
    def verdict(self) -> str:
        """'independent' (proven), 'coupled' (proven), or 'unproven'."""
        if self.error:
            return "unproven"
        kinds = {s.kind for s in self.out_states}
        if "coupled" in kinds:
            return "coupled"
        for s in self.out_states:
            if s.kind == "row" and isinstance(s.off, int) and s.off != 0:
                return "coupled"  # statically row-shifted output
        if kinds <= {"repl", "row"}:
            return "independent"
        return "unproven"

    @property
    def coupler_reasons(self) -> tuple:
        out = frozenset()
        for s in self.out_states:
            out |= s.reasons
        return tuple(sorted(out))

    @property
    def plane_deps(self) -> frozenset:
        return frozenset().union(*(s.plane for s in self.out_states)) \
            if self.out_states else frozenset()


def analyze_trace(traced) -> DepAnalysis:
    """Run the abstract interpretation over one TracedKernel."""
    entry = traced.entry
    sharded = traced.spec.statics.get("mesh") is not None
    events: list = []
    closed = traced.closed_jaxpr
    n_in = len(closed.jaxpr.invars)
    row_args = set(getattr(entry, "row_args", ()) or ())
    plane_args = set(getattr(entry, "plane_args", ()) or ())
    in_states = []
    for i in range(n_in):
        plane = frozenset({i}) if i in plane_args else frozenset()
        if i in row_args:
            in_states.append(RowDep("row", 0, 0, plane=plane,
                                    repl_ok=not sharded))
        else:
            in_states.append(RowDep("repl", plane=plane))
    try:
        out = _Analyzer(events, sharded).run(closed.jaxpr, in_states)
    except Exception as exc:  # noqa: BLE001 — an analyzer crash must
        # degrade to 'unproven', never abort the whole run
        return DepAnalysis(traced, [], events, sharded,
                           error=f"analysis failed: {exc!r}")
    return DepAnalysis(traced, out, events, sharded)


class DepContext:
    """Cross-rule state of one dep run (the IRContext analogue)."""

    def __init__(self, config, entries: dict, full_run: bool):
        self.config = config
        self.entries = entries
        self.full_run = full_run
        self.analyses: list = []  # DepAnalysis, trace order
        self.trace_failures: list = []  # (entry, spec, err)
        self._modinfos: dict = {}
        self._def_lines: dict = {}

    def by_entry(self) -> dict:
        out: dict = {}
        for a in self.analyses:
            out.setdefault(a.traced.entry.name, []).append(a)
        return out


def declared_row_coupled(entry) -> dict:
    """Every declaration surface for one entry: the registry field, the
    live function attribute, and (manifest kernels only) the prewarm
    name->row_coupled dict. Missing surfaces map to None."""
    from .ir import resolve_kernel

    out = {"registry": getattr(entry, "row_coupled", None)}
    try:
        fn = resolve_kernel(entry)
        out["kernel"] = getattr(fn, "row_coupled", None)
    except Exception as exc:  # noqa: BLE001 — surfaced by IR004 already
        out["kernel"] = None
        out["kernel_error"] = repr(exc)
    if entry.manifest_kernel:
        from karmada_tpu.scheduler import prewarm

        kernels = prewarm._KERNELS
        out["prewarm"] = (
            kernels.get(entry.manifest_kernel)
            if isinstance(kernels, dict) else None
        )
    return out


def run_dep(
    families=None,
    *,
    root=None,
    baseline="auto",
    entries: Optional[dict] = None,
):
    """One-call API behind ``--dep`` and the tier-1 gate — mirrors
    ``ir.run_ir``: ``families`` filters by entry name, ``entries``
    substitutes the registry wholesale (the seeded-mutant fixtures)."""
    from .ir import ENTRY_POINTS, IRContext, trace_spec

    config = default_config(root)
    registry = dict(entries) if entries is not None else dict(ENTRY_POINTS)
    full_run = entries is None and not families
    if families:
        unknown = sorted(set(families) - set(registry))
        if unknown:
            raise KeyError(
                f"unknown kernel families {unknown}; known: "
                f"{sorted(registry)}"
            )
        registry = {name: registry[name] for name in families}

    ctx = DepContext(config, registry, full_run)
    # reuse the IR tier's def-line/suppression machinery via a throwaway
    # IRContext (same config, same parsed-module cache semantics)
    irctx = IRContext(config, registry)
    ctx._ir = irctx
    for entry in registry.values():
        line = irctx.entry_line(entry)
        for spec in entry.make_specs():
            try:
                traced = trace_spec(entry, spec, line)
            except Exception as exc:  # noqa: BLE001 — IR004 territory;
                # the dep tier reports it as an unprovable entry
                ctx.trace_failures.append((entry, spec, repr(exc)))
                continue
            ctx.analyses.append(analyze_trace(traced))

    raw: list = []
    suppressed = 0
    seen: set = set()
    for r in DEP_RULES.values():
        found: list = []
        for a in ctx.analyses:
            found.extend(r.check(a, ctx))
        found.extend(r.finalize(ctx))
        for f in found:
            key = (f.identity, f.line)
            if key in seen:
                continue
            seen.add(key)
            mod = irctx.modinfo(f.path)
            if mod is not None and mod.suppressed(
                f.rule, f.line, f.anchor_line
            ):
                suppressed += 1
            else:
                raw.append(f)

    baseline_path = None
    if baseline == "auto":
        baseline_path = config.root / config.baseline_path
    elif baseline:
        baseline_path = config.root / baseline
    checked = len(ctx.analyses) + len(ctx.trace_failures)
    return apply_baseline(
        raw, baseline=baseline_path, checked_files=checked,
        suppressed=suppressed,
    )


# --------------------------------------------------------------------------
# the delta-safe registry surface (docs table + the future dirty-row solve)
# --------------------------------------------------------------------------


def delta_safe_registry(root=None) -> list:
    """Per-entry certification summary, the single source the generated
    DEVELOPMENT.md table renders from and the incremental solve will
    assert at arm time: ``delta_safe`` is True only for kernels DECLARED
    row-independent whose every spec variant the analyzer PROVES
    independent."""
    from .ir import ENTRY_POINTS, IRContext, trace_spec

    config = default_config(root)
    irctx = IRContext(config, dict(ENTRY_POINTS))
    rows = []
    for entry in ENTRY_POINTS.values():
        verdicts = []
        plane = frozenset()
        for spec in entry.make_specs():
            try:
                traced = trace_spec(entry, spec, irctx.entry_line(entry))
            except Exception:  # noqa: BLE001 — IR004's finding, not ours
                verdicts.append("unproven")
                continue
            a = analyze_trace(traced)
            verdicts.append(a.verdict)
            plane |= a.plane_deps
        if "coupled" in verdicts:
            verdict = "coupled"
        elif verdicts and all(v == "independent" for v in verdicts):
            verdict = "independent"
        else:
            verdict = "unproven"
        declared = getattr(entry, "row_coupled", None)
        rows.append({
            "name": entry.name,
            "family": entry.family,
            "row_coupled": declared,
            "verdict": verdict,
            "plane_coupled": bool(plane),
            "delta_safe": declared is False and verdict == "independent",
        })
    return rows


def render_delta_safe_table(root=None) -> str:
    rows = delta_safe_registry(root)
    out = [
        "| kernel | family | `row_coupled` | analyzer verdict | "
        "`delta_safe` |",
        "|---|---|---|---|---|",
    ]
    for r in rows:
        verdict = r["verdict"]
        if r["plane_coupled"]:
            verdict += " (plane-state input)"
        out.append(
            f"| `{r['name']}` | {r['family']} | `{r['row_coupled']}` | "
            f"{verdict} | {'yes' if r['delta_safe'] else 'no'} |"
        )
    return "\n".join(out)


def _debug_main() -> int:  # pragma: no cover — developer surface
    import sys

    from .ir import ENTRY_POINTS, IRContext, trace_spec

    config = default_config(None)
    irctx = IRContext(config, dict(ENTRY_POINTS))
    names = sys.argv[1:] or list(ENTRY_POINTS)
    for name in names:
        entry = ENTRY_POINTS[name]
        for spec in entry.make_specs():
            try:
                traced = trace_spec(entry, spec, irctx.entry_line(entry))
            except Exception as exc:  # noqa: BLE001
                print(f"{name}[{spec.variant}]: TRACE FAIL {exc!r}")
                continue
            a = analyze_trace(traced)
            outs = ",".join(s.kind for s in a.out_states)
            evs = "; ".join(
                f"{e.prim}:{e.reason}{'' if e.replicated_ok else ' !repl'}"
                for e in a.events
            )
            print(f"{name}[{spec.variant}]: {a.verdict} outs=[{outs}] "
                  f"plane={sorted(a.plane_deps)} "
                  f"reasons={a.coupler_reasons} "
                  f"{('events: ' + evs) if evs else ''} "
                  f"{('ERROR ' + a.error) if a.error else ''}")
    return 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(_debug_main())
