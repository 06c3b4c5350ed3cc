"""Shardflow pass 1: abstract interpretation of a traced step jaxpr.

Walks every equation of a technique's traced ``train_step`` (abstract
values only — CPU, no chip) carrying a per-variable sharding spec, and
records every communication event GSPMD would have to materialize into a
:class:`CommLedger`: explicit collectives (``psum`` / ``all_gather`` /
``all_to_all`` / ``ppermute`` from shard_map techniques) are counted
directly, while for pjit/GSPMD techniques the collectives are *predicted*
from the propagation rules (GSPMD, arxiv 2105.04663):

- a dot_general contracting a dimension sharded the same way on both
  operands produces partial sums -> **all-reduce** of the output;
- a dot_general operand sharded on an axis the output cannot carry (the
  ZeRO-3 parameter pattern) is **all-gathered** first;
- a reduction over a sharded dimension -> **all-reduce**;
- a gather from an operand sharded on its indexed dimension (the
  vocab-sharded embedding) -> masked local gather + **all-reduce**;
- two genuinely conflicting shardings meeting in one elementwise op ->
  an **implicit reshard** (SAT-X001 material — never intended).

Known approximation (documented, tolerance-checked by the differential
test): the ZeRO gradient reduce-scatter is modelled as an all-reduce —
the byte totals differ by the well-known 2x ring factor, and XLA's
all-reduce combiner merges per-parameter collectives, so the ledger's
*per-class byte totals* are the comparable quantity, not raw op counts.

Wire bytes use the standard ring-algorithm cost factors over the axis
group size ``n``: all-reduce ``2(n-1)/n``, all-gather / reduce-scatter /
all-to-all ``(n-1)/n``, ppermute ``1``.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("saturn_tpu")

#: One sharding spec: per-dimension tuple of mesh axis names (empty tuple =
#: replicated along that dimension).
Spec = Tuple[Tuple[str, ...], ...]

_ELEMENTWISE = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "atan2",
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "logistic", "erf",
    "erfc", "erf_inv", "rsqrt", "sqrt", "cbrt", "neg", "abs", "sign",
    "floor", "ceil", "round", "sin", "cos", "tan", "asin", "acos", "atan",
    "sinh", "cosh", "convert_element_type", "integer_pow", "not", "and",
    "or", "xor", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "eq", "ne", "lt", "le", "gt", "ge",
    "select_n", "clamp", "nextafter", "is_finite", "stop_gradient",
    "copy", "real", "imag", "square", "logistic", "rng_uniform",
    "add_any",
})

_REDUCERS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin",
})

#: Wire-cost factor per collective class for an axis group of size n.
_WIRE_FACTOR = {
    "all_reduce": lambda n: 2.0 * (n - 1) / n,
    "all_gather": lambda n: (n - 1) / n,
    "reduce_scatter": lambda n: (n - 1) / n,
    "all_to_all": lambda n: (n - 1) / n,
    "ppermute": lambda n: 1.0,
    "reshard": lambda n: (n - 1) / n,
}


@dataclass
class CollectiveRecord:
    """One (possibly scan-repeated) communication event in the ledger."""

    op: str                    # all_reduce | all_gather | all_to_all |
    #                            ppermute | reduce_scatter | reshard
    axes: Tuple[str, ...]      # mesh axes the transfer spans
    bytes: int                 # logical payload bytes per occurrence
    wire_bytes: float          # ring-cost bytes per occurrence
    count: int                 # occurrences per step (scan trip counts folded)
    primitive: str             # jaxpr primitive that produced it
    provenance: str            # file:line-ish origin (source_info or eqn#)
    scan_depth: int = 0        # 0 = top level, >=1 = inside a scan body
    explicit: bool = False     # present in the jaxpr vs predicted by GSPMD

    def to_json(self) -> Dict[str, Any]:
        return {
            "op": self.op,
            "axes": list(self.axes),
            "bytes": self.bytes,
            "wire_bytes": round(self.wire_bytes, 1),
            "count": self.count,
            "primitive": self.primitive,
            "provenance": self.provenance,
            "scan_depth": self.scan_depth,
            "explicit": self.explicit,
        }


@dataclass
class CommLedger:
    """Per-collective communication ledger for one traced step."""

    records: List[CollectiveRecord] = field(default_factory=list)
    flops: float = 0.0         # dense dot_general flops per step (global)
    resharded: List[CollectiveRecord] = field(default_factory=list)
    replicated_intermediates: List[Tuple[int, str]] = field(
        default_factory=list
    )  # (bytes, provenance) of large fully-replicated eqn outputs

    def add(self, rec: CollectiveRecord) -> None:
        self.records.append(rec)
        if rec.op == "reshard":
            self.resharded.append(rec)

    def total_bytes(self) -> int:
        return sum(r.bytes * r.count for r in self.records)

    def total_wire_bytes(self) -> float:
        return sum(r.wire_bytes * r.count for r in self.records)

    def by_op(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        for r in self.records:
            agg = out.setdefault(
                r.op, {"count": 0, "bytes": 0, "wire_bytes": 0.0}
            )
            agg["count"] += r.count
            agg["bytes"] += r.bytes * r.count
            agg["wire_bytes"] += r.wire_bytes * r.count
        return out

    def to_json(self) -> Dict[str, Any]:
        return {
            "flops": self.flops,
            "total_bytes": self.total_bytes(),
            "total_wire_bytes": round(self.total_wire_bytes(), 1),
            "by_op": self.by_op(),
            "records": [r.to_json() for r in self.records],
        }


def _itemsize(aval: Any) -> int:
    try:
        return int(aval.dtype.itemsize)
    except Exception:
        return 4


def _nbytes(aval: Any) -> int:
    try:
        n = 1
        for d in aval.shape:
            n *= int(d)
        return n * _itemsize(aval)
    except Exception:
        return 0


def _provenance(eqn: Any, index: int) -> str:
    """file:line-ish origin of one equation — the user frame from jax's
    source_info when available, else a stable eqn# handle."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        pass
    return f"eqn#{index}:{eqn.primitive.name}"


def _replicated(aval: Any) -> Spec:
    return tuple(() for _ in getattr(aval, "shape", ()))


def _axis_group_size(axes: Sequence[str], mesh_axes: Dict[str, int]) -> int:
    n = 1
    for a in axes:
        n *= int(mesh_axes.get(a, 1))
    return max(n, 1)


def _from_pspec(pspec: Any, rank: int) -> Spec:
    """Normalize a PartitionSpec (or None) to the interpreter's Spec form."""
    entries = tuple(pspec) if pspec is not None else ()
    out: List[Tuple[str, ...]] = []
    for d in range(rank):
        e = entries[d] if d < len(entries) else None
        if e is None:
            out.append(())
        elif isinstance(e, str):
            out.append((e,))
        else:
            out.append(tuple(e))
    return tuple(out)


class Interpreter:
    """One pass over one closed jaxpr, collecting a :class:`CommLedger`.

    ``mesh_axes`` maps axis name -> size. ``replicated_threshold`` is the
    SAT-X003 byte floor for flagging fully-replicated intermediates.
    """

    def __init__(
        self,
        mesh_axes: Dict[str, int],
        replicated_threshold: int = 1 << 26,
    ) -> None:
        self.mesh_axes = dict(mesh_axes)
        self.replicated_threshold = int(replicated_threshold)
        self.ledger = CommLedger()
        # > 0 while interpreting a shard_map body: avals there are
        # per-shard and sharding is manual, so the implicit GSPMD rules
        # (dot resharding, reduce-over-sharded-dim, SAT-X003) must not
        # fire — only the body's explicit collectives count.
        self._shmap_depth = 0

    # ------------------------------------------------------------- plumbing
    def run(self, closed: Any, in_specs: Sequence[Spec]) -> List[Spec]:
        jaxpr = getattr(closed, "jaxpr", closed)
        consts = getattr(closed, "consts", ())
        env: Dict[Any, Spec] = {}
        for cv, c in zip(jaxpr.constvars, consts):
            env[cv] = _replicated(cv.aval)
        for cv in jaxpr.constvars:
            env.setdefault(cv, _replicated(cv.aval))
        invars = list(jaxpr.invars)
        specs = list(in_specs)
        if len(specs) < len(invars):
            # leading invars without a declared spec (captured consts in
            # some call primitives): treat as replicated, align at the end
            pad = len(invars) - len(specs)
            specs = [_replicated(v.aval) for v in invars[:pad]] + specs
        for v, s in zip(invars, specs):
            env[v] = self._fit(s, v.aval)
        self._interpret(jaxpr, env, multiplier=1, scan_depth=0)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _fit(self, spec: Any, aval: Any) -> Spec:
        rank = len(getattr(aval, "shape", ()))
        if spec is None:
            return tuple(() for _ in range(rank))
        spec = tuple(spec)
        if len(spec) < rank:
            spec = spec + tuple(() for _ in range(rank - len(spec)))
        return tuple(tuple(e) if not isinstance(e, str) else (e,)
                     for e in spec[:rank])

    def _read(self, env: Dict[Any, Spec], atom: Any) -> Spec:
        if hasattr(atom, "val"):          # Literal
            return _replicated(atom.aval)
        return env.get(atom, _replicated(atom.aval))

    def _record(self, op: str, axes: Sequence[str], payload: int,
                eqn: Any, index: int, multiplier: int, scan_depth: int,
                explicit: bool = False) -> None:
        axes = tuple(a for a in axes if a in self.mesh_axes)
        n = _axis_group_size(axes, self.mesh_axes)
        if n <= 1:
            return  # a 1-wide axis moves no bytes
        self.ledger.add(CollectiveRecord(
            op=op, axes=axes, bytes=int(payload),
            wire_bytes=_WIRE_FACTOR[op](n) * payload,
            count=max(int(multiplier), 1),
            primitive=eqn.primitive.name,
            provenance=_provenance(eqn, index),
            scan_depth=scan_depth, explicit=explicit,
        ))

    # ---------------------------------------------------------- interpreter
    def _interpret(self, jaxpr: Any, env: Dict[Any, Spec],
                   multiplier: int, scan_depth: int) -> None:
        for index, eqn in enumerate(jaxpr.eqns):
            name = eqn.primitive.name
            in_specs = [self._read(env, v) for v in eqn.invars]
            handler = getattr(self, f"_h_{name}", None)
            if handler is None:
                if name in _ELEMENTWISE:
                    outs = self._elementwise(eqn, in_specs, index,
                                             multiplier, scan_depth)
                elif name in _REDUCERS:
                    outs = self._reduce(eqn, in_specs, index,
                                        multiplier, scan_depth)
                else:
                    outs = [_replicated(v.aval) for v in eqn.outvars]
            else:
                outs = handler(eqn, in_specs, index, multiplier, scan_depth)
            for v, s in zip(eqn.outvars, outs):
                if not hasattr(v, "aval"):
                    continue
                fitted = self._fit(s, v.aval)
                env[v] = fitted
                nb = _nbytes(v.aval)
                if (
                    nb >= self.replicated_threshold
                    and self._shmap_depth == 0
                    and all(not e for e in fitted)
                    and len(fitted) > 0
                ):
                    self.ledger.replicated_intermediates.append(
                        (nb, _provenance(eqn, index))
                    )

    # elementwise: unify; conflicting non-trivial shardings -> reshard
    def _elementwise(self, eqn, in_specs, index, multiplier, scan_depth):
        out_aval = eqn.outvars[0].aval
        rank = len(getattr(out_aval, "shape", ()))
        unified: List[Tuple[str, ...]] = [() for _ in range(rank)]
        for spec, invar in zip(in_specs, eqn.invars):
            if len(spec) != rank:
                continue
            for d in range(rank):
                if not spec[d]:
                    continue
                if not unified[d]:
                    unified[d] = spec[d]
                elif unified[d] != spec[d] and self._shmap_depth == 0:
                    # genuine conflict: GSPMD inserts a resharding transfer
                    self._record(
                        "reshard", set(unified[d]) | set(spec[d]),
                        _nbytes(invar.aval), eqn, index, multiplier,
                        scan_depth,
                    )
        return [tuple(unified) for _ in eqn.outvars]

    def _reduce(self, eqn, in_specs, index, multiplier, scan_depth):
        axes_param = eqn.params.get("axes", ())
        spec = in_specs[0] if in_specs else ()
        reduced_mesh_axes: List[str] = []
        out_spec: List[Tuple[str, ...]] = []
        for d, e in enumerate(spec):
            if d in axes_param:
                reduced_mesh_axes.extend(e)
            else:
                out_spec.append(e)
        if reduced_mesh_axes and self._shmap_depth == 0:
            self._record("all_reduce", reduced_mesh_axes,
                         _nbytes(eqn.outvars[0].aval), eqn, index,
                         multiplier, scan_depth)
        return [tuple(out_spec) for _ in eqn.outvars]

    # ---------------------------------------------------------- dot_general
    def _h_dot_general(self, eqn, in_specs, index, multiplier, scan_depth):
        (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
        lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
        ls, rs = in_specs[0], in_specs[1]

        # flops: 2 * |out| * |contraction|
        out_elems = 1
        for d in getattr(eqn.outvars[0].aval, "shape", ()):
            out_elems *= int(d)
        contract = 1
        for d in lc:
            contract *= int(lhs.shape[d])
        flops = 2.0 * out_elems * contract * max(multiplier, 1)
        if self._shmap_depth > 0:
            # body avals are per-shard; scale to the global total so both
            # trace styles report the same workload flops
            for n in self.mesh_axes.values():
                flops *= max(int(n), 1)
        self.ledger.flops += flops

        # output sharding skeleton: batch dims, then lhs free, then rhs free
        l_free = [d for d in range(len(lhs.shape)) if d not in lc and d not in lb]
        r_free = [d for d in range(len(rhs.shape)) if d not in rc and d not in rb]
        out_spec: List[Tuple[str, ...]] = []
        used_axes: set = set()
        for d in lb:
            out_spec.append(ls[d] if d < len(ls) else ())
            used_axes.update(out_spec[-1])
        for d in l_free:
            out_spec.append(ls[d] if d < len(ls) else ())
            used_axes.update(out_spec[-1])

        # rhs free dims: an axis already claimed by the lhs side cannot
        # shard the output a second way — GSPMD all-gathers the rhs (the
        # ZeRO-3 parameter pattern: W sharded on 'data' meets a
        # 'data'-sharded batch).
        implicit = self._shmap_depth == 0
        rhs_gathered = False
        for d in r_free:
            e = rs[d] if d < len(rs) else ()
            if e and set(e) & used_axes:
                if not rhs_gathered and implicit:
                    self._record("all_gather", e, _nbytes(rhs), eqn, index,
                                 multiplier, scan_depth)
                    rhs_gathered = True
                out_spec.append(())
            else:
                out_spec.append(e)
                used_axes.update(e)

        # contracting dims: same axis on both sides -> partial sums ->
        # all-reduce of the output. Sharded on exactly one side -> that
        # operand must be gathered along the contraction.
        reduce_axes: List[str] = []
        for dl, dr in zip(lc, rc):
            el = set(ls[dl]) if dl < len(ls) else set()
            er = set(rs[dr]) if dr < len(rs) else set()
            both = el & er
            reduce_axes.extend(sorted(both))
            only_l = el - er
            only_r = er - el
            if only_l and implicit:
                self._record("all_gather", sorted(only_l), _nbytes(lhs),
                             eqn, index, multiplier, scan_depth)
            if only_r and not rhs_gathered and implicit:
                self._record("all_gather", sorted(only_r), _nbytes(rhs),
                             eqn, index, multiplier, scan_depth)
        if reduce_axes and implicit:
            self._record("all_reduce", reduce_axes,
                         _nbytes(eqn.outvars[0].aval), eqn, index,
                         multiplier, scan_depth)
        return [tuple(out_spec)]

    # ------------------------------------------------------- shape plumbing
    def _h_broadcast_in_dim(self, eqn, in_specs, index, multiplier, scan_depth):
        bd = eqn.params["broadcast_dimensions"]
        out_rank = len(eqn.outvars[0].aval.shape)
        spec = in_specs[0] if in_specs else ()
        out = [() for _ in range(out_rank)]
        in_shape = getattr(eqn.invars[0].aval, "shape", ())
        for i, d in enumerate(bd):
            if i < len(spec) and i < len(in_shape) and int(in_shape[i]) > 1:
                out[d] = spec[i]
        return [tuple(out)]

    def _h_transpose(self, eqn, in_specs, index, multiplier, scan_depth):
        perm = eqn.params["permutation"]
        spec = in_specs[0]
        return [tuple(spec[p] if p < len(spec) else () for p in perm)]

    def _h_reshape(self, eqn, in_specs, index, multiplier, scan_depth):
        in_shape = tuple(int(d) for d in eqn.invars[0].aval.shape)
        out_shape = tuple(int(d) for d in eqn.outvars[0].aval.shape)
        spec = in_specs[0]
        out: List[Tuple[str, ...]] = [() for _ in out_shape]
        # conservative: carry specs only across a dimension-preserving
        # prefix/suffix; split or merged dims silently drop to replicated
        # (a dropped spec can only *miss* communication, never invent it)
        i = 0
        while (i < len(in_shape) and i < len(out_shape)
               and in_shape[i] == out_shape[i]):
            if i < len(spec):
                out[i] = spec[i]
            i += 1
        j = 0
        while (j < len(in_shape) - i and j < len(out_shape) - i
               and in_shape[-1 - j] == out_shape[-1 - j]):
            k = len(spec) - 1 - j
            if 0 <= k:
                out[len(out_shape) - 1 - j] = spec[k]
            j += 1
        return [tuple(out)]

    def _h_squeeze(self, eqn, in_specs, index, multiplier, scan_depth):
        dims = set(eqn.params["dimensions"])
        spec = in_specs[0]
        return [tuple(e for d, e in enumerate(spec) if d not in dims)]

    def _h_expand_dims(self, eqn, in_specs, index, multiplier, scan_depth):
        dims = set(eqn.params["dimensions"])
        out_rank = len(eqn.outvars[0].aval.shape)
        spec = list(in_specs[0])
        out: List[Tuple[str, ...]] = []
        src = 0
        for d in range(out_rank):
            if d in dims:
                out.append(())
            else:
                out.append(spec[src] if src < len(spec) else ())
                src += 1
        return [tuple(out)]

    def _h_concatenate(self, eqn, in_specs, index, multiplier, scan_depth):
        dim = eqn.params["dimension"]
        rank = len(eqn.outvars[0].aval.shape)
        out = [() for _ in range(rank)]
        for spec in in_specs:
            for d in range(min(rank, len(spec))):
                if d != dim and spec[d] and not out[d]:
                    out[d] = spec[d]
        return [tuple(out)]

    def _h_slice(self, eqn, in_specs, index, multiplier, scan_depth):
        return [in_specs[0]]

    def _h_dynamic_slice(self, eqn, in_specs, index, multiplier, scan_depth):
        return [in_specs[0]]

    def _h_dynamic_update_slice(self, eqn, in_specs, index, multiplier,
                                scan_depth):
        return [in_specs[0]]

    def _h_pad(self, eqn, in_specs, index, multiplier, scan_depth):
        return [in_specs[0]]

    def _h_gather(self, eqn, in_specs, index, multiplier, scan_depth):
        """take/embedding-lookup pattern: a sharded table (vocab-sharded
        wte) forces a masked local gather + all-reduce of the result."""
        operand_spec = in_specs[0]
        idx_spec = in_specs[1] if len(in_specs) > 1 else ()
        out_rank = len(eqn.outvars[0].aval.shape)
        table_axes = sorted({a for e in operand_spec for a in e})
        if table_axes and self._shmap_depth == 0:
            self._record("all_reduce", table_axes,
                         _nbytes(eqn.outvars[0].aval), eqn, index,
                         multiplier, scan_depth)
        out = [() for _ in range(out_rank)]
        for d in range(min(out_rank, len(idx_spec))):
            out[d] = idx_spec[d]
        return [tuple(out)]

    # -------------------------------------------------- explicit collectives
    def _named_axes(self, eqn) -> Tuple[str, ...]:
        p = eqn.params
        axes = p.get("axes", p.get("axis_name", ()))
        if isinstance(axes, str):
            axes = (axes,)
        return tuple(a for a in axes if isinstance(a, str))

    def _h_psum(self, eqn, in_specs, index, multiplier, scan_depth):
        axes = self._named_axes(eqn)
        for v in eqn.outvars:
            self._record("all_reduce", axes, _nbytes(v.aval), eqn, index,
                         multiplier, scan_depth, explicit=True)
        return list(in_specs[: len(eqn.outvars)]) or [
            _replicated(v.aval) for v in eqn.outvars
        ]

    # psum inside a shard_map body traces as ``psum_invariant`` — same wire
    # traffic as psum.
    def _h_psum_invariant(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._h_psum(eqn, in_specs, index, multiplier, scan_depth)

    # shard_map's varying-axes bookkeeping: no bytes move.
    def _h_pvary(self, eqn, in_specs, index, multiplier, scan_depth):
        return list(in_specs[: len(eqn.outvars)]) or [
            _replicated(v.aval) for v in eqn.outvars
        ]

    def _h_pmax(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._h_psum(eqn, in_specs, index, multiplier, scan_depth)

    def _h_pmin(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._h_psum(eqn, in_specs, index, multiplier, scan_depth)

    def _h_all_gather(self, eqn, in_specs, index, multiplier, scan_depth):
        axes = self._named_axes(eqn)
        self._record("all_gather", axes, _nbytes(eqn.outvars[0].aval),
                     eqn, index, multiplier, scan_depth, explicit=True)
        return [_replicated(v.aval) for v in eqn.outvars]

    def _h_all_to_all(self, eqn, in_specs, index, multiplier, scan_depth):
        axes = self._named_axes(eqn)
        self._record("all_to_all", axes, _nbytes(eqn.outvars[0].aval),
                     eqn, index, multiplier, scan_depth, explicit=True)
        return [in_specs[0]]

    def _h_ppermute(self, eqn, in_specs, index, multiplier, scan_depth):
        axes = self._named_axes(eqn)
        self._record("ppermute", axes, _nbytes(eqn.outvars[0].aval),
                     eqn, index, multiplier, scan_depth, explicit=True)
        return list(in_specs[: len(eqn.outvars)]) or [
            _replicated(v.aval) for v in eqn.outvars
        ]

    def _h_psum_scatter(self, eqn, in_specs, index, multiplier, scan_depth):
        axes = self._named_axes(eqn)
        self._record("reduce_scatter", axes,
                     _nbytes(eqn.invars[0].aval), eqn, index, multiplier,
                     scan_depth, explicit=True)
        return [in_specs[0]]

    def _h_axis_index(self, eqn, in_specs, index, multiplier, scan_depth):
        return [_replicated(v.aval) for v in eqn.outvars]

    # --------------------------------------------------- structured control
    def _recurse(self, inner: Any, in_specs: Sequence[Spec],
                 multiplier: int, scan_depth: int) -> List[Spec]:
        jaxpr = getattr(inner, "jaxpr", inner)
        env: Dict[Any, Spec] = {}
        for cv in getattr(jaxpr, "constvars", ()):
            env[cv] = _replicated(cv.aval)
        invars = list(jaxpr.invars)
        specs = list(in_specs)
        if len(specs) < len(invars):
            pad = len(invars) - len(specs)
            specs = [_replicated(v.aval) for v in invars[:pad]] + specs
        for v, s in zip(invars, specs):
            env[v] = self._fit(s, v.aval)
        self._interpret(jaxpr, env, multiplier, scan_depth)
        return [self._read(env, v) for v in jaxpr.outvars]

    def _h_jit(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_closed_call(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["call_jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_core_call(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["call_jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_remat2(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_remat(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_checkpoint(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_custom_jvp_call(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["call_jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_custom_vjp_call(self, eqn, in_specs, index, multiplier, scan_depth):
        return self._recurse(eqn.params["call_jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_custom_vjp_call_jaxpr(self, eqn, in_specs, index, multiplier,
                                 scan_depth):
        return self._recurse(eqn.params["fun_jaxpr"], in_specs, multiplier,
                             scan_depth)

    def _h_scan(self, eqn, in_specs, index, multiplier, scan_depth):
        p = eqn.params
        length = int(p.get("length", 1))
        n_consts = int(p.get("num_consts", 0))
        n_carry = int(p.get("num_carry", 0))
        inner = p["jaxpr"]
        body_in: List[Spec] = []
        for i, spec in enumerate(in_specs):
            if i < n_consts + n_carry:
                body_in.append(spec)
            else:
                body_in.append(tuple(spec[1:]))  # xs lose the scan dim
        body_out = self._recurse(inner, body_in,
                                 multiplier * max(length, 1),
                                 scan_depth + 1)
        outs: List[Spec] = []
        for i, v in enumerate(eqn.outvars):
            s = body_out[i] if i < len(body_out) else _replicated(v.aval)
            if i < n_carry:
                outs.append(s)
            else:
                outs.append(((),) + tuple(s))  # ys gain the scan dim
        return outs

    def _h_while(self, eqn, in_specs, index, multiplier, scan_depth):
        p = eqn.params
        n_cc = int(p.get("cond_nconsts", 0))
        n_bc = int(p.get("body_nconsts", 0))
        carry = in_specs[n_cc + n_bc:]
        body_in = list(in_specs[n_cc: n_cc + n_bc]) + list(carry)
        return self._recurse(p["body_jaxpr"], body_in, multiplier,
                             scan_depth)

    def _h_cond(self, eqn, in_specs, index, multiplier, scan_depth):
        branches = eqn.params["branches"]
        # one representative branch for the ledger; specs from the first
        return self._recurse(branches[0], in_specs[1:], multiplier,
                             scan_depth)

    def _h_shard_map(self, eqn, in_specs, index, multiplier, scan_depth):
        """shard_map body: avals inside are already per-shard; explicit
        collectives in the body are counted directly."""
        p = eqn.params
        inner = p["jaxpr"]
        jaxpr = getattr(inner, "jaxpr", inner)
        body_in = [
            _from_pspec(spec, len(getattr(v.aval, "shape", ())))
            for v, spec in zip(jaxpr.invars, p["in_specs"])
        ]
        self._shmap_depth += 1
        try:
            self._recurse(inner, body_in, multiplier, scan_depth)
        finally:
            self._shmap_depth -= 1
        return [
            _from_pspec(spec, len(getattr(v.aval, "shape", ())))
            for v, spec in zip(eqn.outvars, p["out_specs"])
        ]


def interpret(traced: Dict[str, Any],
              replicated_threshold: int = 1 << 26) -> CommLedger:
    """Run the interpreter over one ``SPMDTechnique.trace_step`` result."""
    import jax
    from jax.sharding import PartitionSpec

    closed = traced["jaxpr"]
    mesh_axes = traced["mesh_axes"]
    state_leaves = jax.tree_util.tree_leaves(traced["state_shapes"])
    spec_leaves = jax.tree_util.tree_leaves(
        traced["state_specs"],
        is_leaf=lambda x: x is None or isinstance(x, PartitionSpec),
    )
    in_specs: List[Spec] = []
    for leaf, pspec in zip(state_leaves, spec_leaves):
        in_specs.append(_from_pspec(pspec, len(leaf.shape)))
    in_specs.append(
        _from_pspec(traced["batch_spec"], len(traced["batch_sds"].shape))
    )
    interp = Interpreter(mesh_axes, replicated_threshold=replicated_threshold)
    interp.run(closed, in_specs)
    return interp.ledger
