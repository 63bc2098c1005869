"""SPMD soundness prover — the ``spmd`` audit family.

The sharded verification program (partition.py) and the pod dispatch
layer await their hardware verdict with only dynamic multi-CPU tests
behind them.  This family is the static half of that contract: it
re-stages every sharded program over a device-less
``jax.sharding.AbstractMesh``, walks the staged jaxprs with an abstract
interpreter (built on ``range_lint``'s interval arrays plus a
per-device replication lattice), and proves four theorem classes:

* **collective legality** (``spmd-collective``) — every ``psum`` /
  ``all_gather`` / ``ppermute`` / ``all_to_all`` names a mesh axis in
  the declared registry (``mesh.BATCH_AXIS`` or the defs module's
  ``DECLARED_AXES``), and no collective executes under a shard-varying
  conditional, where the shards would disagree about whether to enter
  the rendezvous and deadlock or desync.
* **replication soundness** (``spmd-replication``) — a
  version-independent ``check_rep``: each value carries the set of
  device offsets it can depend on.  ``axis_index`` taints; ``psum`` /
  full-group ``all_gather`` restore invariance; a uniform-ring
  ``ppermute`` shifts the offset set, and a commutative combine whose
  offsets cover the whole axis promotes back to invariant — so the
  n-1-hop ``ring_reduce`` proves replicated even though jax's own
  ``check_vma`` cannot see it (the documented gap in multichip.py).
  An ``out_specs`` that claims replication for a value still inferred
  shard-varying is a finding: the pod's "first answer wins" read of
  the verdict vector would be unsound.
* **pad absorption / gather bounds** (``spmd-pad`` / ``spmd-bounds``)
  — pad lanes are proved to be *duplicates of a real column* by
  provenance: each real input column is seeded with a distinct marker
  interval and every pad column of the output must carry exactly some
  real column's marker (a zero- or mean-filled pad fails).  The
  verdict reduction's backward slice must be idempotent-combine only
  (AND/OR/min/max — a sum or product would double-count duplicated
  lanes).  Interval analysis with branch-constraint refinement proves
  masked ``take`` indices in the registry gather stay inside the local
  shard for every width x batch shape, including non-divisible
  remainders, and that ``dynamic_slice`` starts can never hit XLA's
  silent runtime clamp.
* **donation discipline** (``spmd-donate``) — an AST lint over the
  scanned corpus: ``donate_argnums`` must be an empty literal or
  assigned under a TPU-backend guard (the backend's dispatch contract
  — CPU/GPU test paths must never donate live buffers), and a buffer
  passed to a donating kernel must not be read again in the same
  function.

``spmd-interp`` reports analysis-infrastructure failures (a program
that fails to trace, an unreadable defs module) so they can never pass
silently.  Like the range family, per-program verdicts are cached in
``.range_proof_cache.json`` under the family's own ``spmd_fingerprint``
(the range fingerprint — which covers partition.py/mesh.py — extended
with this module), and fixture corpora are never cached.
"""

from __future__ import annotations

import ast
import importlib.util
import json
import os

import numpy as np

from .range_lint import (
    IV,
    _SAT,
    _aval_shape,
    _dtype_range,
    _eqn_src as _eqn_src_abs,
    _is_literal,
    iv_add,
    iv_mul,
    iv_sub,
)
from .report import Violation

RULE_COLLECTIVE = "spmd-collective"
RULE_REP = "spmd-replication"
RULE_PAD = "spmd-pad"
RULE_BOUNDS = "spmd-bounds"
RULE_DONATE = "spmd-donate"
RULE_INTERP = "spmd-interp"

MAX_FINDINGS_PER_PROGRAM = 16
_SCAN_ITERS = 16     # scan/while carry fixpoint cap before widening
_MARK_SHIFT = 8      # pad-provenance marker for column j is 1 << (j + 8)

_COLLECTIVES = {
    "psum", "pmax", "pmin", "all_gather", "ppermute", "pshuffle",
    "all_to_all", "reduce_scatter", "psum_invariant", "pbroadcast",
}
# verdict-path reductions that are NOT idempotent over duplicated pad
# lanes: a pad column contributing to one of these double-counts
_NON_IDEMPOTENT = {
    "reduce_sum", "reduce_prod", "cumsum", "cumprod", "dot_general",
    "cumlogsumexp",
}
# elementwise combines that commute, so "depends on every offset the
# same way" promotes a full-coverage offset set back to invariant
_COMMUTATIVE = {"add", "mul", "and", "or", "xor", "min", "max"}


def _eqn_src(eqn):
    """Basename (source hint, line) — keeps finding symbols stable
    across checkouts (the raw jax frame path is absolute)."""
    fname, line = _eqn_src_abs(eqn)
    return (os.path.basename(fname) if fname else fname), line


def _axis_names(params):
    """Flat tuple of axis names from a collective's params.

    psum-style primitives carry ``axes`` (already a flat tuple);
    all_gather/ppermute/axis_index carry ``axis_name``, which jax may
    store either as a bare string or as a one-tuple like ``('cols',)``.
    """
    axes = params.get("axes")
    if axes is None:
        axes = params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    flat = []
    for ax in axes:
        if isinstance(ax, (tuple, list)):
            flat.extend(ax)
        else:
            flat.append(ax)
    return tuple(flat)


# ---------------------------------------------------------------------------
# Abstract values: interval x replication offsets x identity taint
# ---------------------------------------------------------------------------


class AV:
    """Abstract value for one jaxpr var.

    ``iv``     per-element integer interval (None: non-integer/unknown)
    ``off``    device offsets (relative shard indices, mod width) the
               value can depend on; ``None`` means axis-invariant —
               provably identical on every shard
    ``taint``  depends on device *identity* (``axis_index``), which no
               offset-coverage argument can wash out
    """

    __slots__ = ("iv", "off", "taint")

    def __init__(self, iv=None, off=None, taint=False):
        self.iv = iv
        self.off = off
        self.taint = bool(taint)

    @property
    def varying(self) -> bool:
        return self.taint or self.off is not None

    def same(self, other) -> bool:
        if (self.iv is None) != (other.iv is None):
            return False
        if self.iv is not None and not (
            np.array_equal(self.iv.lo, other.iv.lo)
            and np.array_equal(self.iv.hi, other.iv.hi)
        ):
            return False
        return self.off == other.off and self.taint == other.taint


def _aval_iv(aval):
    rng = _dtype_range(aval)
    if rng is None:
        return None
    return IV.full(_aval_shape(aval), rng[0], rng[1])


def _join_av(a: AV, b: AV) -> AV:
    if a.iv is None or b.iv is None:
        iv = None
    else:
        iv = a.iv.join(b.iv)
    if a.off is None and b.off is None:
        off = None
    else:
        off = frozenset(a.off or ()) | frozenset(b.off or ())
    return AV(iv, off, a.taint or b.taint)


def _mix_off(ins):
    """Offset-set/taint of an elementwise combination of ``ins``."""
    offs = [a.off for a in ins if a.off is not None]
    taint = any(a.taint for a in ins)
    if not offs:
        return None, taint
    u = frozenset()
    for o in offs:
        u = u | o
    return u, taint


# ---------------------------------------------------------------------------
# Program registry
# ---------------------------------------------------------------------------


class SpmdProgram:
    """One proof obligation over a staged sharded program.

    ``build()`` returns ``(fn, example_args)``; the program is traced
    with ``jax.make_jaxpr(fn)(*example_args)``.

    ``kind="mesh"`` programs must stage at least one ``shard_map``;
    every interior is walked for all four theorem classes.  ``domains``
    optionally maps shard_map operand position -> ``(lo, hi)`` input
    interval (e.g. the slot vector's validator-slot domain).

    ``kind="pad"`` programs take one integer array whose trailing axis
    is ``n_real`` real columns and produce an array with extra pad
    columns; provenance marking proves every pad column duplicates a
    real one.  ``combine`` names a reduction primitive expected on the
    verdict path (fixtures use it to seed non-idempotent shapes).
    """

    __slots__ = ("name", "path", "build", "kind", "domains", "n_real",
                 "axis", "note")

    def __init__(self, name, path, build, kind="mesh", domains=None,
                 n_real=0, axis="batch", note=""):
        self.name = name
        self.path = path
        self.build = build
        self.kind = kind
        self.domains = dict(domains or {})
        self.n_real = int(n_real)
        self.axis = axis
        self.note = note


def trace_mesh(axes):
    """A device-less mesh over ``axes`` (name -> size) that shard_map
    programs can be staged over with ``jax.make_jaxpr`` — no physical
    devices are touched, so any width is analyzable anywhere."""
    from jax.sharding import AbstractMesh

    axes = list(axes)
    return AbstractMesh(tuple(int(v) for _, v in axes),
                        tuple(str(k) for k, _ in axes))


# ---------------------------------------------------------------------------
# Finding collection
# ---------------------------------------------------------------------------


class _Findings:
    def __init__(self, program: SpmdProgram):
        self.program = program
        self.seen: set = set()
        self.out: list = []

    def add(self, rule: str, symbol: str, message: str, line: int = 0):
        key = (rule, symbol, message)
        if key in self.seen or len(self.out) >= MAX_FINDINGS_PER_PROGRAM:
            return
        self.seen.add(key)
        self.out.append(Violation(
            rule=rule, path=self.program.path, line=line,
            symbol=f"{self.program.name}:{symbol}", message=message,
        ))


# ---------------------------------------------------------------------------
# The SPMD abstract interpreter
# ---------------------------------------------------------------------------


class _Interp:
    """One shard_map interior: interval + replication walk."""

    def __init__(self, program: SpmdProgram, findings: _Findings,
                 axis_sizes: dict, declared: set):
        self.program = program
        self.findings = findings
        self.axis_sizes = dict(axis_sizes)   # mesh axis -> size
        self.declared = set(declared)
        self.width = int(axis_sizes.get(program.axis, 1))
        self.diverging = 0   # >0: under a shard-varying conditional
        # bool var -> (true_map, false_map); each maps var -> (lo, hi)
        self.cons: dict = {}

    # -- eqn walk ------------------------------------------------------------

    def run_jaxpr(self, jaxpr, const_avs, in_avs):
        env: dict = {}

        def write(var, av):
            if type(var).__name__ == "DropVar":
                return
            env[var] = av

        def read(atom):
            if _is_literal(atom):
                return AV(IV.const(np.asarray(atom.val))
                          if np.issubdtype(np.asarray(atom.val).dtype,
                                           np.number)
                          or np.asarray(atom.val).dtype == np.bool_
                          else None)
            return env[atom]

        for var, av in zip(jaxpr.constvars, const_avs):
            write(var, av)
        for var, av in zip(jaxpr.invars, in_avs):
            write(var, av)
        for eqn in jaxpr.eqns:
            ins = [read(a) for a in eqn.invars]
            outs = self.eval_eqn(eqn, ins, env)
            for var, av in zip(eqn.outvars, outs):
                write(var, av)
        return [read(v) for v in jaxpr.outvars]

    def run_closed(self, closed, in_avs):
        consts = [AV(IV.const(np.asarray(c)))
                  if _np_intlike(c) else AV()
                  for c in closed.consts]
        return self.run_jaxpr(closed.jaxpr, consts, in_avs)

    def eval_eqn(self, eqn, ins, env):
        name = eqn.primitive.name
        if name in _COLLECTIVES:
            return self._collective(eqn, ins)
        handler = getattr(self, "_h_" + name, None)
        if handler is not None:
            return handler(eqn, ins, env)
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None:   # pjit / closed_call / custom_* / remat
            self._import_cons(eqn, sub.jaxpr if hasattr(sub, "consts")
                              else sub)
            if hasattr(sub, "consts"):
                return self.run_closed(sub, ins)
            return self.run_jaxpr(sub, [], ins)
        return self.default(eqn, ins)

    def _import_cons(self, eqn, sub_jaxpr):
        """Carry var-vs-const bound maps across a call boundary: an
        outer operand's constraint entry is re-keyed onto the callee
        invars (``jnp.where`` lowers as a pjit, so the `hit` mask and
        the `rel` index it bounds both cross one)."""
        pos = {a: i for i, a in enumerate(eqn.invars)
               if not _is_literal(a)}
        inner = list(sub_jaxpr.invars)
        for atom, i in pos.items():
            maps = self.cons.get(atom)
            if maps is None or i >= len(inner):
                continue
            translated = []
            for m in maps:
                tm = {}
                for var, bound in m.items():
                    j = pos.get(var)
                    if j is not None and j < len(inner):
                        tm[inner[j]] = bound
                translated.append(tm)
            if any(translated):
                self.cons[inner[i]] = tuple(translated)

    def default(self, eqn, ins):
        off, taint = _mix_off(ins)
        outs = []
        for var in eqn.outvars:
            iv = _elementwise_iv(eqn.primitive.name, ins, var.aval)
            av = AV(iv, off, taint)
            self._promote(eqn.primitive.name, av)
            outs.append(av)
        return outs

    def _promote(self, prim: str, av: AV) -> None:
        # a commutative combine whose offset set covers the whole axis
        # depends on every shard symmetrically -> invariant again (the
        # ring_reduce theorem jax's check_vma cannot express)
        if (av.off is not None and not av.taint and self.width > 1
                and prim in _COMMUTATIVE
                and av.off >= frozenset(range(self.width))):
            av.off = None

    # -- collectives ---------------------------------------------------------

    def _collective(self, eqn, ins):
        name = eqn.primitive.name
        axes = _axis_names(eqn.params)
        fname, line = _eqn_src(eqn)
        for ax in axes:
            if isinstance(ax, str) and ax not in self.declared:
                self.findings.add(
                    RULE_COLLECTIVE, f"{name}@{ax}",
                    f"collective `{name}` names mesh axis {ax!r} which is"
                    f" not in the declared axis registry"
                    f" {sorted(self.declared)} ({fname}:{line})",
                    line,
                )
        if self.diverging:
            self.findings.add(
                RULE_COLLECTIVE, f"{name}:diverging",
                f"collective `{name}` executes under a shard-varying"
                f" conditional: shards can disagree about reaching this"
                f" rendezvous ({fname}:{line})",
                line,
            )
        groups = eqn.params.get("axis_index_groups")
        if name in ("psum", "pmax", "pmin", "psum_invariant"):
            n = 1
            for ax in axes:
                n *= int(self.axis_sizes.get(ax, 1))
            outs = []
            for var, a in zip(eqn.outvars, ins):
                if a.iv is not None and name in ("psum", "psum_invariant"):
                    iv = IV(np.clip(a.iv.lo * n, -_SAT, _SAT),
                            np.clip(a.iv.hi * n, -_SAT, _SAT))
                elif a.iv is not None:
                    iv = IV.full(_aval_shape(var.aval), a.iv.min_lo(),
                                 a.iv.max_hi())
                else:
                    iv = None
                # a full-group reduction is identical on every member
                outs.append(AV(iv, None if groups is None else
                              frozenset({0}),
                              a.taint and groups is not None))
            return outs
        if name == "all_gather":
            a = ins[0]
            var = eqn.outvars[0]
            iv = (IV.full(_aval_shape(var.aval), a.iv.min_lo(),
                          a.iv.max_hi()) if a.iv is not None else None)
            if groups is None:
                return [AV(iv, None, False)]
            return [AV(iv, frozenset({0}), a.taint)]
        if name == "ppermute":
            return [self._ppermute(eqn, a) for a in ins]
        # all_to_all / pshuffle / anything else: data crosses shards in
        # a layout we don't model — varying, identity-tainted
        return [AV(_aval_iv(v.aval), frozenset({0}), True)
                for v in eqn.outvars]

    def _ppermute(self, eqn, a: AV) -> AV:
        perm = eqn.params.get("perm") or ()
        axes = _axis_names(eqn.params)
        w = 1
        for ax in axes:
            w *= int(self.axis_sizes.get(ax, 1))
        shift = None
        if len(perm) == w and w > 0:
            shifts = {(dst - src) % w for src, dst in perm}
            if len(shifts) == 1:
                shift = next(iter(shifts))
        if shift is None or a.taint:
            # partial / non-uniform permutation: receiver-dependent data
            return AV(a.iv, frozenset({0}), True)
        off = a.off if a.off is not None else frozenset({0})
        return AV(a.iv, frozenset((o + shift) % w for o in off), False)

    # -- device identity ------------------------------------------------------

    def _h_axis_index(self, eqn, ins, env):
        names = _axis_names(eqn.params)
        ax = names[0] if names else None
        w = int(self.axis_sizes.get(ax, self.width))
        fname, line = _eqn_src(eqn)
        if isinstance(ax, str) and ax not in self.declared:
            self.findings.add(
                RULE_COLLECTIVE, f"axis_index@{ax}",
                f"`axis_index` names mesh axis {ax!r} outside the"
                f" declared registry {sorted(self.declared)}"
                f" ({fname}:{line})",
                line,
            )
        return [AV(IV.full((), 0, max(0, w - 1)), frozenset({0}), True)]

    # -- structured control flow ---------------------------------------------

    def _h_cond(self, eqn, ins, env):
        pred, ops = ins[0], ins[1:]
        branches = eqn.params["branches"]
        if pred.varying:
            # interior collectives fire spmd-collective via the
            # diverging counter as each branch is walked below
            self.diverging += 1
        branch_outs = []
        for br in branches:
            branch_outs.append(self.run_closed(br, list(ops)))
        if pred.varying:
            self.diverging -= 1
        outs = branch_outs[0]
        for bo in branch_outs[1:]:
            outs = [_join_av(a, b) for a, b in zip(outs, bo)]
        if pred.varying:
            outs = [AV(a.iv,
                       frozenset(a.off or ()) | frozenset(pred.off or ()),
                       a.taint or pred.taint) for a in outs]
        return outs

    def _h_while(self, eqn, ins, env):
        cn = int(eqn.params.get("cond_nconsts", 0))
        bn = int(eqn.params.get("body_nconsts", 0))
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        body = eqn.params["body_jaxpr"]
        cond = eqn.params["cond_jaxpr"]
        for it in range(_SCAN_ITERS):
            pred = self.run_closed(cond, cond_consts + carry)[0]
            if pred.varying:
                self.diverging += 1
            nxt = self.run_closed(body, body_consts + carry)
            if pred.varying:
                self.diverging -= 1
            joined = [_join_av(c, n) for c, n in zip(carry, nxt)]
            if all(a.same(b) for a, b in zip(joined, carry)):
                carry = joined
                break
            carry = joined
        else:
            carry = [AV(_aval_iv(v.aval), a.off, a.taint)
                     for v, a in zip(eqn.outvars, carry)]
        return carry

    def _h_scan(self, eqn, ins, env):
        nc = int(eqn.params.get("num_consts", 0))
        ncar = int(eqn.params.get("num_carry", 0))
        length = int(eqn.params.get("length", 0) or 0)
        body = eqn.params["jaxpr"]
        consts = ins[:nc]
        carry = list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        # per-iteration slice of xs: leading axis dropped, aggregate iv
        xslices = []
        for a, var in zip(xs, eqn.invars[nc + ncar:]):
            shape = _aval_shape(var.aval)[1:]
            iv = (IV.full(shape, a.iv.min_lo(), a.iv.max_hi())
                  if a.iv is not None else None)
            xslices.append(AV(iv, a.off, a.taint))
        ys_avs = None
        for it in range(_SCAN_ITERS):
            outs = self.run_closed(body, consts + carry + xslices)
            new_carry = [_join_av(c, n)
                         for c, n in zip(carry, outs[:ncar])]
            ys_avs = outs[ncar:]
            if all(a.same(b) for a, b in zip(new_carry, carry)):
                carry = new_carry
                break
            carry = new_carry
        else:
            carry = [AV(_aval_iv(v.aval), a.off, a.taint)
                     for v, a in zip(eqn.outvars[:ncar], carry)]
        ys = []
        for var, a in zip(eqn.outvars[ncar:], ys_avs or []):
            iv = (IV.full(_aval_shape(var.aval), a.iv.min_lo(),
                          a.iv.max_hi()) if a.iv is not None else None)
            ys.append(AV(iv, a.off, a.taint))
        return carry + ys

    # -- structural primitives (exact, needed by pad provenance) -------------

    def _h_reshape(self, eqn, ins, env):
        a = ins[0]
        shape = _aval_shape(eqn.outvars[0].aval)
        iv = (IV(a.iv.lo.reshape(shape), a.iv.hi.reshape(shape))
              if a.iv is not None else None)
        return [AV(iv, a.off, a.taint)]

    def _h_squeeze(self, eqn, ins, env):
        return self._h_reshape(eqn, ins, env)

    def _h_expand_dims(self, eqn, ins, env):
        return self._h_reshape(eqn, ins, env)

    def _h_broadcast_in_dim(self, eqn, ins, env):
        a = ins[0]
        shape = tuple(eqn.params["shape"])
        bdims = tuple(eqn.params["broadcast_dimensions"])
        if a.iv is None:
            return [AV(None, a.off, a.taint)]
        src = [1] * len(shape)
        for i, d in enumerate(bdims):
            src[d] = a.iv.lo.shape[i]
        lo = np.broadcast_to(a.iv.lo.reshape(src), shape).copy()
        hi = np.broadcast_to(a.iv.hi.reshape(src), shape).copy()
        return [AV(IV(lo, hi), a.off, a.taint)]

    def _h_transpose(self, eqn, ins, env):
        a = ins[0]
        perm = tuple(eqn.params["permutation"])
        iv = (IV(np.transpose(a.iv.lo, perm), np.transpose(a.iv.hi, perm))
              if a.iv is not None else None)
        return [AV(iv, a.off, a.taint)]

    def _h_slice(self, eqn, ins, env):
        a = ins[0]
        if a.iv is None:
            return [AV(None, a.off, a.taint)]
        idx = tuple(
            slice(s, l, st) for s, l, st in zip(
                eqn.params["start_indices"], eqn.params["limit_indices"],
                eqn.params.get("strides") or
                [1] * len(eqn.params["start_indices"]),
            )
        )
        return [AV(IV(a.iv.lo[idx].copy(), a.iv.hi[idx].copy()),
                   a.off, a.taint)]

    def _h_concatenate(self, eqn, ins, env):
        dim = int(eqn.params["dimension"])
        off, taint = _mix_off(ins)
        if any(a.iv is None for a in ins):
            return [AV(None, off, taint)]
        lo = np.concatenate([a.iv.lo for a in ins], axis=dim)
        hi = np.concatenate([a.iv.hi for a in ins], axis=dim)
        return [AV(IV(lo, hi), off, taint)]

    def _h_iota(self, eqn, ins, env):
        shape = _aval_shape(eqn.outvars[0].aval)
        dim = int(eqn.params["dimension"])
        vals = np.arange(shape[dim], dtype=np.int64)
        vals = vals.reshape([-1 if i == dim else 1
                             for i in range(len(shape))])
        vals = np.broadcast_to(vals, shape).copy()
        return [AV(IV(vals, vals.copy()))]

    def _h_convert_element_type(self, eqn, ins, env):
        a = ins[0]
        rng = _dtype_range(eqn.outvars[0].aval)
        if a.iv is None or rng is None:
            return [AV(_aval_iv(eqn.outvars[0].aval), a.off, a.taint)]
        return [AV(a.iv.clamp(rng[0], rng[1]), a.off, a.taint)]

    def _h_stop_gradient(self, eqn, ins, env):
        return [ins[0]]

    def _h_copy(self, eqn, ins, env):
        return [ins[0]]

    # -- arithmetic / comparisons with constraint recording ------------------

    def _binop(self, eqn, ins, fn):
        a, b = ins
        off, taint = _mix_off(ins)
        iv = fn(a.iv, b.iv) if (a.iv is not None and b.iv is not None) \
            else _aval_iv(eqn.outvars[0].aval)
        av = AV(iv, off, taint)
        self._promote(eqn.primitive.name, av)
        return [av]

    def _h_add(self, eqn, ins, env):
        return self._binop(eqn, ins, iv_add)

    def _h_sub(self, eqn, ins, env):
        return self._binop(eqn, ins, iv_sub)

    def _h_mul(self, eqn, ins, env):
        return self._binop(eqn, ins, iv_mul)

    def _h_max(self, eqn, ins, env):
        return self._binop(eqn, ins, lambda x, y: IV(
            np.maximum(x.lo, y.lo), np.maximum(x.hi, y.hi)))

    def _h_min(self, eqn, ins, env):
        return self._binop(eqn, ins, lambda x, y: IV(
            np.minimum(x.lo, y.lo), np.minimum(x.hi, y.hi)))

    def _cmp(self, eqn, ins, env, op):
        a, b = ins
        off, taint = _mix_off(ins)
        out = eqn.outvars[0]
        iv = IV.full(_aval_shape(out.aval), 0, 1)
        if a.iv is not None and b.iv is not None:
            always, never = _cmp_fold(op, a.iv, b.iv)
            if always:
                iv = IV.full(_aval_shape(out.aval), 1, 1)
            elif never:
                iv = IV.full(_aval_shape(out.aval), 0, 0)
        self._record_cmp(eqn, op, env)
        return [AV(iv, off, taint)]

    def _h_ge(self, eqn, ins, env):
        return self._cmp(eqn, ins, env, "ge")

    def _h_gt(self, eqn, ins, env):
        return self._cmp(eqn, ins, env, "gt")

    def _h_le(self, eqn, ins, env):
        return self._cmp(eqn, ins, env, "le")

    def _h_lt(self, eqn, ins, env):
        return self._cmp(eqn, ins, env, "lt")

    def _h_eq(self, eqn, ins, env):
        off, taint = _mix_off(ins)
        return [AV(IV.full(_aval_shape(eqn.outvars[0].aval), 0, 1),
                   off, taint)]

    def _h_ne(self, eqn, ins, env):
        return self._h_eq(eqn, ins, env)

    def _record_cmp(self, eqn, op, env):
        """var-vs-constant comparison -> (true, false) bound maps."""
        x, y = eqn.invars
        var, const, flipped = None, None, False
        if not _is_literal(x) and _const_scalar(y, env) is not None:
            var, const = x, _const_scalar(y, env)
        elif not _is_literal(y) and _const_scalar(x, env) is not None:
            var, const, flipped = y, _const_scalar(x, env), True
        if var is None:
            return
        if flipped:   # const OP var  ->  var FLIP(OP) const
            op = {"ge": "le", "gt": "lt", "le": "ge", "lt": "gt"}[op]
        c = int(const)
        bounds = {
            "ge": ((c, _SAT), (-_SAT, c - 1)),
            "gt": ((c + 1, _SAT), (-_SAT, c)),
            "le": ((-_SAT, c), (c + 1, _SAT)),
            "lt": ((-_SAT, c - 1), (c, _SAT)),
        }[op]
        self.cons[eqn.outvars[0]] = (
            {var: bounds[0]}, {var: bounds[1]}
        )

    def _h_and(self, eqn, ins, env):
        out = self._binop(eqn, ins, lambda x, y: IV(
            np.minimum(x.lo, y.lo) * 0,
            np.minimum(x.hi, y.hi),
        ) if (x.lo >= 0).all() and (y.lo >= 0).all()
            else _aval_iv(eqn.outvars[0].aval))
        # conjunction of constraints: both operands' true-maps hold
        tmap: dict = {}
        for a in eqn.invars:
            maps = self.cons.get(a)
            if maps:
                for v, (lo, hi) in maps[0].items():
                    plo, phi = tmap.get(v, (-_SAT, _SAT))
                    tmap[v] = (max(plo, lo), min(phi, hi))
        if tmap:
            self.cons[eqn.outvars[0]] = (tmap, {})
        return out

    def _h_or(self, eqn, ins, env):
        return self.default(eqn, ins)

    def _h_xor(self, eqn, ins, env):
        return self.default(eqn, ins)

    def _h_not(self, eqn, ins, env):
        a = ins[0]
        iv = (IV(1 - a.iv.hi, 1 - a.iv.lo)
              if a.iv is not None else
              IV.full(_aval_shape(eqn.outvars[0].aval), 0, 1))
        maps = self.cons.get(eqn.invars[0])
        if maps:
            self.cons[eqn.outvars[0]] = (maps[1], maps[0])
        return [AV(iv, a.off, a.taint)]

    def _h_select_n(self, eqn, ins, env):
        pred, cases = ins[0], ins[1:]
        out_shape = _aval_shape(eqn.outvars[0].aval)
        if pred.iv is not None and len(cases) == 2:
            if pred.iv.max_hi() == 0:
                chosen = [cases[0]]
            elif pred.iv.min_lo() == 1:
                chosen = [cases[1]]
            else:
                chosen = None
        else:
            chosen = None
        if chosen is None:
            maps = self.cons.get(eqn.invars[0]) if len(cases) == 2 \
                else None
            refined = []
            for i, c in enumerate(cases):
                av = c
                if maps is not None:
                    bound = (maps[1] if i == 0 else maps[0]).get(
                        eqn.invars[1 + i])
                    if bound is not None and av.iv is not None:
                        av = AV(av.iv.clamp(bound[0], bound[1]),
                                av.off, av.taint)
                refined.append(av)
            joined = refined[0]
            for av in refined[1:]:
                joined = _join_av(joined, av)
            off = frozenset(joined.off or ()) | frozenset(pred.off or ())
            chosen = [AV(joined.iv,
                         off if (joined.off is not None
                                 or pred.off is not None) else None,
                         joined.taint or pred.taint)]
        av = chosen[0]
        if av.iv is not None and av.iv.shape != out_shape:
            av = AV(av.iv.broadcast(out_shape), av.off, av.taint)
        return [av]

    # -- reductions -----------------------------------------------------------

    def _reduce(self, eqn, ins, np_fn, scale=False):
        a = ins[0]
        axes = tuple(eqn.params.get("axes", ()))
        out_shape = _aval_shape(eqn.outvars[0].aval)
        if a.iv is None:
            return [AV(_aval_iv(eqn.outvars[0].aval), a.off, a.taint)]
        lo = np_fn(a.iv.lo, axis=axes) if axes else np_fn(a.iv.lo)
        hi = np_fn(a.iv.hi, axis=axes) if axes else np_fn(a.iv.hi)
        lo = np.clip(np.asarray(lo, dtype=np.float64), -_SAT, _SAT)
        hi = np.clip(np.asarray(hi, dtype=np.float64), -_SAT, _SAT)
        iv = IV(lo.reshape(out_shape).astype(np.int64),
                hi.reshape(out_shape).astype(np.int64))
        return [AV(iv, a.off, a.taint)]

    def _h_reduce_and(self, eqn, ins, env):
        return self._reduce(eqn, ins, np.min)

    def _h_reduce_or(self, eqn, ins, env):
        return self._reduce(eqn, ins, np.max)

    def _h_reduce_min(self, eqn, ins, env):
        return self._reduce(eqn, ins, np.min)

    def _h_reduce_max(self, eqn, ins, env):
        return self._reduce(eqn, ins, np.max)

    def _h_reduce_sum(self, eqn, ins, env):
        return self._reduce(eqn, ins, np.sum)

    def _h_reduce_prod(self, eqn, ins, env):
        a = ins[0]
        return [AV(_aval_iv(eqn.outvars[0].aval), a.off, a.taint)]

    # -- indexing: the bounds theorems ----------------------------------------

    def _h_gather(self, eqn, ins, env):
        operand, indices = ins[0], ins[1]
        dnums = eqn.params["dimension_numbers"]
        slice_sizes = tuple(eqn.params["slice_sizes"])
        op_shape = _aval_shape(eqn.invars[0].aval)
        fname, line = _eqn_src(eqn)
        if indices.iv is not None:
            lo, hi = indices.iv.min_lo(), indices.iv.max_hi()
            for d in dnums.start_index_map:
                limit = op_shape[d] - slice_sizes[d]
                if lo < 0 or hi > limit:
                    self.findings.add(
                        RULE_BOUNDS, f"gather@{fname}:{line}",
                        f"gather index interval [{lo}, {hi}] escapes the"
                        f" local shard bound [0, {limit}] on operand dim"
                        f" {d} (shape {op_shape}, slice {slice_sizes})"
                        f" — out-of-shard slots must be masked before"
                        f" the take ({fname}:{line})",
                        line,
                    )
                    break
        else:
            self.findings.add(
                RULE_BOUNDS, f"gather@{fname}:{line}",
                f"gather indices carry no provable interval; shard-"
                f"bounds theorem fails open ({fname}:{line})",
                line,
            )
        off, taint = _mix_off(ins)
        out = eqn.outvars[0]
        iv = (IV.full(_aval_shape(out.aval), operand.iv.min_lo(),
                      operand.iv.max_hi())
              if operand.iv is not None else None)
        return [AV(iv, off, taint)]

    def _h_dynamic_slice(self, eqn, ins, env):
        operand, starts = ins[0], ins[1:]
        op_shape = _aval_shape(eqn.invars[0].aval)
        slice_sizes = tuple(eqn.params["slice_sizes"])
        fname, line = _eqn_src(eqn)
        for d, s in enumerate(starts):
            limit = op_shape[d] - slice_sizes[d]
            if s.iv is None:
                self.findings.add(
                    RULE_BOUNDS, f"dynamic_slice@{fname}:{line}",
                    f"dynamic_slice start on dim {d} carries no provable"
                    f" interval ({fname}:{line})",
                    line,
                )
                continue
            lo, hi = s.iv.min_lo(), s.iv.max_hi()
            if lo < 0 or hi > limit:
                self.findings.add(
                    RULE_BOUNDS, f"dynamic_slice@{fname}:{line}",
                    f"dynamic_slice start interval [{lo}, {hi}] on dim"
                    f" {d} escapes [0, {limit}] (shape {op_shape}, slice"
                    f" {slice_sizes}): XLA clamps silently, shifting the"
                    f" window to the wrong columns ({fname}:{line})",
                    line,
                )
        off, taint = _mix_off(ins)
        out = eqn.outvars[0]
        iv = (IV.full(_aval_shape(out.aval), operand.iv.min_lo(),
                      operand.iv.max_hi())
              if operand.iv is not None else None)
        return [AV(iv, off, taint)]


def _cmp_fold(op, a: IV, b: IV):
    """(always_true, always_false) for an aggregate comparison."""
    if op == "ge":
        return a.min_lo() >= b.max_hi(), a.max_hi() < b.min_lo()
    if op == "gt":
        return a.min_lo() > b.max_hi(), a.max_hi() <= b.min_lo()
    if op == "le":
        return a.max_hi() <= b.min_lo(), a.min_lo() > b.max_hi()
    return a.max_hi() < b.min_lo(), a.min_lo() >= b.max_hi()


def _const_scalar(atom, env):
    if _is_literal(atom):
        v = np.asarray(atom.val)
        if v.size == 1:
            return float(v.reshape(()))
        return None
    av = env.get(atom)
    if av is not None and av.iv is not None and av.iv.lo.size == 1 \
            and av.iv.lo.reshape(()) == av.iv.hi.reshape(()):
        return float(av.iv.lo.reshape(()))
    return None


def _elementwise_iv(prim, ins, out_aval):
    ivs = [a.iv for a in ins if a.iv is not None]
    rng = _dtype_range(out_aval)
    if rng is None:
        return None
    if prim in ("and", "or", "not", "xor") and \
            np.dtype(out_aval.dtype).name == "bool":
        return IV.full(_aval_shape(out_aval), 0, 1)
    if len(ivs) == len(ins) and ivs:
        lo = min(iv.min_lo() for iv in ivs)
        hi = max(iv.max_hi() for iv in ivs)
        if lo >= rng[0] and hi <= rng[1] and prim in (
                "neg", "abs", "rem", "clamp", "rev", "pad"):
            return IV.full(_aval_shape(out_aval), rng[0], rng[1])
    return IV.full(_aval_shape(out_aval), rng[0], rng[1])


def _np_intlike(c) -> bool:
    arr = np.asarray(c)
    return arr.dtype == np.bool_ or np.issubdtype(arr.dtype, np.integer)


def _sub_jaxprs(v):
    out = []
    stack = [v]
    while stack:
        x = stack.pop()
        if hasattr(x, "jaxpr") and hasattr(x, "consts"):
            out.append(x.jaxpr)
        elif hasattr(x, "eqns"):
            out.append(x)
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
    return out


# ---------------------------------------------------------------------------
# Program drivers
# ---------------------------------------------------------------------------


def _find_shard_maps(jaxpr, out=None):
    if out is None:
        out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            out.append(eqn)
            continue
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _find_shard_maps(sub, out)
    return out


def _names_dict(spec):
    """One shard_map in_specs/out_specs PartitionSpec as ``{dim: axes}``
    over its sharded dims — empty when the operand is replicated."""
    return {d: axes for d, axes in enumerate(spec) if axes is not None}


def _check_mesh_program(prog: SpmdProgram, closed, declared,
                        findings: _Findings) -> None:
    smaps = _find_shard_maps(closed.jaxpr)
    if not smaps:
        findings.add(
            RULE_INTERP, "no-shard-map",
            "mesh program staged no shard_map eqn — nothing to prove",
        )
        return
    for eqn in smaps:
        mesh = eqn.params.get("mesh")
        axis_sizes = {str(k): int(v)
                      for k, v in dict(getattr(mesh, "shape", {})).items()}
        inner = eqn.params["jaxpr"]
        in_names = [_names_dict(e) for e in eqn.params["in_specs"]]
        out_names = [_names_dict(e) for e in eqn.params["out_specs"]]
        interp = _Interp(prog, findings, axis_sizes, declared)
        in_avs = []
        for i, var in enumerate(inner.invars):
            names = in_names[i] if i < len(in_names) else {}
            dom = prog.domains.get(i)
            if dom is not None:
                iv = IV.full(_aval_shape(var.aval), int(dom[0]),
                             int(dom[1]))
            else:
                iv = _aval_iv(var.aval)
            off = frozenset({0}) if names else None
            in_avs.append(AV(iv, off, False))
        const_avs = [AV(_aval_iv(getattr(v, "aval", None)))
                     for v in inner.constvars]
        try:
            out_avs = interp.run_jaxpr(inner, const_avs, in_avs)
        except Exception as exc:
            findings.add(
                RULE_INTERP, "walk-failed",
                f"abstract interpretation of shard_map interior failed:"
                f" {exc!r}",
            )
            continue
        for j, av in enumerate(out_avs):
            names = out_names[j] if j < len(out_names) else {}
            if not names and av.varying:
                why = ("device-identity (axis_index) dependence"
                       if av.taint else
                       f"offset set {sorted(av.off or ())} does not"
                       f" prove shard-independence")
                findings.add(
                    RULE_REP, f"out{j}",
                    f"out_specs claims output {j} replicated but the"
                    f" inferred value is shard-varying ({why}); a"
                    f" first-answer-wins read of it is unsound",
                )
        _check_combine(inner, findings)


def _check_combine(jaxpr, findings: _Findings) -> None:
    """Backward slice from the interior outputs: duplicated pad lanes
    make sum/product-style reductions double-count, so the verdict path
    must be idempotent-combine only."""
    need = {v for v in jaxpr.outvars if not _is_literal(v)}
    for eqn in reversed(jaxpr.eqns):
        if not any(v in need for v in eqn.outvars):
            continue
        for a in eqn.invars:
            if not _is_literal(a):
                need.add(a)
        if eqn.primitive.name in _NON_IDEMPOTENT:
            fname, line = _eqn_src(eqn)
            findings.add(
                RULE_PAD, f"{eqn.primitive.name}@{fname}:{line}",
                f"verdict path reduces with non-idempotent"
                f" `{eqn.primitive.name}`: duplicated pad lanes"
                f" double-count under it ({fname}:{line})",
                line,
            )


def _check_pad_program(prog: SpmdProgram, closed,
                       findings: _Findings) -> None:
    jaxpr = closed.jaxpr
    if len(jaxpr.invars) != 1:
        findings.add(RULE_INTERP, "arity",
                     "pad program must take exactly one array")
        return
    var = jaxpr.invars[0]
    shape = _aval_shape(var.aval)
    n_real = prog.n_real
    if not shape or shape[-1] != n_real:
        findings.add(
            RULE_INTERP, "shape",
            f"pad program input trailing axis {shape} != n_real"
            f" {n_real}",
        )
        return
    # provenance seed: column j carries the singleton marker 1 << (j+8)
    marks = np.array([1 << (_MARK_SHIFT + j) for j in range(n_real)],
                     dtype=np.int64)
    lo = np.broadcast_to(marks, shape).copy()
    in_av = AV(IV(lo, lo.copy()))
    interp = _Interp(prog, findings, {}, set())
    const_avs = [AV(_aval_iv(getattr(v, "aval", None)))
                 for v in jaxpr.constvars]
    try:
        out_avs = interp.run_jaxpr(jaxpr, const_avs, [in_av])
    except Exception as exc:
        findings.add(RULE_INTERP, "walk-failed",
                     f"pad provenance walk failed: {exc!r}")
        return
    av = out_avs[0]
    if av.iv is None:
        findings.add(
            RULE_PAD, "unprovable",
            "pad output carries no integer interval (a float detour —"
            " e.g. a mean fill — destroys column provenance); pad"
            " lanes cannot be proved duplicates of a real column",
        )
        return
    out_shape = av.iv.shape
    if not out_shape or out_shape[-1] < n_real:
        findings.add(RULE_INTERP, "shape",
                     f"pad output shape {out_shape} narrower than"
                     f" n_real {n_real}")
        return
    markset = {int(m) for m in marks}
    flat_lo = av.iv.lo.reshape(-1, out_shape[-1])
    flat_hi = av.iv.hi.reshape(-1, out_shape[-1])
    for j in range(n_real, out_shape[-1]):
        col_lo, col_hi = flat_lo[:, j], flat_hi[:, j]
        exact = np.array_equal(col_lo, col_hi)
        vals = set(int(v) for v in col_lo) if exact else set()
        if not exact or len(vals) != 1 or next(iter(vals)) not in markset:
            got = (f"marker {sorted(vals)}" if exact
                   else f"interval [{int(col_lo.min())},"
                        f" {int(col_hi.max())}]")
            findings.add(
                RULE_PAD, f"col{j}",
                f"pad column {j} is not a duplicate of any real column"
                f" ({got} vs real markers"
                f" [{int(marks[0])}..{int(marks[-1])}]): a non-absorbing"
                f" pad lane can flip the AND-reduction verdict",
            )


def analyze_program(prog: SpmdProgram, declared) -> list:
    import jax

    findings = _Findings(prog)
    try:
        fn, args = prog.build()
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:
        findings.add(RULE_INTERP, "trace-failed",
                     f"program failed to stage: {exc!r}")
        return findings.out
    if prog.kind == "pad":
        _check_pad_program(prog, closed, findings)
    else:
        _check_mesh_program(prog, closed, declared, findings)
    return findings.out


# ---------------------------------------------------------------------------
# Donation discipline (AST, over the scanned corpus)
# ---------------------------------------------------------------------------


def _tpu_gated(node, parents) -> bool:
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, ast.If):
            try:
                if "tpu" in ast.unparse(cur.test).lower():
                    return True
            except Exception:
                pass
        cur = parents.get(id(cur))
    return False


def _donate_literal(v):
    """True: provably non-empty literal; False: provably empty;
    None: not statically known here (a Name, a call, ...)."""
    if isinstance(v, (ast.Tuple, ast.List)):
        return bool(v.elts)
    if isinstance(v, ast.Constant):
        if v.value in ((), None):
            return False
        if isinstance(v.value, int) and not isinstance(v.value, bool):
            return True
    if isinstance(v, ast.Call):
        f = v.func
        name = getattr(f, "id", getattr(f, "attr", ""))
        if name in ("tuple", "range") and not v.args:
            return False
        return None
    return None


def _donate_positions(v):
    if isinstance(v, (ast.Tuple, ast.List)):
        pos = []
        for e in v.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                pos.append(int(e.value))
            else:
                return None
        return tuple(pos)
    if isinstance(v, ast.Constant) and isinstance(v.value, int) \
            and not isinstance(v.value, bool):
        return (int(v.value),)
    return None


def donation_violations(files) -> list:
    """The spmd-donate lint over a ``[(rel_path, src)]`` corpus."""
    out: list = []
    for path, src in files:
        if "donate_argnums" not in src:
            continue
        try:
            tree = ast.parse(src, filename=path)
        except SyntaxError:
            continue
        parents: dict = {}
        for node in ast.walk(tree):
            for ch in ast.iter_child_nodes(node):
                parents[id(ch)] = node
        funcs = [n for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for call in ast.walk(tree):
            if not isinstance(call, ast.Call):
                continue
            kw = next((k for k in call.keywords
                       if k.arg == "donate_argnums"), None)
            if kw is None:
                continue
            lit = _donate_literal(kw.value)
            if lit is True and not _tpu_gated(call, parents):
                out.append(Violation(
                    rule=RULE_DONATE, path=path, line=call.lineno,
                    symbol="ungated-donation",
                    message=(
                        "donate_argnums is non-empty outside a TPU-"
                        "backend guard: CPU/GPU paths would donate live"
                        " buffers (the dispatch contract gates donation"
                        " on jax.default_backend() == 'tpu')"
                    ),
                ))
            elif lit is None and isinstance(kw.value, ast.Name):
                fn = _enclosing_function(call, parents)
                body = fn if fn is not None else tree
                for sub in ast.walk(body):
                    if not isinstance(sub, ast.Assign):
                        continue
                    if not any(isinstance(t, ast.Name)
                               and t.id == kw.value.id
                               for t in sub.targets):
                        continue
                    if _donate_literal(sub.value) is False:
                        continue
                    if not _tpu_gated(sub, parents):
                        out.append(Violation(
                            rule=RULE_DONATE, path=path, line=sub.lineno,
                            symbol="ungated-donation",
                            message=(
                                f"donation flag {kw.value.id!r} is"
                                f" assigned a possibly non-empty value"
                                f" outside a TPU-backend guard"
                            ),
                        ))
        for fn in funcs:
            out.extend(_read_after_donate(fn, path))
    return out


def _enclosing_function(node, parents):
    cur = parents.get(id(node))
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur
        cur = parents.get(id(cur))
    return None


def _read_after_donate(fn, path: str) -> list:
    """Within one function: ``k = jit(f, donate_argnums=(i,))`` then
    ``k(a, b)`` donates the positional args at those indices — any
    later read of those names (before reassignment) is a finding."""
    out: list = []
    jitted: dict = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Call):
            kw = next((k for k in node.value.keywords
                       if k.arg == "donate_argnums"), None)
            if kw is None:
                continue
            pos = _donate_positions(kw.value)
            if not pos:
                continue
            for t in node.targets:
                if isinstance(t, ast.Name):
                    jitted[t.id] = pos
    if not jitted:
        return out
    donated: list = []   # (argname, donate_lineno)
    for node in ast.walk(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in jitted):
            continue
        for p in jitted[node.func.id]:
            if p < len(node.args) and isinstance(node.args[p], ast.Name):
                donated.append((node.args[p].id, node.lineno))
    for name, line in donated:
        stores = sorted(
            n.lineno for n in ast.walk(fn)
            if isinstance(n, ast.Name) and n.id == name
            and isinstance(n.ctx, ast.Store) and n.lineno > line
        )
        horizon = stores[0] if stores else None
        for n in ast.walk(fn):
            if (isinstance(n, ast.Name) and n.id == name
                    and isinstance(n.ctx, ast.Load)
                    and n.lineno > line
                    and (horizon is None or n.lineno < horizon)):
                out.append(Violation(
                    rule=RULE_DONATE, path=path, line=n.lineno,
                    symbol="read-after-donate",
                    message=(
                        f"buffer {name!r} is read after being donated"
                        f" to a donate_argnums kernel at line {line}:"
                        f" the backing memory may already be aliased"
                        f" by the kernel's outputs"
                    ),
                ))
                break
    return out


# ---------------------------------------------------------------------------
# Live program registry
# ---------------------------------------------------------------------------

_LIVE_PATH = "lighthouse_tpu/parallel/partition.py"
_MESH_PATH = "lighthouse_tpu/parallel/mesh.py"
_POD_PATH = "lighthouse_tpu/parallel/pod.py"

# width x raw-batch shapes: every width a pod probe uses, every batch
# non-divisible so the dup-of-column-0 remainder path is always proved
_LIVE_SHAPES = ((2, 5, 8), (4, 10, 16), (8, 13, 40))
_LIMB_ROWS = 26
_WBIT_ROWS = 64


class _StubLFp:
    """Pytree-registered stand-in for the field stack's LFp: a limb
    plane plus a static bound, shaped exactly like the marshal output
    so ``named_operand_leaves``/``program_in_specs`` see the real
    operand structure without importing field code."""

    _registered = False

    def __init__(self, limbs, bound=1):
        self.limbs = limbs
        self.bound = bound

    @classmethod
    def register(cls):
        if cls._registered:
            return
        import jax

        jax.tree_util.register_pytree_node(
            cls,
            lambda x: ((x.limbs,), x.bound),
            lambda bound, ch: cls(ch[0], bound),
        )
        cls._registered = True


def _stub_verify(pk, sig, h, wbits):
    """Stub local kernel with the real kernel's SPMD-relevant shape: a
    scan with a replicated carry init (the exact pattern jax's
    check_vma rejects — see multichip.py) folding per-column bits into
    one scalar verdict via AND."""
    import jax
    import jax.numpy as jnp

    def body(c, w):
        return c & jnp.all(w > 0), None

    ok, _ = jax.lax.scan(body, jnp.asarray(True), wbits)
    ok = ok & jnp.all(pk[0].limbs < jnp.uint32(0xFFFFFFFF))
    ok = ok & jnp.all(sig[0][0].limbs < jnp.uint32(0xFFFFFFFF))
    ok = ok & jnp.all(h[0][0].limbs < jnp.uint32(0xFFFFFFFF))
    return ok


def _flat_stub_args(b_cols: int):
    import jax.numpy as jnp

    _StubLFp.register()

    def lfp():
        return _StubLFp(jnp.zeros((_LIMB_ROWS, b_cols), jnp.uint32))

    pk = (lfp(), lfp())
    sig = ((lfp(), lfp()), (lfp(), lfp()))
    h = ((lfp(), lfp()), (lfp(), lfp()))
    wbits = jnp.zeros((_WBIT_ROWS, b_cols), jnp.uint32)
    return pk, sig, h, wbits


def build_live_programs() -> list:
    """The live proof obligations: the flat and registry staged verify
    programs at every pod shape, ring_reduce replication at every
    width, and the operand/slot pad constructors."""
    from ..parallel import mesh as M
    from ..parallel import partition as P

    programs: list = []
    for width, b_raw, n_total in _LIVE_SHAPES:
        b_pad = b_raw + ((-b_raw) % width)
        n_local = n_total // width

        def mk_flat(width=width, b_pad=b_pad):
            def build():
                amesh = trace_mesh((("batch", width),))
                args = _flat_stub_args(b_pad)
                local = P.staged_local(_stub_verify, axis="batch")
                specs = P.program_in_specs(args, deferred_pk=False)
                fn = M.shard_map(local, amesh, in_specs=specs,
                                        out_specs=P._ps())
                return fn, args
            return build

        programs.append(SpmdProgram(
            name=f"verify_flat_w{width}_b{b_raw}",
            path=_LIVE_PATH, build=mk_flat(), kind="mesh",
            note=f"flat staged verify, width {width}, padded batch"
                 f" {b_pad}",
        ))

        def mk_registry(width=width, b_pad=b_pad, n_total=n_total):
            def build():
                import jax.numpy as jnp

                amesh = trace_mesh((("batch", width),))
                _StubLFp.register()

                def kern(pk, sig, h, wbits):
                    return _stub_verify(
                        (_StubLFp(pk[0]), _StubLFp(pk[1])), sig, h,
                        wbits)

                _pk, sig, h, wbits = _flat_stub_args(b_pad)
                rest = (sig, h, wbits)
                reg_x = jnp.zeros((_LIMB_ROWS, n_total), jnp.uint32)
                reg_y = jnp.zeros((_LIMB_ROWS, n_total), jnp.uint32)
                slots = jnp.zeros((b_pad,), jnp.int32)
                args = (reg_x, reg_y, slots) + rest
                local = P.staged_local(
                    kern, axis="batch", deferred_pk=True,
                    pk_wrap=lambda x, y: (x, y),
                )
                specs = P.program_in_specs(rest, deferred_pk=True)
                fn = M.shard_map(local, amesh, in_specs=specs,
                                        out_specs=P._ps())
                return fn, args
            return build

        programs.append(SpmdProgram(
            name=f"verify_registry_w{width}_b{b_raw}_n{n_total}",
            path=_LIVE_PATH, build=mk_registry(), kind="mesh",
            # slot vector (shard_map operand 2) holds validator slots:
            # registry_device_sharded zero-pads the validator axis, and
            # slots never reference pad columns -> [0, n_total - 1]
            domains={2: (0, n_total - 1)},
            note=f"registry staged verify, width {width}, registry"
                 f" {n_total} ({n_local}/shard)",
        ))

        def mk_pad(b_raw=b_raw, b_pad=b_pad):
            def build():
                import jax.numpy as jnp

                pad = b_pad - b_raw

                def f(a):
                    return P._pad_tail((a,), pad)[0] if pad else \
                        jnp.asarray(a)

                return f, (jnp.zeros((4, b_raw), jnp.int32),)
            return build

        programs.append(SpmdProgram(
            name=f"pad_operands_w{width}_b{b_raw}",
            path=_LIVE_PATH, build=mk_pad(), kind="pad", n_real=b_raw,
            note="operand dup-of-column-0 padding is absorbing",
        ))

        def mk_pad_slots(b_raw=b_raw, b_pad=b_pad):
            def build():
                import jax.numpy as jnp

                pad = b_pad - b_raw

                def f(s):
                    return P._pad_slots(s, pad)

                return f, (jnp.zeros((b_raw,), jnp.int32),)
            return build

        programs.append(SpmdProgram(
            name=f"pad_slots_w{width}_b{b_raw}",
            path=_LIVE_PATH, build=mk_pad_slots(), kind="pad",
            n_real=b_raw,
            note="slot dup-of-slot-0 padding matches operand padding",
        ))

    for width in sorted({w for w, _, _ in _LIVE_SHAPES}):
        def mk_ring(width=width):
            def build():
                import jax.numpy as jnp

                amesh = trace_mesh((("batch", width),))

                def local(x):
                    return M.ring_reduce(
                        jnp.reshape(x, ()), lambda a, b: a & b, "batch",
                    )

                fn = M.shard_map(
                    local, amesh, in_specs=P._ps("batch"),
                    out_specs=P._ps(),
                )
                return fn, (jnp.ones((width,), jnp.uint32),)
            return build

        programs.append(SpmdProgram(
            name=f"ring_reduce_w{width}",
            path=_MESH_PATH, build=mk_ring(), kind="mesh",
            note="n-1-hop ring fold is replicated (check_vma's gap)",
        ))

    # the other two dispatch consumers stage through the same builders,
    # at their own characteristic shapes: stream_epoch pushes
    # committee-sized chunk batches and the pod's canary/probe path
    # dispatches tiny known-answer batches — prove both explicitly so
    # a shape-dependent regression (e.g. a pad rule keyed on batch
    # size) cannot hide behind the three pod shapes above
    def mk_shape(width, b_pad):
        def build():
            amesh = trace_mesh((("batch", width),))
            args = _flat_stub_args(b_pad)
            local = P.staged_local(_stub_verify, axis="batch")
            specs = P.program_in_specs(args, deferred_pk=False)
            fn = M.shard_map(local, amesh, in_specs=specs,
                                    out_specs=P._ps())
            return fn, args
        return build

    programs.append(SpmdProgram(
        name="stream_chunk_w8_b64",
        path=_LIVE_PATH, build=mk_shape(8, 64), kind="mesh",
        note="stream_epoch committee-chunk shape through the flat"
             " program",
    ))
    programs.append(SpmdProgram(
        name="pod_canary_w4_b4",
        path=_POD_PATH, build=mk_shape(4, 4), kind="mesh",
        note="pod canary/probe dispatch shape (tiny known-answer"
             " batch, one column per shard)",
    ))
    return programs


def _declared_axes_live(root: str) -> tuple:
    """AST-parse the mesh module for the declared axis literals."""
    path = os.path.join(root, _MESH_PATH)
    try:
        with open(path, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=path)
    except (OSError, SyntaxError):
        return ("batch",)
    axes = []
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id.endswith("_AXIS") \
                        and isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str):
                    axes.append(node.value.value)
    return tuple(axes) or ("batch",)


# ---------------------------------------------------------------------------
# Cache + audit entry
# ---------------------------------------------------------------------------

_CACHE_STATS = {"hits": 0, "misses": 0}


def _spmd_fingerprint(root: str) -> str:
    """The range-family fingerprint (which already covers partition.py
    and mesh.py) extended with this module: editing the prover
    invalidates spmd verdicts without discarding the minutes-scale
    range traces."""
    import hashlib

    from . import range_lint

    h = hashlib.sha256()
    h.update(range_lint._proof_fingerprint(root).encode())
    rel = "lighthouse_tpu/analysis/spmd_lint.py"
    h.update(rel.encode())
    try:
        with open(os.path.join(root, rel), "rb") as f:
            h.update(f.read())
    except OSError:
        h.update(b"?")
    return h.hexdigest()


def _load_defs(root: str, rel_path: str):
    full = os.path.join(root, rel_path)
    spec = importlib.util.spec_from_file_location("spmd_defs_corpus", full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def generate(root: str, cfg) -> list:
    """Trace + prove the program registry (cached); no report dict —
    the theorems are pass/fail, there is no numeric envelope to pin."""
    from .range_lint import _CACHE_FILE

    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0
    try:
        import jax  # noqa: F401
    except Exception as exc:  # pragma: no cover - jax is baked in
        return [Violation(
            rule=RULE_INTERP, path="lighthouse_tpu/analysis/spmd_lint.py",
            line=0, symbol="import-jax",
            message=f"spmd family needs jax to stage programs: {exc}",
        )]
    defs_rel = getattr(cfg, "spmd_defs", None)
    if defs_rel:
        try:
            mod = _load_defs(root, defs_rel)
            programs = list(mod.build_programs())
            declared = set(getattr(mod, "DECLARED_AXES", ("batch",)))
        except Exception as exc:
            return [Violation(
                rule=RULE_INTERP, path=defs_rel, line=0, symbol="defs",
                message=f"spmd defs module failed to load: {exc!r}",
            )]
    else:
        programs = build_live_programs()
        declared = set(_declared_axes_live(root))
    use_cache = bool(getattr(cfg, "range_cache", True)) and not defs_rel
    cache_path = os.path.join(root, _CACHE_FILE)
    fingerprint = _spmd_fingerprint(root) if use_cache else ""
    cached: dict = {}
    disk: dict = {}
    if use_cache:
        try:
            with open(cache_path, encoding="utf-8") as f:
                disk = json.load(f)
            if disk.get("spmd_fingerprint") == fingerprint:
                cached = dict(disk.get("spmd_programs") or {})
        except (OSError, ValueError):
            disk, cached = {}, {}
    violations: list = []
    dirty = False
    for prog in programs:
        entry = cached.get(prog.name)
        if entry is not None:
            _CACHE_STATS["hits"] += 1
            vios = [Violation(**v) for v in entry["violations"]]
        else:
            _CACHE_STATS["misses"] += 1
            vios = analyze_program(prog, declared)
            if use_cache:
                cached[prog.name] = {
                    "violations": [v.to_dict() for v in vios],
                }
                dirty = True
        violations.extend(vios)
    if use_cache and dirty:
        # shared file: carry the range family's sections through
        doc = {k: v for k, v in disk.items()
               if not k.startswith("spmd_")}
        doc["spmd_fingerprint"] = fingerprint
        doc["spmd_programs"] = cached
        try:
            with open(cache_path, "w", encoding="utf-8") as f:
                json.dump(doc, f)
        except OSError:
            pass
    return violations


def run(root: str, cfg, files) -> list:
    """Audit-family entry: staged-program theorems + donation lint."""
    violations = generate(root, cfg)
    violations.extend(donation_violations(files))
    return violations
