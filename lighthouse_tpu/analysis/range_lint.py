"""Limb-range abstract interpreter — the ``range`` audit family.

Every field kernel in the BLS stack (``crypto/bls/jax_backend``) computes
on 26x15-bit quasi-normalized uint32 limbs and justifies carry/overflow
safety by hand-reasoned bounds: ``fp.py`` carries trace-time ``LFp``
value bounds (units of P), the Pallas kernel interiors justify uint32
safety in comments.  This module machine-checks both layers.

**Interval layer** (``_Interp``): an abstract interpreter over jaxprs.
Every registered kernel program (``build_live_programs``) is traced with
``jax.make_jaxpr`` — Pallas kernels in interpret mode, so the kernel
body rides along as the ``pallas_call`` eqn's ``jaxpr`` param — and
per-element integer intervals are propagated through every primitive
(add/mul/shift/and/select/concat/pad/``scan`` fixpoint with widening/
...).  It proves, per program:

* theorem class 1 — **no uint32 overflow**: every integer intermediate
  stays inside its dtype; a violation names the eqn site and the
  computed interval;
* theorem class 2 — **representation contracts**: declared output
  contracts hold (STRICT limbs < 2^15 out of ``_mont_reduce``'s masked
  carry chain, quasi limbs <= QMAX after carry passes, and the
  ``fp_sub``/``ksub`` bias columns never underflow given the declared
  subtrahend bound — the per-k ``*_sub_k*``/``*_ksub_k*`` programs).

**Exact layer** (``lfp_check``): the hand-derived bound *algebra* in
``fp.py`` is re-derived in exact ``fractions.Fraction`` arithmetic —
``mont_mul``'s claimed ``prod/MONT_DIVISOR + MONT_EPS`` output bound
against the true ``prod*P/R + 1``, ``REDUCE_PIN``, the ``fp_pow``
fixpoint closure, ``MAX_BOUND`` top-column carry headroom, and the
per-k bias tables (value == k*P, low limbs >= QMAX, and the top-limb
domination rule enforced by ``fp._k_for``).  Theorem class 3: any
unsound constant is a ``range-lfp`` violation; a sound-but-loose one
(relative slack above ``SLACK_MAX``) is a ``range-slack`` violation.

**Why two layers.**  Top-limb facts like "a value < 2P has limb 25
<= floor(2P / 2^375) = 104" are *value*-bound consequences, not
derivable from limb intervals (a Montgomery output's limb interval is
[0, 2^15) — the interval layer cannot see that its *value* is < 2P).
The proof is therefore modular: the exact layer validates the bound
algebra, which justifies the per-limb input caps (``caps_iv``) fed to
the interval layer; the interval layer then closes the induction by
proving each op preserves the representation invariants for *all*
inputs satisfying those caps.  Whole-kernel composition runs (the
``heavy`` programs) set ``clamp_sub=True``: interior bias subtractions
are clamped non-negative without a finding because the per-k op
programs already discharge that obligation universally — the
composition run still proves accumulation/overflow safety and output
contracts.

**MXU report** (``mxu_report``): per-kernel max accumulation magnitude
from the interval run, the direct dot-product column magnitude of the
15-bit representation, the generic limb-split table (w <= 9 for
f32-mantissa MXU accumulation, w <= 13 for int32), and the
``selected_split`` block for the split ``pallas_mxu`` actually ships
(w=13, 31 limbs, int32 column budget 31 * QMAX13^2 < 2^31).  The MXU
kernels are registered programs like any other — their dot-product
column proof rides the precise non-negative ``dot_general`` transfer,
which in turn needs the iota/div/rem/eq handlers to constant-fold the
in-kernel band matrix to its exact 0/1 entries.  The full result is
serialized as ``RANGE_REPORT.json`` and checked in; the audit
regenerates it and fails with ``range-report`` on drift.

**Proof cache**: per-program verdicts are replayed from
``.range_proof_cache.json`` when a sha256 fingerprint over the kernel
sources (+ this module + jax/numpy versions) is unchanged — the
interpret-mode traces dominate audit wall time; the warm path skips
them all.  ``--no-cache`` (``cfg.range_cache = False``) forces fresh
traces; cached and fresh runs produce byte-identical verdicts.

Fixture corpora re-point the registry via the ``range_defs`` audit
config key (a python file exposing ``build_programs()`` /
``LFP_CLAIMS``); see ``tests/fixtures/lint/range_defs.py``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
from dataclasses import dataclass
from fractions import Fraction

import numpy as np

from .report import Violation

RULE_OVERFLOW = "range-overflow"
RULE_CONTRACT = "range-contract"
RULE_LFP = "range-lfp"
RULE_SLACK = "range-slack"
RULE_INTERP = "range-interp"
RULE_REPORT = "range-report"

# sound-but-loose threshold: relative slack of a claimed bound over the
# exact one.  Live constants sit well under (max ~10.3% on REDUCE_PIN).
SLACK_MAX = 0.5

# saturation ceiling for interval endpoints; interval arithmetic runs in
# float64 (exact below 2^53 — far above any sound kernel's 2^36) and
# clips here, so int64 endpoint math can never itself wrap
_SAT = 1 << 62

_DTYPE_RANGE = {
    "uint8": (0, (1 << 8) - 1),
    "uint16": (0, (1 << 16) - 1),
    "uint32": (0, (1 << 32) - 1),
    "uint64": (0, _SAT),
    "int8": (-(1 << 7), (1 << 7) - 1),
    "int16": (-(1 << 15), (1 << 15) - 1),
    "int32": (-(1 << 31), (1 << 31) - 1),
    "int64": (-_SAT, _SAT),
}

MAX_FINDINGS_PER_PROGRAM = 8
_FIX_ITERS = 64     # scan/while fixpoint iteration cap; must exceed 2N+2:
                    #  the shift-register scans in fp._mul_cols_wide/_low
                    #  stabilise one accumulator slot per round (52 slots)
_WIDEN_AFTER = 56   # rounds before power-of-two widening kicks in; widening
                    #  an additive chain early cascades one bit per round,
                    #  so it must start only after natural convergence fails

DEFAULT_REPORT = "RANGE_REPORT.json"


# ---------------------------------------------------------------------------
# Interval arrays
# ---------------------------------------------------------------------------


def _i64(arr):
    """Clip a float64 array into the saturation range and cast int64."""
    return np.clip(np.asarray(arr, dtype=np.float64),
                   -float(_SAT), float(_SAT)).astype(np.int64)


class IV:
    """Per-element integer interval: two int64 arrays of the aval shape."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo, hi):
        self.lo = np.asarray(lo, dtype=np.int64)
        self.hi = np.asarray(hi, dtype=np.int64)

    @property
    def shape(self):
        return self.lo.shape

    @classmethod
    def const(cls, arr):
        a = _i64(np.asarray(arr, dtype=np.float64))
        return cls(a, a.copy())

    @classmethod
    def full(cls, shape, lo, hi):
        return cls(np.full(shape, lo, dtype=np.int64),
                   np.full(shape, hi, dtype=np.int64))

    def broadcast(self, shape):
        return IV(np.broadcast_to(self.lo, shape).copy(),
                  np.broadcast_to(self.hi, shape).copy())

    def join(self, other):
        return IV(np.minimum(self.lo, other.lo), np.maximum(self.hi, other.hi))

    def contains(self, other) -> bool:
        return bool(np.all(self.lo <= other.lo) and np.all(self.hi >= other.hi))

    def clamp(self, lo, hi):
        return IV(np.clip(self.lo, lo, hi), np.clip(self.hi, lo, hi))

    def min_lo(self) -> int:
        return int(self.lo.min()) if self.lo.size else 0

    def max_hi(self) -> int:
        return int(self.hi.max()) if self.hi.size else 0


def iv_add(a: IV, b: IV) -> IV:
    return IV(_i64(a.lo.astype(np.float64) + b.lo.astype(np.float64)),
              _i64(a.hi.astype(np.float64) + b.hi.astype(np.float64)))


def iv_sub(a: IV, b: IV) -> IV:
    return IV(_i64(a.lo.astype(np.float64) - b.hi.astype(np.float64)),
              _i64(a.hi.astype(np.float64) - b.lo.astype(np.float64)))


def iv_mul(a: IV, b: IV) -> IV:
    al, ah = a.lo.astype(np.float64), a.hi.astype(np.float64)
    bl, bh = b.lo.astype(np.float64), b.hi.astype(np.float64)
    cands = np.stack(np.broadcast_arrays(al * bl, al * bh, ah * bl, ah * bh))
    return IV(_i64(cands.min(axis=0)), _i64(cands.max(axis=0)))


def log2_or_zero(v) -> float:
    v = float(v)
    return round(math.log2(v), 2) if v > 0 else 0.0


# ---------------------------------------------------------------------------
# Program registry
# ---------------------------------------------------------------------------


@dataclass
class RangeProgram:
    """One proof obligation: a traceable callable plus input intervals.

    ``build()`` returns ``(fn, example_args, in_ivs)`` — ``fn`` is traced
    with ``jax.make_jaxpr(fn)(*example_args)`` and ``in_ivs`` (aligned
    with the jaxpr invars; ``None`` or a short list is completed by
    ``_default_ivs``) define the universally-quantified input set.
    ``contracts`` is a tuple of ``(out_index, kind)`` with kind one of
    ``"strict"`` (< 2^15), ``"quasi"`` (<= QMAX) or ``("max", cap)``.
    ``clamp_sub=True`` marks a whole-kernel composition run whose bias
    subtractions are discharged by the per-k op programs (see module
    docstring); ``heavy`` marks minutes-scale traces the fast test tier
    skips.
    """

    name: str
    path: str
    build: object
    contracts: tuple = ()
    clamp_sub: bool = False
    heavy: bool = False
    note: str = ""


# ---------------------------------------------------------------------------
# The abstract interpreter
# ---------------------------------------------------------------------------


def _eqn_src(eqn) -> tuple:
    """(source file hint, line) for an eqn, best effort."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return frame.file_name, int(frame.start_line)
    except Exception:
        pass
    return "", 0


class _Findings:
    """Deduplicated finding collector for one program."""

    def __init__(self, program: RangeProgram):
        self.program = program
        self.by_key: dict = {}
        self.order: list = []

    def add(self, rule: str, symbol: str, message: str, line: int = 0):
        key = (rule, symbol, line)
        if key in self.by_key:
            self.by_key[key] += 1
            return
        self.by_key[key] = 1
        self.order.append((rule, symbol, message, line))

    def violations(self) -> list:
        out = []
        for rule, symbol, message, line in self.order[:MAX_FINDINGS_PER_PROGRAM]:
            n = self.by_key[(rule, symbol, line)]
            if n > 1:
                message += f" [x{n} eqns at this site]"
            out.append(Violation(
                rule=rule, path=self.program.path, line=line,
                symbol=f"{self.program.name}:{symbol}", message=message,
            ))
        dropped = len(self.order) - MAX_FINDINGS_PER_PROGRAM
        if dropped > 0:
            out.append(Violation(
                rule=self.order[MAX_FINDINGS_PER_PROGRAM][0],
                path=self.program.path, line=0,
                symbol=f"{self.program.name}:more",
                message=f"{dropped} further distinct finding sites suppressed",
            ))
        return out


def _dtype_range(aval):
    dt = getattr(aval, "dtype", None)
    if dt is None:
        return None
    name = np.dtype(dt).name
    if name == "bool":
        return (0, 1)
    return _DTYPE_RANGE.get(name)


def _aval_shape(aval):
    return tuple(getattr(aval, "shape", ()))


def _is_literal(atom) -> bool:
    return hasattr(atom, "val")


class _Interp:
    """Interval evaluation of one (possibly nested) jaxpr."""

    def __init__(self, program: RangeProgram, findings: _Findings):
        self.program = program
        self.findings = findings
        self.eqn_count = 0
        self.max_any = 0   # max |endpoint| over every integer intermediate
        self.max_acc = 0   # max over `add` outputs — accumulation magnitude
        self.max_dot = 0   # max over `dot_general` outputs — MXU column sums
        self.unknown_prims: set = set()
        self._swap_target = None
        self._ref_state: dict = {}

    # -- jaxpr evaluation --------------------------------------------------

    def run_closed(self, closed, in_ivs):
        consts = [IV.const(np.asarray(c)) for c in closed.consts]
        return self.run_jaxpr(closed.jaxpr, consts, in_ivs)

    def run_jaxpr(self, jaxpr, const_ivs, in_ivs):
        env: dict = {}

        def write(var, iv):
            if type(var).__name__ == "DropVar":
                return
            env[var] = iv

        def read(atom):
            if _is_literal(atom):
                return IV.const(np.asarray(atom.val))
            return env[atom]

        for var, iv in zip(jaxpr.constvars, const_ivs):
            write(var, iv)
        for var, iv in zip(jaxpr.invars, in_ivs):
            write(var, iv)

        # liveness: drop intermediates after their last use so deep
        # kernels (a fused Miller step is ~180k eqns) hold a bounded
        # working set instead of every interval ever computed
        last_use: dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for a in eqn.invars:
                if not _is_literal(a):
                    last_use[a] = i
        keep = set(jaxpr.invars) | set(jaxpr.constvars)
        for v in jaxpr.outvars:
            if not _is_literal(v):
                keep.add(v)

        for i, eqn in enumerate(jaxpr.eqns):
            ins = [read(a) for a in eqn.invars]
            self._swap_target = None
            outs = self.eval_eqn(eqn, ins)
            if self._swap_target is not None:
                # a `swap` stored into a ref: rebind the ref var (and the
                # kernel-body ref state, for pallas output refs)
                tvar, tiv = self._swap_target
                env[tvar] = tiv
                if tvar in self._ref_state:
                    self._ref_state[tvar] = tiv
                self._swap_target = None
            for var, iv in zip(eqn.outvars, outs):
                iv = self._post(eqn, var, iv)
                write(var, iv)
            for a in eqn.invars:
                if not _is_literal(a) and last_use.get(a) == i \
                        and a not in keep and a in env:
                    del env[a]
        return [read(v) for v in jaxpr.outvars]

    def run_ref_body(self, body, ref_ivs):
        """Evaluate a pallas kernel body whose invars are refs."""
        self._ref_state = dict(zip(body.invars, ref_ivs))
        self.run_jaxpr(body, [], ref_ivs)

    # -- per-eqn postprocessing: overflow theorem + stats -----------------

    def _post(self, eqn, var, iv: IV) -> IV:
        self.eqn_count += 1
        rng = _dtype_range(getattr(var, "aval", None))
        if rng is None or not iv.lo.size:
            return iv
        if eqn.primitive.name == "swap":
            # the returned pre-write buffer contents (kernels discard them)
            # carry the out-ref's initial full-range state, not a computed
            # value; counting them would pin max_any at the dtype ceiling
            return iv
        mag = max(abs(iv.min_lo()), abs(iv.max_hi()))
        if mag > self.max_any:
            self.max_any = mag
        name = eqn.primitive.name
        if name == "add" and iv.max_hi() > self.max_acc:
            self.max_acc = iv.max_hi()
        if name == "dot_general" and iv.max_hi() > self.max_dot:
            self.max_dot = iv.max_hi()
        lo_ok, hi_ok = iv.min_lo() >= rng[0], iv.max_hi() <= rng[1]
        if lo_ok and hi_ok:
            return iv
        if name == "sub" and self.program.clamp_sub and hi_ok:
            # composition run: interior bias-subtraction non-negativity
            # is discharged universally by the per-k op programs
            return iv.clamp(rng[0], rng[1])
        fname, line = _eqn_src(eqn)
        dt = np.dtype(var.aval.dtype).name
        self.findings.add(
            RULE_OVERFLOW, f"{name}@{os.path.basename(fname) or '?'}:{line}",
            f"`{name}` interval [{iv.min_lo()}, {iv.max_hi()}] escapes "
            f"{dt} (2^{log2_or_zero(mag)}) at {fname}:{line}",
            line,
        )
        return iv.clamp(rng[0], rng[1])

    # -- eqn dispatch ------------------------------------------------------

    def eval_eqn(self, eqn, ins):
        handler = _HANDLERS.get(eqn.primitive.name)
        if handler is not None:
            return handler(self, eqn, ins)
        sub = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if sub is not None:   # pjit / closed_call / custom_* wrappers
            if hasattr(sub, "consts"):
                return self.run_closed(sub, ins)
            return self.run_jaxpr(sub, [], ins)
        return self.unknown(eqn, ins)

    def unknown(self, eqn, ins):
        name = eqn.primitive.name
        if name not in self.unknown_prims:
            self.unknown_prims.add(name)
            self.findings.add(
                RULE_INTERP, name,
                f"no interval transfer for primitive `{name}`; result "
                f"assumed full dtype range (analysis precision loss)",
            )
        outs = []
        for var in eqn.outvars:
            rng = _dtype_range(var.aval) or (-_SAT, _SAT)
            outs.append(IV.full(_aval_shape(var.aval), rng[0], rng[1]))
        return outs


# -- primitive handlers ------------------------------------------------------


def _h_add(it, eqn, ins):
    return [iv_add(ins[0], ins[1])]


def _h_sub(it, eqn, ins):
    return [iv_sub(ins[0], ins[1])]


def _h_mul(it, eqn, ins):
    return [iv_mul(ins[0], ins[1])]


def _h_and(it, eqn, ins):
    a, b = ins
    if a.min_lo() >= 0 and b.min_lo() >= 0:
        if _is_exact(a) and _is_exact(b):   # e.g. floor-correction preds
            v = np.broadcast_arrays(a.lo, b.lo)
            v = (v[0] & v[1]).copy()
            return [IV(v, v.copy())]
        hi = np.minimum(*np.broadcast_arrays(a.hi, b.hi)).copy()
        return [IV(np.zeros_like(hi), hi)]
    return it.unknown(eqn, ins)


def _h_or_xor(it, eqn, ins):
    a, b = ins
    if a.min_lo() >= 0 and b.min_lo() >= 0:
        cap = (1 << max(a.max_hi(), b.max_hi(), 1).bit_length()) - 1
        shape = np.broadcast_shapes(a.shape, b.shape)
        return [IV.full(shape, 0, cap)]
    return it.unknown(eqn, ins)


def _h_shr(it, eqn, ins):
    a, s = ins
    if a.min_lo() >= 0 and s.min_lo() >= 0:
        s_lo, s_hi = s.min_lo(), min(s.max_hi(), 63)
        shape = np.broadcast_shapes(a.shape, s.shape)
        return [IV(np.broadcast_to(a.lo >> s_hi, shape).copy(),
                   np.broadcast_to(a.hi >> s_lo, shape).copy())]
    return it.unknown(eqn, ins)


def _h_shl(it, eqn, ins):
    a, s = ins
    if a.min_lo() >= 0 and s.min_lo() >= 0:
        s_lo, s_hi = s.min_lo(), min(s.max_hi(), 62)
        shape = np.broadcast_shapes(a.shape, s.shape)
        lo = _i64(np.broadcast_to(a.lo, shape).astype(np.float64)
                  * float(1 << s_lo))
        hi = _i64(np.broadcast_to(a.hi, shape).astype(np.float64)
                  * float(1 << s_hi))
        return [IV(lo, hi)]
    return it.unknown(eqn, ins)


def _is_exact(iv: IV) -> bool:
    return bool(np.array_equal(iv.lo, iv.hi))


_CMP_NP = {
    "eq": np.equal, "ne": np.not_equal, "lt": np.less, "le": np.less_equal,
    "gt": np.greater, "ge": np.greater_equal,
}


def _h_cmp(it, eqn, ins):
    # exact on degenerate intervals — load-bearing for the MXU path: the
    # band matrix is built in-kernel as `(iota // n + iota % n) == k`
    # (pallas_call forbids captured constants), and the dot-product
    # column proof needs the exact 0/1 band, not the [0, 1] envelope
    # ([0, 1] weights would put every outer-product element in every
    # column: ~2^37 >> 2^31).  jnp's integer `//`/`%` also lower their
    # floor corrections through lt/ne over exact values.
    a, b = ins
    shape = np.broadcast_shapes(a.shape, b.shape)
    op = _CMP_NP.get(eqn.primitive.name)
    if op is not None and _is_exact(a) and _is_exact(b):
        v = op(np.broadcast_to(a.lo, shape),
               np.broadcast_to(b.lo, shape)).astype(np.int64)
        return [IV(v.copy(), v.copy())]
    return [IV.full(shape, 0, 1)]


def _h_sign(it, eqn, ins):
    # sign is monotone, so endpoint evaluation is sound and exact on
    # degenerate intervals (jnp floor_div/floor_mod corrections use it)
    a = ins[0]
    return [IV(np.sign(a.lo).copy(), np.sign(a.hi).copy())]


def _h_div(it, eqn, ins):
    # jax integer `div` rounds toward zero == floor for non-negative
    # operands, so monotone endpoint division is exact on degenerate
    # intervals and sound everywhere non-negative
    a, b = ins
    if a.min_lo() >= 0 and b.min_lo() >= 1:
        shape = np.broadcast_shapes(a.shape, b.shape)
        return [IV(np.broadcast_to(a.lo, shape) // np.broadcast_to(b.hi, shape),
                   np.broadcast_to(a.hi, shape) // np.broadcast_to(b.lo, shape))]
    return it.unknown(eqn, ins)


def _h_rem(it, eqn, ins):
    a, b = ins
    if a.min_lo() >= 0 and b.min_lo() >= 1:
        shape = np.broadcast_shapes(a.shape, b.shape)
        if _is_exact(a) and _is_exact(b):
            v = np.broadcast_to(a.lo, shape) % np.broadcast_to(b.lo, shape)
            return [IV(v.copy(), v.copy())]
        hi = np.minimum(np.broadcast_to(a.hi, shape),
                        np.broadcast_to(b.hi, shape) - 1).copy()
        return [IV(np.zeros_like(hi), hi)]
    return it.unknown(eqn, ins)


def _h_select_n(it, eqn, ins):
    pred, cases = ins[0], ins[1:]
    shape = _aval_shape(eqn.outvars[0].aval)
    if pred.min_lo() == pred.max_hi():   # statically-known selector
        idx = int(pred.min_lo())
        if 0 <= idx < len(cases):
            return [cases[idx].broadcast(shape)]
    out = cases[0]
    for c in cases[1:]:
        out = out.join(c)
    return [out.broadcast(shape)]


def _h_broadcast_in_dim(it, eqn, ins):
    shape = tuple(eqn.params["shape"])
    bdims = tuple(eqn.params["broadcast_dimensions"])
    src = ins[0]
    reshape = [1] * len(shape)
    for i, d in enumerate(bdims):
        reshape[d] = src.shape[i] if i < len(src.shape) else 1
    return [IV(np.broadcast_to(src.lo.reshape(reshape), shape).copy(),
               np.broadcast_to(src.hi.reshape(reshape), shape).copy())]


def _h_reshape(it, eqn, ins):
    shape = _aval_shape(eqn.outvars[0].aval)
    return [IV(ins[0].lo.reshape(shape), ins[0].hi.reshape(shape))]


def _h_slice(it, eqn, ins):
    p = eqn.params
    strides = p.get("strides") or (1,) * len(p["start_indices"])
    idx = tuple(slice(s, l, st) for s, l, st in
                zip(p["start_indices"], p["limit_indices"], strides))
    return [IV(ins[0].lo[idx].copy(), ins[0].hi[idx].copy())]


def _h_concatenate(it, eqn, ins):
    d = eqn.params["dimension"]
    return [IV(np.concatenate([iv.lo for iv in ins], axis=d),
               np.concatenate([iv.hi for iv in ins], axis=d))]


def _h_pad(it, eqn, ins):
    operand, padval = ins
    cfg = eqn.params["padding_config"]
    shape = _aval_shape(eqn.outvars[0].aval)
    lo = np.full(shape, padval.min_lo(), dtype=np.int64)
    hi = np.full(shape, padval.max_hi(), dtype=np.int64)
    idx = tuple(slice(max(l, 0), max(l, 0) + (d - 1) * (i + 1) + 1, i + 1)
                for (l, _h, i), d in zip(cfg, operand.shape))
    try:
        lo[idx] = operand.lo
        hi[idx] = operand.hi
    except ValueError:
        return it.unknown(eqn, ins)   # negative (clipping) pads: unused here
    return [IV(lo, hi)]


def _h_transpose(it, eqn, ins):
    perm = eqn.params["permutation"]
    return [IV(np.transpose(ins[0].lo, perm).copy(),
               np.transpose(ins[0].hi, perm).copy())]


def _h_rev(it, eqn, ins):
    dims = tuple(eqn.params["dimensions"])
    return [IV(np.flip(ins[0].lo, dims).copy(),
               np.flip(ins[0].hi, dims).copy())]


def _h_iota(it, eqn, ins):
    shape = _aval_shape(eqn.outvars[0].aval)
    d = eqn.params["dimension"]
    vals = np.arange(shape[d], dtype=np.int64)
    vals = np.broadcast_to(
        vals.reshape([-1 if i == d else 1 for i in range(len(shape))]), shape)
    return [IV(vals.copy(), vals.copy())]


def _h_identity(it, eqn, ins):
    return [IV(ins[0].lo.copy(), ins[0].hi.copy())]


def _h_scatter_add(it, eqn, ins):
    # blunt but sound: every output element may absorb any update sum;
    # the XLA mont path's `.at[].add` touches each slot once, so the
    # global update min/max is the exact increment envelope
    operand, _indices, updates = ins
    return [IV(_i64(operand.lo.astype(np.float64) + min(0, updates.min_lo())),
               _i64(operand.hi.astype(np.float64) + max(0, updates.max_hi())))]


def _h_reduce_sum(it, eqn, ins):
    axes = tuple(eqn.params["axes"])
    return [IV(_i64(ins[0].lo.astype(np.float64).sum(axis=axes)),
               _i64(ins[0].hi.astype(np.float64).sum(axis=axes)))]


def _h_reduce_minmax(it, eqn, ins):
    axes = tuple(eqn.params["axes"])
    return [IV(ins[0].lo.min(axis=axes), ins[0].hi.max(axis=axes))]


def _h_min(it, eqn, ins):
    a, b = ins
    return [IV(np.minimum(*np.broadcast_arrays(a.lo, b.lo)).copy(),
               np.minimum(*np.broadcast_arrays(a.hi, b.hi)).copy())]


def _h_max(it, eqn, ins):
    a, b = ins
    return [IV(np.maximum(*np.broadcast_arrays(a.lo, b.lo)).copy(),
               np.maximum(*np.broadcast_arrays(a.hi, b.hi)).copy())]


def _h_dot_general(it, eqn, ins):
    a, b = ins
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    shape = _aval_shape(eqn.outvars[0].aval)
    if not lb and not rb and a.min_lo() >= 0 and b.min_lo() >= 0:
        # precise non-negative interval matmul: every product term is
        # monotone in both endpoints, so lo = lo.lo and hi = hi.hi,
        # contracted per element.  This is what proves the MXU column
        # budget: an exact 0/1 band row sums only its own diagonal's
        # outer products (31 * 8194^2 < 2^31), where the k*max*max
        # envelope below would claim 961 * 8194^2.  float64 is exact
        # here (sums stay far below 2^53) and _i64 saturates the cast.
        lo = np.tensordot(a.lo.astype(np.float64),
                          b.lo.astype(np.float64), axes=(lc, rc))
        hi = np.tensordot(a.hi.astype(np.float64),
                          b.hi.astype(np.float64), axes=(lc, rc))
        if np.shape(lo) == shape:
            return [IV(_i64(lo), _i64(hi))]
    # coarse envelope (mixed signs / batch dims): k * max|a| * max|b|
    k = 1
    for d in lc:
        k *= a.shape[d]
    mag = float(k) * max(abs(a.min_lo()), abs(a.max_hi())) \
        * max(abs(b.min_lo()), abs(b.max_hi()))
    lo = 0.0 if (a.min_lo() >= 0 and b.min_lo() >= 0) else -mag
    return [IV.full(shape, int(_i64(np.float64(lo))),
                    int(_i64(np.float64(mag))))]


def _h_get(it, eqn, ins):
    ref = ins[0]
    out_shape = _aval_shape(eqn.outvars[0].aval)
    if ref.shape == out_shape:
        return [IV(ref.lo.copy(), ref.hi.copy())]
    # indexed read (e.g. the SMEM digit tape): envelope of the ref
    return [IV.full(out_shape, ref.min_lo(), ref.max_hi())]


def _h_swap(it, eqn, ins):
    ref_var = eqn.invars[0]
    old, val = ins[0], ins[1]
    out_shape = _aval_shape(eqn.outvars[0].aval)
    if val.shape == old.shape:
        new = IV(val.lo.copy(), val.hi.copy())
    else:   # partial store: conservative join over the whole ref
        new = old.join(IV.full(old.shape, val.min_lo(), val.max_hi()))
    it._swap_target = (ref_var, new)
    if old.shape == out_shape:
        return [IV(old.lo.copy(), old.hi.copy())]
    return [IV.full(out_shape, old.min_lo(), old.max_hi())]


def _widen(iv: IV) -> IV:
    hi = (1 << min(62, max(1, iv.max_hi()).bit_length() + 1)) - 1
    lo_m = iv.min_lo()
    lo = 0 if lo_m >= 0 else -(1 << min(62, int(-lo_m).bit_length() + 1))
    return IV.full(iv.shape, lo, hi)


def _fixpoint(it, run_body, carry, what, pinned=()):
    state = list(carry)
    for rounds in range(_FIX_ITERS):
        outs = run_body(state)
        stable, nxt = True, []
        for i, (old, new) in enumerate(zip(state, outs[:len(state)])):
            if i in pinned or old.contains(new):
                nxt.append(old)
                continue
            stable = False
            j = old.join(new)
            if rounds >= _WIDEN_AFTER:
                j = _widen(j)
            nxt.append(j)
        state = nxt
        if stable:
            return state, outs
    it.findings.add(
        RULE_INTERP, f"{what}-fixpoint",
        f"{what} carry did not converge within {_FIX_ITERS} iterations; "
        f"intervals widened to saturation",
    )
    state = [IV.full(s.shape, -_SAT, _SAT) for s in state]
    return state, run_body(state)


def _scan_counter_pins(body, nc, ncarry, carry, length):
    """Exact ranges for arithmetic-progression carry slots.

    ``fori_loop`` lowers to ``scan`` with its counter in the carry; a
    counter has no fixpoint (it strictly increments), but the scan's
    static trip count bounds it exactly: a slot whose body output is
    ``add(slot_invar, literal c)`` holds ``init + c*t`` for
    ``t in [0, length-1]``."""
    pins = {}
    if not length:
        return pins
    try:
        jaxpr = body.jaxpr
        for i in range(ncarry):
            ov, in_v = jaxpr.outvars[i], jaxpr.invars[nc + i]
            for eq in jaxpr.eqns:
                if (len(eq.outvars) != 1 or eq.outvars[0] is not ov
                        or eq.primitive.name != "add"):
                    continue
                a, b = eq.invars
                c = None
                if a is in_v and _is_literal(b):
                    c = int(b.val)
                elif b is in_v and _is_literal(a):
                    c = int(a.val)
                if c is None:
                    continue
                lo0, hi0 = carry[i].min_lo(), carry[i].max_hi()
                last = c * (int(length) - 1)
                pins[i] = IV.full(carry[i].shape,
                                  min(lo0, lo0 + last), max(hi0, hi0 + last))
    except Exception:
        return {}
    return pins


def _h_scan(it, eqn, ins):
    p = eqn.params
    nc, ncarry = p["num_consts"], p["num_carry"]
    body = p["jaxpr"]
    consts, carry, xs = ins[:nc], ins[nc:nc + ncarry], ins[nc + ncarry:]
    x_elems = []
    for iv in xs:
        if iv.lo.ndim >= 1 and iv.lo.shape[0] > 0:
            x_elems.append(IV(iv.lo.min(axis=0), iv.hi.max(axis=0)))
        else:
            x_elems.append(IV.full(iv.shape[1:], 0, 0))
    pins = _scan_counter_pins(body, nc, ncarry, carry, p.get("length"))
    carry = [pins.get(i, c) for i, c in enumerate(carry)]

    def run_body(state):
        return it.run_closed(body, consts + state + x_elems)

    state, outs = _fixpoint(it, run_body, carry, "scan",
                            pinned=frozenset(pins))
    stacked = []
    for y, var in zip(outs[ncarry:], eqn.outvars[ncarry:]):
        shape = _aval_shape(var.aval)
        stacked.append(IV(np.broadcast_to(y.lo, shape).copy(),
                          np.broadcast_to(y.hi, shape).copy()))
    return list(state) + stacked


def _while_counter_caps(p, cond_consts):
    """Strict upper bounds the loop condition imposes on carry slots.

    ``fori_loop`` lowers to ``while`` with an ``i < n`` condition; without
    this refinement the counter has no fixpoint and widens to saturation.
    Sound for any ``lt(carry_i, B)``: while the body runs the condition
    held, so carry_i <= hi(B) - 1 inside the body (the loop *output* may
    still equal hi(B) and is not clamped)."""
    caps = {}
    try:
        jaxpr = p["cond_jaxpr"].jaxpr
        cn = len(cond_consts)
        slot = {v: i - cn for i, v in enumerate(jaxpr.invars)}
        out = jaxpr.outvars[0]
        for eq in jaxpr.eqns:
            if eq.primitive.name != "lt" or eq.outvars[0] is not out:
                continue
            a, b = eq.invars
            if _is_literal(a) or slot.get(a, -1) < 0:
                continue
            if _is_literal(b):
                caps[slot[a]] = int(b.val) - 1
            elif b in slot:
                bound = cond_consts[slot[b]] if slot[b] < 0 else None
                if bound is not None:
                    caps[slot[a]] = bound.max_hi() - 1
    except Exception:
        return {}
    return caps


def _h_while(it, eqn, ins):
    p = eqn.params
    cn, bn = p["cond_nconsts"], p["body_nconsts"]
    body = p["body_jaxpr"]
    b_consts = ins[cn:cn + bn]
    carry = ins[cn + bn:]
    caps = _while_counter_caps(p, ins[:cn])

    def run_body(state):
        fed = list(state)
        for i, cap in caps.items():
            s = fed[i]
            if s.max_hi() > cap:
                fed[i] = IV(np.minimum(s.lo, cap), np.minimum(s.hi, cap))
        return it.run_closed(body, b_consts + fed)

    state, _outs = _fixpoint(it, run_body, carry, "while")
    return state


def _h_cond(it, eqn, ins):
    branches = eqn.params["branches"]
    ops = list(ins[1:])
    outs = None
    for br in branches:
        b_outs = it.run_closed(br, ops)
        outs = b_outs if outs is None else [
            a.join(b) for a, b in zip(outs, b_outs)
        ]
    return outs


def _h_pallas_call(it, eqn, ins):
    body = eqn.params["jaxpr"]   # kernel body; invars are refs
    n_in, n_out = len(eqn.invars), len(eqn.outvars)
    if len(body.invars) != n_in + n_out:
        it.findings.add(
            RULE_INTERP, "pallas-refs",
            f"kernel body has {len(body.invars)} refs for {n_in} inputs + "
            f"{n_out} outputs (scratch refs unsupported); outputs assumed "
            f"full-range",
        )
        return [IV.full(_aval_shape(v.aval), *(
            _dtype_range(v.aval) or (-_SAT, _SAT))) for v in eqn.outvars]
    out_states = []
    for v in eqn.outvars:
        rng = _dtype_range(v.aval) or (-_SAT, _SAT)
        out_states.append(IV.full(_aval_shape(v.aval), rng[0], rng[1]))
    it.run_ref_body(body, list(ins) + out_states)
    return [it._ref_state[body.invars[n_in + i]] for i in range(n_out)]


_HANDLERS = {
    "add": _h_add, "sub": _h_sub, "mul": _h_mul,
    "and": _h_and, "or": _h_or_xor, "xor": _h_or_xor,
    "shift_right_logical": _h_shr, "shift_right_arithmetic": _h_shr,
    "shift_left": _h_shl,
    "eq": _h_cmp, "ne": _h_cmp, "lt": _h_cmp, "le": _h_cmp,
    "gt": _h_cmp, "ge": _h_cmp,
    "div": _h_div, "rem": _h_rem, "sign": _h_sign,
    "select_n": _h_select_n,
    "broadcast_in_dim": _h_broadcast_in_dim,
    "reshape": _h_reshape, "squeeze": _h_reshape,
    "slice": _h_slice, "concatenate": _h_concatenate, "pad": _h_pad,
    "transpose": _h_transpose, "rev": _h_rev, "iota": _h_iota,
    "convert_element_type": _h_identity,
    "device_put": _h_identity, "copy": _h_identity,
    "stop_gradient": _h_identity,
    "scatter-add": _h_scatter_add,
    "reduce_sum": _h_reduce_sum,
    "reduce_max": _h_reduce_minmax, "reduce_min": _h_reduce_minmax,
    "min": _h_min, "max": _h_max,
    "dot_general": _h_dot_general,
    "get": _h_get, "swap": _h_swap,
    "scan": _h_scan, "while": _h_while, "cond": _h_cond,
    "pallas_call": _h_pallas_call,
}


# ---------------------------------------------------------------------------
# Input-interval builders (exported for registries and fixtures)
# ---------------------------------------------------------------------------


def _fp_mod():
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    return F


def cap_for_bound(bound) -> int:
    """Max top limb of a 26x15 representation of a value < bound*P."""
    F = _fp_mod()
    return int((Fraction(bound) * F.P_INT) // (1 << (F.BITS * (F.N - 1))))


def caps_iv(shape, kind="quasi", bound=None) -> IV:
    """Per-limb input interval for a (26, T) limb plane.

    kind "strict" caps rows at 2^15 - 1, "quasi" at QMAX; a value bound
    (units of P) additionally caps the top row at cap_for_bound(bound) —
    justified by the exact-layer bound algebra (see module docstring).
    """
    F = _fp_mod()
    base = F.MASK if kind == "strict" else F.QMAX
    hi = np.full(shape, int(base), dtype=np.int64)
    if bound is not None:
        hi[F.N - 1] = min(int(base), cap_for_bound(bound))
    return IV(np.zeros(shape, dtype=np.int64), hi)


def _limbs_mod():
    from lighthouse_tpu.crypto.bls.jax_backend import limbs as L
    return L


def caps13_iv(shape, kind="quasi13") -> IV:
    """Per-limb input interval for a (31, T) 13-bit limb plane.

    kind "strict13" caps rows at 2^13 - 1, "quasi13" at limbs.SPEC13's
    QMAX13 = 2^13 + 2 — the declared representation contract of the MXU
    re-limb (``_to13`` actually proves <= 8193; the extra headroom keeps
    the contract independent of the conversion's incidental tightness).
    """
    L = _limbs_mod()
    base = (1 << 13) - 1 if kind == "strict13" else int(L.SPEC13.qmax)
    return IV(np.zeros(shape, dtype=np.int64),
              np.full(shape, base, dtype=np.int64))


def bits_iv(shape) -> IV:
    return IV.full(shape, 0, 1)


def range_iv(shape, lo, hi) -> IV:
    return IV.full(shape, lo, hi)


# ---------------------------------------------------------------------------
# Live program registry
# ---------------------------------------------------------------------------

_TILE = 128
_FP_PATH = "lighthouse_tpu/crypto/bls/jax_backend/fp.py"
_PF_PATH = "lighthouse_tpu/crypto/bls/jax_backend/pallas_fp.py"
_PM_PATH = "lighthouse_tpu/crypto/bls/jax_backend/pallas_miller.py"
_PW_PATH = "lighthouse_tpu/crypto/bls/jax_backend/pallas_wsm.py"
_PMX_PATH = "lighthouse_tpu/crypto/bls/jax_backend/pallas_mxu.py"

STRICT_CONTRACT = "strict"
QUASI_CONTRACT = "quasi"
STRICT13_CONTRACT = "strict13"   # < 2^13 (MXU plane, post carry chain)
QUASI13_CONTRACT = "quasi13"     # <= QMAX13 = 2^13 + 2 (MXU plane)


def _u32(shape):
    import jax.numpy as jnp
    return jnp.ones(shape, dtype=jnp.uint32)


def _build_pallas_mont():
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    def fn(x, y):
        return PF.mont_mul_limbs(x, y, interpret=True)

    a = _u32((26, _TILE))
    return fn, (a, a), [caps_iv((26, _TILE)), caps_iv((26, _TILE))]


def _build_pallas_mont_sqr():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    def kernel(a_ref, p_ref, pp_ref, o_ref):
        o_ref[:] = PF._mont_sqr_core(a_ref[:], p_ref[:], pp_ref[:])

    p = jnp.broadcast_to(jnp.asarray(PF._P_COLS, dtype=jnp.uint32),
                         (26, _TILE))
    pp = jnp.broadcast_to(jnp.asarray(PF._PP_COLS, dtype=jnp.uint32),
                          (26, _TILE))

    def fn(a, pc, ppc):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((26, _TILE), jnp.uint32),
            interpret=True,
        )(a, pc, ppc)

    return fn, (_u32((26, _TILE)), p, pp), [
        caps_iv((26, _TILE)), IV.const(np.asarray(p)), IV.const(np.asarray(pp)),
    ]


def _build_megachain():
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    # 4 base-16 digits -> in-kernel power table + 3 window iterations
    def fn(x):
        return PF.pow_chain_limbs(x, 0x1234, interpret=True)

    a = _u32((26, _TILE))
    return fn, (a,), [caps_iv((26, _TILE))]


def _build_fp2_megachain():
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF
    bits = (1, 0, 1, 1, 0, 1, 0, 1)

    def fn(x, y):
        return PF.fp2_pow_chain(x, y, bits, interpret=True)

    a = _u32((26, _TILE))
    return fn, (a, a), [caps_iv((26, _TILE)), caps_iv((26, _TILE))]


def _strict2():
    return caps_iv((26, _TILE), "strict", 2.0)


def _build_miller(which):
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_miller as PM
    consts = PM._const_arrays(_TILE)
    plane = _u32((26, _TILE))
    bitp = _u32((1, _TILE))
    if which == "dbl":
        call = PM._dbl_call(_TILE, _TILE, True)
        n_planes = PM._F12 + PM._TPT + 2
        args = [plane] * n_planes + list(consts)
        ivs = [_strict2() for _ in range(n_planes)] \
            + [IV.const(np.asarray(c)) for c in consts]
    else:
        call = PM._add_call(_TILE, _TILE, True)
        n_planes = PM._F12 + PM._TPT + 4 + 2
        args = [plane] * n_planes + [bitp] + list(consts)
        ivs = [_strict2() for _ in range(n_planes)] + [bits_iv((1, _TILE))] \
            + [IV.const(np.asarray(c)) for c in consts]

    def fn(*xs):
        return call(*xs)

    return fn, tuple(args), ivs


def _build_wsm(ncoords):
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_miller as PM
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_wsm as PW
    consts = PM._const_arrays(_TILE)
    plane = _u32((26, _TILE))
    flag = _u32((1, _TILE))
    call = PW._step_call(ncoords, _TILE, _TILE, True)
    n_acc = 3 * ncoords    # jacobian accumulator
    n_base = 2 * ncoords   # affine base point
    args = [plane] * n_acc + [flag] + [plane] * n_base + [flag, flag] \
        + list(consts)
    ivs = [_strict2() for _ in range(n_acc)] + [bits_iv((1, _TILE))] \
        + [_strict2() for _ in range(n_base)] \
        + [bits_iv((1, _TILE)), bits_iv((1, _TILE))] \
        + [IV.const(np.asarray(c)) for c in consts]

    def fn(*xs):
        return call(*xs)

    return fn, tuple(args), ivs


def _build_mxu_mont():
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    def fn(x, y):
        return PF.mont_mul_limbs(x, y, interpret=True, mxu=True)

    a = _u32((26, _TILE))
    return fn, (a, a), [caps_iv((26, _TILE)), caps_iv((26, _TILE))]


def _build_mxu_mont_sqr():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_mxu as PMX

    def kernel(a_ref, p_ref, pp_ref, o_ref):
        a = a_ref[:]
        o_ref[:] = PMX.mont_core_mxu(a, a, p_ref[:], pp_ref[:])

    p = jnp.broadcast_to(jnp.asarray(PF._P_COLS, dtype=jnp.uint32),
                         (26, _TILE))
    pp = jnp.broadcast_to(jnp.asarray(PF._PP_COLS, dtype=jnp.uint32),
                          (26, _TILE))

    def fn(a, pc, ppc):
        return pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct((26, _TILE), jnp.uint32),
            interpret=True,
        )(a, pc, ppc)

    return fn, (_u32((26, _TILE)), p, pp), [
        caps_iv((26, _TILE)), IV.const(np.asarray(p)), IV.const(np.asarray(pp)),
    ]


def _build_mxu_megachain():
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    def fn(x):
        return PF.pow_chain_limbs(x, 0x1234, interpret=True, mxu=True)

    a = _u32((26, _TILE))
    return fn, (a,), [caps_iv((26, _TILE))]


def _build_mxu_component(which):
    """Standalone traces of the MXU re-limb/dot building blocks at their
    *declared* representation caps — stronger than the derived bounds the
    whole-kernel runs propagate, so the contracts stay meaningful if the
    conversions ever get looser."""
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_mxu as PMX
    if which == "to13":
        a = _u32((26, _TILE))
        return (lambda x: PMX._to13(x)), (a,), [caps_iv((26, _TILE))]
    if which == "to15":
        a = _u32((31, _TILE))
        return (lambda x: PMX._to15(x)), (a,), \
            [caps13_iv((31, _TILE), "strict13")]
    a = _u32((31, _TILE))
    return (lambda x, y: PMX._dot_cols(x, y)), (a, a), \
        [caps13_iv((31, _TILE)), caps13_iv((31, _TILE))]


def _build_xla_mont():
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F

    def fn(a, b):
        was = F.pallas_enabled()
        F.set_pallas(False)
        try:
            # the bound labels only steer trace-time bookkeeping; the
            # intervals below quantify over ALL quasi limb planes
            return F.mont_mul(F.LFp(a, 40.0), F.LFp(b, 40.0)).limbs
        finally:
            F.set_pallas(was)

    a = _u32((26, 8))
    return fn, (a, a), [caps_iv((26, 8)), caps_iv((26, 8))]


def _build_xla_fp_add():
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    half = F.MAX_BOUND / 2

    def fn(a, b):
        return F.fp_add(F.LFp(a, half), F.LFp(b, half)).limbs

    a = _u32((26, 8))
    return fn, (a, a), [caps_iv((26, 8), "quasi", half),
                        caps_iv((26, 8), "quasi", half)]


def _build_xla_fp_sub(k):
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    b_bound = F.sub_bias_max_bound(k)
    a_bound = F.MAX_BOUND - k

    def fn(a, b):
        return F.fp_sub(F.LFp(a, a_bound), F.LFp(b, b_bound)).limbs

    a = _u32((26, 8))
    return fn, (a, a), [caps_iv((26, 8), "quasi", a_bound),
                        caps_iv((26, 8), "quasi", b_bound)]


def _build_ksub(k):
    """Pallas-side bias subtraction columns (pad-based _compress1)."""
    import jax.numpy as jnp
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF
    b_bound = F.sub_bias_max_bound(k)
    a_bound = F.MAX_BOUND - k
    bias = jnp.asarray(F._BIAS_NP[k].reshape(26, 1))

    def fn(a, b):
        return PF._compress1((a + jnp.broadcast_to(bias, a.shape)) - b)

    a = _u32((26, _TILE))
    return fn, (a, a), [caps_iv((26, _TILE), "quasi", a_bound),
                        caps_iv((26, _TILE), "quasi", b_bound)]


def build_live_programs() -> list:
    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    progs = [
        RangeProgram(
            "pallas_mont_mul", _PF_PATH, _build_pallas_mont,
            contracts=((0, STRICT_CONTRACT),),
            note="Montgomery product kernel, ALL quasi inputs",
        ),
        RangeProgram(
            "pallas_mont_sqr", _PF_PATH, _build_pallas_mont_sqr,
            contracts=((0, STRICT_CONTRACT),),
            note="_mont_sqr_core triangle square, ALL quasi inputs",
        ),
        RangeProgram(
            "pallas_megachain_w4", _PF_PATH, _build_megachain,
            contracts=((0, QUASI_CONTRACT),), clamp_sub=True,
            note="fused pow chain: SMEM digit tape + in-kernel table "
                 "(loop output joins the quasi power-table init, so the "
                 "provable exit contract is quasi, not strict)",
        ),
        RangeProgram(
            "pallas_fp2_megachain_w4", _PF_PATH, _build_fp2_megachain,
            contracts=((0, QUASI_CONTRACT), (1, QUASI_CONTRACT)),
            clamp_sub=True,
            note="fp2 Karatsuba pow chain; exit bounds <= (3.2P, 5.2P)",
        ),
        RangeProgram(
            "mxu_mont_mul", _PMX_PATH, _build_mxu_mont,
            contracts=((0, STRICT_CONTRACT),),
            note="13-bit dot-product Montgomery kernel, ALL quasi inputs; "
                 "the int32 MXU column budget rides the precise "
                 "dot_general transfer (exact iota-built 0/1 band)",
        ),
        RangeProgram(
            "mxu_mont_sqr", _PMX_PATH, _build_mxu_mont_sqr,
            contracts=((0, STRICT_CONTRACT),),
            note="MXU square (mont_core_mxu(a, a)), ALL quasi inputs",
        ),
        RangeProgram(
            "mxu_megachain_w4", _PMX_PATH, _build_mxu_megachain,
            contracts=((0, QUASI_CONTRACT),), clamp_sub=True,
            note="fused pow chain on the MXU cores (mxu=True route); "
                 "same exit contract as the VPU megachain",
        ),
        RangeProgram(
            "mxu_to13", _PMX_PATH, lambda: _build_mxu_component("to13"),
            contracts=((0, QUASI13_CONTRACT),),
            note="15->13 re-limb: quasi-15 in, quasi-13 (<= QMAX13) out",
        ),
        RangeProgram(
            "mxu_to15", _PMX_PATH, lambda: _build_mxu_component("to15"),
            contracts=((0, STRICT_CONTRACT),),
            note="13->15 bit regroup: strict-13 in, strict-15 out",
        ),
        RangeProgram(
            "mxu_dot_cols", _PMX_PATH, lambda: _build_mxu_component("dot"),
            contracts=((0, QUASI13_CONTRACT),),
            note="61-column banded matmul at the declared quasi-13 cap: "
                 "31 * QMAX13^2 < 2^31 int32 budget",
        ),
        RangeProgram(
            "pallas_miller_dbl", _PM_PATH, lambda: _build_miller("dbl"),
            contracts=tuple((i, QUASI_CONTRACT) for i in range(18)),
            clamp_sub=True, heavy=True,
            note="fused Miller double step (f12 sqr + line + mul_by_023)",
        ),
        RangeProgram(
            "pallas_miller_add", _PM_PATH, lambda: _build_miller("add"),
            contracts=tuple((i, QUASI_CONTRACT) for i in range(18)),
            clamp_sub=True, heavy=True,
            note="fused Miller add step (line add + select by bit)",
        ),
        RangeProgram(
            "pallas_wsm_g1", _PW_PATH, lambda: _build_wsm(1),
            contracts=tuple((i, QUASI_CONTRACT) for i in range(3)),
            clamp_sub=True, heavy=True,
            note="fused WSM double+add step, G1 (Fp coords)",
        ),
        RangeProgram(
            "pallas_wsm_g2", _PW_PATH, lambda: _build_wsm(2),
            contracts=tuple((i, QUASI_CONTRACT) for i in range(6)),
            clamp_sub=True, heavy=True,
            note="fused WSM double+add step, G2 (Fp2 coords)",
        ),
        RangeProgram(
            "xla_mont_mul", _FP_PATH, _build_xla_mont,
            contracts=((0, STRICT_CONTRACT),),
            note="XLA Horner-scan Montgomery path, ALL quasi inputs",
        ),
        RangeProgram(
            "xla_fp_add", _FP_PATH, _build_xla_fp_add,
            contracts=((0, QUASI_CONTRACT),),
            note="fp_add at the MAX_BOUND admissibility edge",
        ),
    ]
    for k in F._BIAS_KS:
        progs.append(RangeProgram(
            f"xla_fp_sub_k{k}", _FP_PATH,
            (lambda kk: lambda: _build_xla_fp_sub(kk))(k),
            contracts=((0, QUASI_CONTRACT),),
            note=f"fp_sub bias domination, k={k}, subtrahend at the "
                 f"_k_for threshold bound",
        ))
        progs.append(RangeProgram(
            f"pallas_ksub_k{k}", _PF_PATH,
            (lambda kk: lambda: _build_ksub(kk))(k),
            contracts=((0, QUASI_CONTRACT),),
            note=f"in-kernel ksub columns, k={k}",
        ))
    return progs


# ---------------------------------------------------------------------------
# Program analysis
# ---------------------------------------------------------------------------


def _default_ivs(closed, provided):
    """Align provided IVs with the jaxpr invars; fill gaps generically.

    Registries may leave trailing invars unspecified when they are
    wrapper-materialized operands (digit tapes, broadcast constant
    planes): int32 vectors are treated as window-digit tapes, 26-row
    uint32 planes as quasi limb planes, anything else full dtype range.
    """
    invars = closed.jaxpr.invars
    out = list(provided or ())
    for var in invars[len(out):]:
        rng = _dtype_range(var.aval) or (-_SAT, _SAT)
        shape = _aval_shape(var.aval)
        dt = np.dtype(getattr(var.aval, "dtype", np.int64)).name
        if dt == "int32" and len(shape) == 1:
            out.append(IV.full(shape, 0, 15))
        elif dt == "uint32" and len(shape) == 2 and shape[0] == 26:
            out.append(caps_iv(shape))
        elif dt == "uint32" and len(shape) == 2 and shape[0] == 31:
            out.append(caps13_iv(shape))
        else:
            out.append(IV.full(shape, rng[0], rng[1]))
    return out


def analyze_program(prog: RangeProgram) -> tuple:
    """(violations, per-program report entry)."""
    import jax
    fn, args, ivs = prog.build()
    closed = jax.make_jaxpr(fn)(*args)
    findings = _Findings(prog)
    interp = _Interp(prog, findings)
    outs = interp.run_jaxpr(
        closed.jaxpr,
        [IV.const(np.asarray(c)) for c in closed.consts],
        _default_ivs(closed, ivs),
    )
    F = _fp_mod()
    contracts_ok = True
    for idx, kind in prog.contracts:
        if idx >= len(outs):
            continue
        iv = outs[idx]
        if isinstance(kind, (tuple, list)):
            label, cap = kind
        elif kind == STRICT_CONTRACT:
            label, cap = "strict", F.MASK
        elif kind == STRICT13_CONTRACT:
            label, cap = "strict13", (1 << 13) - 1
        elif kind == QUASI13_CONTRACT:
            label, cap = "quasi13", int(_limbs_mod().SPEC13.qmax)
        else:
            label, cap = "quasi", F.QMAX
        if iv.max_hi() > cap or iv.min_lo() < 0:
            contracts_ok = False
            findings.add(
                RULE_CONTRACT, f"out{idx}",
                f"output {idx} violates `{label}` contract: interval "
                f"[{iv.min_lo()}, {iv.max_hi()}] vs cap {cap}",
            )
    report = {
        "eqns": interp.eqn_count,
        "max_any_log2": log2_or_zero(interp.max_any),
        "max_acc_log2": log2_or_zero(interp.max_acc),
        "max_dot_log2": log2_or_zero(interp.max_dot),
        "out_caps": [iv.max_hi() for iv in outs],
        "contracts_ok": contracts_ok,
        "note": prog.note,
    }
    return findings.violations(), report


# ---------------------------------------------------------------------------
# Exact LFp bound-algebra checks
# ---------------------------------------------------------------------------


def live_claims() -> dict:
    F = _fp_mod()
    return {
        "name": "live",
        "path": _FP_PATH,
        "mont_divisor": F.MONT_DIVISOR,
        "mont_eps": F.MONT_EPS,
        "reduce_pin": F.REDUCE_PIN,
        "max_mul_product": F.MAX_MUL_PRODUCT,
        "max_bound": F.MAX_BOUND,
    }


def lfp_check(claims: dict) -> tuple:
    """Exact-arithmetic soundness/slack audit of one claims set."""
    F = _fp_mod()
    P = F.P_INT
    R = 1 << (F.BITS * F.N)
    shift = F.BITS * (F.N - 1)
    name = claims.get("name", "live")
    path = claims.get("path", _FP_PATH)
    div = Fraction(claims["mont_divisor"])
    eps = Fraction(claims["mont_eps"])
    pin = Fraction(claims["reduce_pin"])
    prod_max = Fraction(claims["max_mul_product"])
    bound_max = Fraction(claims["max_bound"])
    pr = Fraction(P, R)   # exact P/R
    checks: list = []

    def rec(check, sound, claimed, true, slack=None, detail=""):
        checks.append({
            "check": check, "sound": bool(sound),
            "claimed": float(claimed) if claimed is not None else None,
            "true": float(true) if true is not None else None,
            "slack": round(float(slack), 4) if slack is not None else None,
            "detail": detail,
        })

    # 1. mont output bound: claimed prod/div + eps vs exact prod*P/R + 1;
    #    both sides are affine in prod, so endpoint checks suffice
    for prod in (Fraction(0), prod_max):
        claimed = prod / div + eps
        true = prod * pr + 1
        rec(f"mont-output-bound@prod={float(prod):g}", claimed >= true,
            claimed, true,
            float((claimed - true) / claimed) if claimed else None,
            f"exact R/P = {float(Fraction(R, P)):.4f} vs divisor "
            f"{float(div):g}")
    # 2. reduce pin: must cover both the exact bound of a MAX_BOUND input
    #    through one mont-by-one and the trace-time formula label
    true_reduce = bound_max * pr + 1
    formula_reduce = bound_max / div + eps
    rec("reduce-pin", pin >= true_reduce and pin >= formula_reduce,
        pin, true_reduce, float((pin - true_reduce) / pin),
        "fp_reduce pins the scan-stable label; exact worst case "
        "MAX_BOUND*P/R + 1")
    # 3. fp_pow fixpoint closure: fix = claimed(prod_max); requires
    #    fix^2 admissible and claimed(fix^2) <= fix (no slack metric —
    #    this is a closure property, not a tightness one)
    fix = prod_max / div + eps
    closure = (fix * fix) / div + eps
    rec("pow-fix-closure", fix * fix <= prod_max and closure <= fix,
        fix, closure, None,
        "fix must absorb one squaring step (fix^2 admissible, output "
        "re-enters the class)")
    # 4. top-column carry headroom: compress1 silently drops the top
    #    limb's carry; the worst top column of any admissible value is
    #    cap(MAX_BOUND) and must stay below 2^15
    cap_max = int((bound_max * P) // (1 << shift))
    rec("compress1-top-carry", cap_max <= F.MASK,
        Fraction(cap_max), Fraction(F.MASK), None,
        f"cap(MAX_BOUND) = {cap_max} must stay below 2^15 so the "
        f"dropped top carry is identically zero")
    # 5. per-k bias tables: exact value, low-limb quasi domination,
    #    top-limb domination at the _k_for threshold, and top-column
    #    headroom of the fp_sub result at the MAX_BOUND edge
    for k in F._BIAS_KS:
        limbs = [int(v) for v in F._biased_kp(k)]
        value_ok = sum(v << (F.BITS * i)
                       for i, v in enumerate(limbs)) == k * P
        low_ok = all(v >= F.QMAX for v in limbs[:-1])
        top = limbs[-1]
        thr = F.sub_bias_max_bound(k)
        cap_thr = int((Fraction(thr) * P) // (1 << shift))
        dom_ok = cap_thr <= top
        a_cap = int(((bound_max - k) * P) // (1 << shift))
        col_ok = (a_cap + top) <= F.MASK
        rec(f"bias-k{k}", value_ok and low_ok and dom_ok and col_ok,
            Fraction(top), Fraction(cap_thr), None,
            f"value==k*P:{value_ok} low>=QMAX:{low_ok} "
            f"top {top} >= cap(thr {thr:.6g}) = {cap_thr}:{dom_ok} "
            f"top-col {a_cap}+{top} < 2^15:{col_ok}")
    # 6. wide-product admissibility: prod_max * P^2 must fit the 52-limb
    #    double-width accumulator
    rec("mont-prod-admissible", prod_max * P * P < Fraction(R) * R,
        prod_max, Fraction(R) * R / (P * P), None,
        "a*b < prod_max*P^2 must fit the 52-limb wide accumulator")

    violations = []
    for c in checks:
        if not c["sound"]:
            violations.append(Violation(
                rule=RULE_LFP, path=path, line=0,
                symbol=f"{name}:{c['check']}",
                message=(
                    f"unsound bound constant: claimed {c['claimed']} vs "
                    f"exact {c['true']} — {c['detail']}"
                ),
            ))
        elif c["slack"] is not None and c["slack"] > SLACK_MAX:
            violations.append(Violation(
                rule=RULE_SLACK, path=path, line=0,
                symbol=f"{name}:{c['check']}",
                message=(
                    f"needlessly loose bound constant: claimed "
                    f"{c['claimed']} vs exact {c['true']} "
                    f"(slack {c['slack']:.0%} > {SLACK_MAX:.0%})"
                ),
            ))
    return violations, checks


# ---------------------------------------------------------------------------
# MXU-readiness report
# ---------------------------------------------------------------------------

F32_MANTISSA_BUDGET = 1 << 24
I32_BUDGET = 1 << 31
FIELD_BITS = 381


def mxu_limb_split_table() -> list:
    rows = []
    for w in range(6, 16):
        n = -(-FIELD_BITS // w)
        col = n * ((1 << w) - 1) ** 2
        rows.append({
            "w": w, "limbs": n, "col_log2": log2_or_zero(col),
            "f32_ok": col < F32_MANTISSA_BUDGET,
            "i32_ok": col < I32_BUDGET,
        })
    return rows


def mxu_report(program_reports: dict) -> dict:
    F = _fp_mod()
    table = mxu_limb_split_table()
    w_f32 = max(r["w"] for r in table if r["f32_ok"])
    w_i32 = max(r["w"] for r in table if r["i32_ok"])
    direct_col = F.N * F.QMAX ** 2   # un-split dot column, current limbs
    per_kernel = {}
    for name in sorted(program_reports):
        rep = program_reports[name]
        acc = rep["max_acc_log2"]
        per_kernel[name] = {
            "max_acc_log2": acc,
            "max_any_log2": rep["max_any_log2"],
            "max_dot_log2": rep.get("max_dot_log2", 0.0),
            "f32_ok": acc < 24,
            "i32_ok": acc < 31,
        }
    L = _limbs_mod()
    q13, nl13 = int(L.SPEC13.qmax), int(L.SPEC13.n)
    col13 = nl13 * q13 * q13
    return {
        "budgets": {"f32_mantissa_log2": 24, "i32_log2": 31},
        "current_rep": {
            "w": F.BITS, "limbs": F.N,
            "direct_dot_col_log2": log2_or_zero(direct_col),
            "f32_ok": direct_col < F32_MANTISSA_BUDGET,
            "i32_ok": direct_col < I32_BUDGET,
        },
        "limb_split_table": table,
        "max_w_f32": w_f32,
        "max_w_i32": w_i32,
        # the split pallas_mxu ships: w=13 with one spill row (quasi-15
        # inputs overhang 2^390 by up to 2^-15), proved by the mxu_*
        # programs above rather than read off the generic table
        "selected_split": {
            "w": 13, "limbs": nl13, "qmax": q13,
            "col_log2": log2_or_zero(col13),
            "i32_ok": col13 < I32_BUDGET,
            "kernels": ["mxu_mont_mul", "mxu_mont_sqr",
                        "mxu_megachain_w4"],
        },
        "per_kernel": per_kernel,
        "conclusion": (
            f"direct {F.BITS}-bit columns cannot MXU-accumulate "
            f"(2^{log2_or_zero(direct_col)} > 2^31); the shipped MXU "
            f"path (pallas_mxu, LIGHTHOUSE_TPU_MXU=1) re-limbs to w=13 "
            f"({nl13} limbs incl. the spill row, column ceiling "
            f"2^{log2_or_zero(col13)} < 2^31, int32-proved by "
            f"mxu_mont_mul/mxu_dot_cols); f32 dot-products would need "
            f"w<={w_f32} ({-(-FIELD_BITS // w_f32)} limbs)"
        ),
    }


# ---------------------------------------------------------------------------
# Audit-family entry points
# ---------------------------------------------------------------------------


_CACHE_FILE = ".range_proof_cache.json"
_CACHE_VERSION = 1
# per-generate() hit/miss side channel (tests and tooling read it) —
# kept OUT of the report dict so cold and warm reports stay
# byte-identical and the drift check cannot tell them apart
_CACHE_STATS = {"hits": 0, "misses": 0}


def _fingerprint_deps(root: str) -> list:
    """Repo-relative source files whose bytes feed ``_proof_fingerprint``.

    Covers the kernel package AND the sharded-program sources
    (``parallel/partition.py``/``mesh.py``): the spmd family keys its
    cached theorem verdicts off the same fingerprint, so an edit to the
    staged SPMD programs must invalidate them."""
    deps = [
        "lighthouse_tpu/analysis/range_lint.py",
        "lighthouse_tpu/analysis/report.py",
        "lighthouse_tpu/crypto/bls/params.py",
        "lighthouse_tpu/parallel/partition.py",
        "lighthouse_tpu/parallel/mesh.py",
    ]
    kdir = "lighthouse_tpu/crypto/bls/jax_backend"
    full_kdir = os.path.join(root, kdir)
    if os.path.isdir(full_kdir):
        deps.extend(
            f"{kdir}/{fn}" for fn in sorted(os.listdir(full_kdir))
            if fn.endswith(".py")
        )
    return deps


def _proof_fingerprint(root: str) -> str:
    """Content hash of everything a live program verdict depends on.

    Coarse by design: one hash over the whole kernel package, this
    module, and the jax/numpy versions.  Any kernel edit invalidates
    every cached verdict (sound, and the cold run is the status quo);
    an untouched tree replays all of them (the >=5x warm win the audit
    wall-time needs — the traces are minutes, the hash is milliseconds).
    """
    import hashlib

    import jax
    h = hashlib.sha256()
    h.update(
        f"v{_CACHE_VERSION}|jax {jax.__version__}|np {np.__version__}"
        .encode()
    )
    for rel in _fingerprint_deps(root):
        h.update(rel.encode())
        try:
            with open(os.path.join(root, rel), "rb") as f:
                h.update(f.read())
        except OSError:
            h.update(b"?")
    return h.hexdigest()


def _load_defs(root: str, rel_path: str):
    full = os.path.join(root, rel_path)
    spec = importlib.util.spec_from_file_location("range_defs_corpus", full)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _resolve_registry(root: str, cfg):
    """(programs, claim_sets) for the live tree or a fixture corpus."""
    defs = getattr(cfg, "range_defs", None)
    if defs:
        mod = _load_defs(root, defs)
        programs = list(mod.build_programs())
        claim_sets = list(getattr(mod, "LFP_CLAIMS", ()))
        return programs, claim_sets
    return build_live_programs(), [live_claims()]


def generate(root: str, cfg, only: tuple = ()) -> tuple:
    """Run the range family; returns (violations, report dict).

    ``only`` restricts to named programs (test tiers use it to skip the
    minutes-scale Miller traces).

    Per-program verdicts (violations + report entry) are cached in
    ``.range_proof_cache.json`` keyed by ``_proof_fingerprint``: warm
    re-audits of an untouched tree replay them without re-tracing, and
    a replayed report is byte-identical to a fresh one (entries are
    json-round-tripped before first use).  ``cfg.range_cache = False``
    (CLI ``--no-cache``) bypasses read AND write; fixture corpora
    (``range_defs``) are never cached — their programs are trivial and
    their verdicts must not share a file with the live tree's.
    """
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0
    try:
        import jax  # noqa: F401
    except Exception as exc:  # pragma: no cover - jax is baked in
        return [Violation(
            rule=RULE_INTERP, path="lighthouse_tpu/analysis/range_lint.py",
            line=0, symbol="import-jax",
            message=f"range family needs jax to trace kernels: {exc}",
        )], {}
    violations: list = []
    programs, claim_sets = _resolve_registry(root, cfg)
    if only:
        programs = [p for p in programs if p.name in only]
    use_cache = bool(getattr(cfg, "range_cache", True)) \
        and not getattr(cfg, "range_defs", None)
    cache_path = os.path.join(root, _CACHE_FILE)
    fingerprint = _proof_fingerprint(root) if use_cache else ""
    cached: dict = {}
    disk: dict = {}
    if use_cache:
        try:
            with open(cache_path, encoding="utf-8") as f:
                disk = json.load(f)
            if disk.get("fingerprint") == fingerprint:
                cached = dict(disk.get("programs") or {})
        except (OSError, ValueError):
            disk, cached = {}, {}
    dirty = False
    prog_reports: dict = {}
    for prog in programs:
        entry = cached.get(prog.name)
        if entry is not None:
            _CACHE_STATS["hits"] += 1
            vios = [Violation(**v) for v in entry["violations"]]
            rep = entry["report"]
        else:
            _CACHE_STATS["misses"] += 1
            try:
                vios, rep = analyze_program(prog)
            except Exception as exc:
                violations.append(Violation(
                    rule=RULE_INTERP, path=prog.path, line=0,
                    symbol=prog.name,
                    message=f"program failed to trace/analyze: {exc!r}",
                ))
                continue
            rep = json.loads(json.dumps(rep))
            if use_cache:
                cached[prog.name] = {
                    "violations": [v.to_dict() for v in vios],
                    "report": rep,
                }
                dirty = True
        violations.extend(vios)
        prog_reports[prog.name] = rep
    if use_cache and dirty:
        # the cache file is shared with the spmd family: carry its
        # sections (spmd_fingerprint / spmd_programs) through unchanged
        # — each family validates only its own fingerprint on read
        doc = {k: v for k, v in disk.items() if k.startswith("spmd_")}
        doc["fingerprint"] = fingerprint
        doc["programs"] = cached
        try:
            with open(cache_path, "w", encoding="utf-8") as f:
                json.dump(doc, f)
        except OSError:
            pass   # unwritable cache just means the next run is cold too
    checks_out: list = []
    for claims in claim_sets:
        vios, checks = lfp_check(claims)
        violations.extend(vios)
        checks_out.extend(checks)
    report = {
        "version": 1,
        "programs": {k: prog_reports[k] for k in sorted(prog_reports)},
        "lfp_checks": checks_out,
        "mxu": mxu_report(prog_reports),
    }
    return violations, report


def run(root: str, cfg, only: tuple = ()) -> list:
    """Audit entry: full registry + checked-in report drift check.

    A restricted run (``only`` non-empty) cannot validate the full
    checked-in report, so the drift check is skipped for it."""
    violations, report = generate(root, cfg, only=only)
    report_rel = None if only else getattr(cfg, "range_report", None)
    if report_rel:
        report_path = os.path.join(root, report_rel)
        try:
            with open(report_path, encoding="utf-8") as f:
                want = json.load(f)
        except (OSError, ValueError) as exc:
            violations.append(Violation(
                rule=RULE_REPORT, path=report_rel, line=0,
                symbol="missing",
                message=(
                    f"checked-in range report unreadable ({exc}); "
                    f"regenerate with JAX_PLATFORMS=cpu python tools/static_audit.py "
                    f"--write-range-report"
                ),
            ))
            return violations
        got = json.loads(json.dumps(report))
        if got != want:
            diffs = _report_diff(want, got)
            violations.append(Violation(
                rule=RULE_REPORT, path=report_rel, line=0,
                symbol="drift",
                message=(
                    "checked-in range report drifted from the kernels: "
                    + "; ".join(diffs[:6])
                    + " — regenerate with JAX_PLATFORMS=cpu python "
                      "tools/static_audit.py --write-range-report"
                ),
            ))
    return violations


def _report_diff(want, got, prefix="") -> list:
    out = []
    if isinstance(want, dict) and isinstance(got, dict):
        for k in sorted(set(want) | set(got)):
            if k not in want:
                out.append(f"+{prefix}{k}")
            elif k not in got:
                out.append(f"-{prefix}{k}")
            elif want[k] != got[k]:
                out.extend(_report_diff(want[k], got[k], f"{prefix}{k}."))
        return out
    if isinstance(want, list) and isinstance(got, list):
        if len(want) != len(got):
            return [f"{prefix}len {len(want)}->{len(got)}"]
        for i, (w, g) in enumerate(zip(want, got)):
            if w != g:
                out.extend(_report_diff(w, g, f"{prefix}{i}."))
        return out
    return [f"{prefix}: {want!r} -> {got!r}"]


def write_report(root: str, cfg, path: str | None = None) -> str:
    """Regenerate and write the range report; returns the path."""
    _violations, report = generate(root, cfg)
    rel = path or getattr(cfg, "range_report", None) or DEFAULT_REPORT
    full = os.path.join(root, rel)
    with open(full, "w", encoding="utf-8") as f:
        json.dump(json.loads(json.dumps(report)), f, indent=1, sort_keys=True)
        f.write("\n")
    return full
