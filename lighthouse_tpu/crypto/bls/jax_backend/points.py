"""Branchless Jacobian point arithmetic over Fp (G1) and Fp2 (G2) in JAX.

Device analog of blst's point ops as used by verify_signature_sets
(reference: crypto/bls/src/impls/blst.rs:71-117): doubling, addition,
batched 64-bit scalar multiplication (the random batch weights, RAND_BITS=64
at blst.rs:14), the psi and phi endomorphisms and Scott's fast G2 and G1
subgroup tests (constants from endo.py, derived + self-checked there).

A point is a pytree ``(X, Y, Z, inf)``: Jacobian coordinates (x = X/Z^2,
y = Y/Z^3) in the lazy LFp representation plus an explicit boolean infinity
flag.  The flag — rather than a Z ≡ 0 (mod P) test, which would cost a
canonicalization in the lazy representation — makes infinity handling free
inside scan bodies.

Two additions:

* ``jac_add`` — complete: detects doubling (P+P) and cancellation (P-P) via
  canonical equality and handles infinities; use anywhere inputs may
  coincide (batch accumulation of adversarial points, tree reductions).
* ``jac_add_fast`` — no coincidence detection; only infinity flags.  Valid
  when operands cannot be equal or opposite: inside double-and-add scalar
  multiplication with a prime-order base and scalar < order, the running
  accumulator is [k]Q with 2 <= k < order, never ±Q.  This is the hot-loop
  add.

Every point-producing op ends by reducing its coordinates (stacked) so scan
carries have stable static bounds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import endo as _endo
from .. import params
from . import fp as F
from . import tower as T

# ---------------------------------------------------------------------------
# Field-op namespaces so G1/G2 share one implementation
# ---------------------------------------------------------------------------


class _FpOps:
    add = staticmethod(F.fp_add)
    sub = staticmethod(F.fp_sub)
    neg = staticmethod(F.fp_neg)
    mul = staticmethod(F.mont_mul)
    sqr = staticmethod(F.mont_sqr)
    mul_many = staticmethod(T.mm_many)
    select = staticmethod(F.fp_select)
    eq = staticmethod(F.fp_eq)
    is_zero = staticmethod(F.fp_is_zero)
    zero_like = staticmethod(F.zero_like)
    one_like = staticmethod(F.one_like)
    reduce_many = staticmethod(T.reduce_many)
    ncoords = 1  # lanes per field element when stacking

    @staticmethod
    def dbl(a):
        return F.fp_add(a, a)

    @staticmethod
    def lanes(a):
        return [a]

    @staticmethod
    def unlanes(lanes):
        return lanes[0]

    @staticmethod
    def batch_shape(a):
        return F.batch_shape(a)


class _Fp2Ops:
    add = staticmethod(T.fp2_add)
    sub = staticmethod(T.fp2_sub)
    neg = staticmethod(T.fp2_neg)
    mul = staticmethod(T.fp2_mul)
    sqr = staticmethod(T.fp2_sqr)
    mul_many = staticmethod(T.fp2_mul_many)
    select = staticmethod(T.fp2_select)
    eq = staticmethod(T.fp2_eq)
    is_zero = staticmethod(T.fp2_is_zero)
    zero_like = staticmethod(T.fp2_zero_like)
    one_like = staticmethod(T.fp2_one_like)
    dbl = staticmethod(T.fp2_dbl)
    ncoords = 2

    @staticmethod
    def reduce_many(xs):
        return T.reduce_many(xs)

    @staticmethod
    def lanes(a):
        return [a[0], a[1]]

    @staticmethod
    def unlanes(lanes):
        return (lanes[0], lanes[1])

    @staticmethod
    def batch_shape(a):
        return F.batch_shape(a[0])


FP_OPS = _FpOps
FP2_OPS = _Fp2Ops


def _reduce_coords(ops, coords):
    """Stacked reduction of a list of field elements to stable bound 2."""
    lanes = []
    for c in coords:
        lanes += ops.lanes(c)
    red = ops.reduce_many(lanes)
    out = []
    n = ops.ncoords
    for i in range(len(coords)):
        out.append(ops.unlanes(red[i * n : (i + 1) * n]))
    return out


def pt_select(ops, mask, p, q):
    out = tuple(ops.select(mask, a, b) for a, b in zip(p[:3], q[:3]))
    return out + (jnp.where(mask, p[3], q[3]),)


def pt_infinity_like(ops, p):
    one = ops.one_like(p[0])
    bshape = ops.batch_shape(p[0])
    return (one, one, ops.zero_like(p[0]), jnp.ones(bshape, dtype=bool))


def pt_is_infinity(ops, p):
    return p[3]


def from_affine(ops, xy):
    x, y = xy
    bshape = ops.batch_shape(x)
    return (x, y, ops.one_like(x), jnp.zeros(bshape, dtype=bool))


def pt_neg(ops, p):
    return (p[0], ops.neg(p[1]), p[2], p[3])


def jac_double(ops, p):
    """2P, a = 0 curve.  Valid for non-infinity inputs of odd order (no
    y = 0 points); infinity propagates via the flag (coords are garbage
    under the flag, as everywhere)."""
    X, Y, Z = p[0], p[1], p[2]
    A, B, YZ = ops.mul_many([X, Y, Y], [X, Y, Z])
    E = ops.add(ops.dbl(A), A)
    XB = ops.add(X, B)
    C, t, Fv = ops.mul_many([B, XB, E], [B, XB, E])
    D = ops.dbl(ops.sub(ops.sub(t, A), C))
    X3 = ops.sub(Fv, ops.dbl(D))
    (m,) = ops.mul_many([E], [ops.sub(D, X3)])
    C8 = ops.dbl(ops.dbl(ops.dbl(C)))
    Y3 = ops.sub(m, C8)
    Z3 = ops.dbl(YZ)
    X3, Y3, Z3 = _reduce_coords(ops, [X3, Y3, Z3])
    return (X3, Y3, Z3, p[3])


def _raw_add(ops, p1, p2):
    """Core Jacobian addition; undefined when P1 = ±P2 or an input is
    infinity.  Returns reduced coordinates."""
    X1, Y1, Z1 = p1[0], p1[1], p1[2]
    X2, Y2, Z2 = p2[0], p2[1], p2[2]
    Z1Z1, Z2Z2 = ops.mul_many([Z1, Z2], [Z1, Z2])
    U1, U2, t1, t2 = ops.mul_many([X1, X2, Y1, Y2], [Z2Z2, Z1Z1, Z2, Z1])
    S1, S2 = ops.mul_many([t1, t2], [Z2Z2, Z1Z1])
    H = ops.sub(U2, U1)
    rr = ops.dbl(ops.sub(S2, S1))
    H2 = ops.dbl(H)
    Zs = ops.add(Z1, Z2)
    I, rr2, W = ops.mul_many([H2, rr, Zs], [H2, rr, Zs])
    J, V = ops.mul_many([H, U1], [I, I])
    X3 = ops.sub(ops.sub(rr2, J), ops.dbl(V))
    m1, m2, Z3 = ops.mul_many(
        [rr, S1, ops.sub(ops.sub(W, Z1Z1), Z2Z2)],
        [ops.sub(V, X3), J, H],
    )
    Y3 = ops.sub(m1, ops.dbl(m2))
    X3, Y3, Z3 = _reduce_coords(ops, [X3, Y3, Z3])
    return (X3, Y3, Z3), (U1, U2, S1, S2)


def jac_add_fast(ops, p1, p2):
    """P1 + P2 without coincidence detection (see module docstring for the
    validity condition).  Infinity handled via flags only."""
    (X3, Y3, Z3), _ = _raw_add(ops, p1, p2)
    inf1, inf2 = p1[3], p2[3]
    out = (X3, Y3, Z3, inf1 & inf2)
    out = pt_select(ops, inf2, (p1[0], p1[1], p1[2], inf1 & inf2), out)
    out = pt_select(ops, inf1, (p2[0], p2[1], p2[2], inf1 & inf2), out)
    return out


def jac_add(ops, p1, p2):
    """Complete P1 + P2: doubling, cancellation, and infinity via selects."""
    (X3, Y3, Z3), (U1, U2, S1, S2) = _raw_add(ops, p1, p2)
    inf1, inf2 = p1[3], p2[3]
    both_finite = jnp.logical_not(inf1 | inf2)
    ex = ops.eq(U1, U2)
    ey = ops.eq(S1, S2)
    is_dbl = ex & ey & both_finite
    cancels = ex & jnp.logical_not(ey) & both_finite
    inf_out = (inf1 & inf2) | cancels
    out = (X3, Y3, Z3, inf_out)
    dblp = jac_double(ops, p1)
    out = pt_select(ops, is_dbl, (dblp[0], dblp[1], dblp[2], inf_out), out)
    out = pt_select(ops, inf2, (p1[0], p1[1], p1[2], inf_out), out)
    out = pt_select(ops, inf1, (p2[0], p2[1], p2[2], inf_out), out)
    return out


def jac_eq(ops, p1, p2):
    """Equality including infinity, via cross-multiplication."""
    X1, Y1, Z1 = p1[0], p1[1], p1[2]
    X2, Y2, Z2 = p2[0], p2[1], p2[2]
    Z1Z1, Z2Z2, t1, t2 = ops.mul_many([Z1, Z2, Y1, Y2], [Z1, Z2, Z2, Z1])
    a, b, c, d = ops.mul_many([X1, X2, t1, t2], [Z2Z2, Z1Z1, Z2Z2, Z1Z1])
    ex = ops.eq(a, b)
    ey = ops.eq(c, d)
    inf1, inf2 = p1[3], p2[3]
    return (inf1 & inf2) | (jnp.logical_not(inf1 | inf2) & ex & ey)


def pt_relabel(ops, p, bound: float):
    """Pin coordinate bounds (upward) for scan-carry stability."""

    def up(c):
        if isinstance(c, F.LFp):
            return F.relabel(c, bound)
        return tuple(up(x) for x in c)

    return tuple(up(c) for c in p[:3]) + (p[3],)


def scalar_mul_bits(ops, p, bits):
    """[k]P with per-element scalars given as bits (nbits, *batch), MSB
    first.  Double-and-always-add with select; uses the fast add (valid:
    p has prime order r and k < 2^64 << r, so the accumulator never
    coincides with ±p)."""
    p = pt_relabel(ops, p, 2.0)

    def step(acc, bit):
        acc = jac_double(ops, acc)
        added = jac_add_fast(ops, acc, p)
        return pt_select(ops, bit == 1, added, acc), None

    acc, _ = lax.scan(step, pt_relabel(ops, pt_infinity_like(ops, p), 2.0), bits)
    return acc


def scalar_mul_const(ops, p, k: int):
    """[k]P for a static scalar; negative k negates the point."""
    if k < 0:
        return scalar_mul_const(ops, pt_neg(ops, p), -k)
    if k == 0:
        return pt_infinity_like(ops, p)
    bshape = ops.batch_shape(p[0])
    nbits = [int(c) for c in bin(k)[2:]]
    bits = jnp.broadcast_to(
        jnp.array(nbits, dtype=jnp.uint32).reshape((len(nbits),) + (1,) * len(bshape)),
        (len(nbits),) + tuple(bshape),
    )
    return scalar_mul_bits(ops, p, bits)


def to_affine(ops, p, inv_fn):
    """Jacobian -> affine (x, y); where the infinity flag is set the output
    coords are garbage — callers must consult pt_is_infinity."""
    X, Y, Z = p[0], p[1], p[2]
    zinv = inv_fn(Z)
    zinv2 = ops.sqr(zinv)
    (x,) = ops.mul_many([X], [zinv2])
    (y,) = ops.mul_many([ops.mul(Y, zinv2)], [zinv])
    return (x, y)


# ---------------------------------------------------------------------------
# G2 endomorphism + fast subgroup check (constants from endo.py)
# ---------------------------------------------------------------------------


def _psi_consts(bshape):
    cx = T.fp2_const(_endo.PSI_CX, bshape)
    cy = T.fp2_const(_endo.PSI_CY, bshape)
    return cx, cy


def psi_affine(xy):
    """psi on an affine G2 point pytree ((xc0,xc1),(yc0,yc1))."""
    x, y = xy
    bshape = F.batch_shape(x[0])
    cx, cy = _psi_consts(bshape)
    px, py = T.fp2_mul_many([T.fp2_conj(x), T.fp2_conj(y)], [cx, cy])
    return (px, py)


_X_ABS_BITS = [int(c) for c in bin(abs(params.X))[2:]]


def g2_subgroup_check(xy):
    """Scott's test:  Q in G2  iff  psi(Q) == [x]Q  (x < 0: compare with
    the negated |x| multiple).  Batched over trailing dims; returns bools.
    Inputs must be valid curve points (deserialization enforces on-curve)."""
    x, _y = xy
    bshape = F.batch_shape(x[0])
    Q = from_affine(FP2_OPS, xy)
    bits = jnp.broadcast_to(
        jnp.array(_X_ABS_BITS, dtype=jnp.uint32).reshape(
            (len(_X_ABS_BITS),) + (1,) * len(bshape)
        ),
        (len(_X_ABS_BITS),) + tuple(bshape),
    )
    xQ = scalar_mul_bits(FP2_OPS, Q, bits)  # [|x|]Q
    psiQ = from_affine(FP2_OPS, psi_affine(xy))
    return jac_eq(FP2_OPS, psiQ, pt_neg(FP2_OPS, xQ))


# ---------------------------------------------------------------------------
# G1 endomorphism + fast subgroup check (the public-key validation)
# ---------------------------------------------------------------------------

_X_ABS = abs(params.X)
_BETA_LIMBS = F.int_to_limbs(_endo.BETA * F.R_INT % F.P_INT)


def phi_affine(xy):
    """phi(x, y) = (beta x, y) on an affine G1 point pytree (x, y)."""
    x, y = xy
    beta = F.LFp(F.bcast(jnp.asarray(_BETA_LIMBS), F.batch_shape(x)), 1.0)
    return (F.mont_mul(x, beta), y)


def g1_subgroup_check(xy):
    """Scott's test:  P in G1  iff  phi(P) == [x^2 - 1]P, the multiple
    taken as [|x|+1]([|x|-1]P) (two 64-bit ladders; |x|^2 - 1 = x^2 - 1).
    Batched over trailing dims; returns bools.  Inputs must be valid
    curve points (decompression enforces on-curve).

    The ladders use the fast add, exact for a member.  On a non-member
    of small order an add can meet +-acc; that leaves Z = 0 (H = 0 in
    the add), and Z stays 0 through every later double and add, so the
    test also demands a finite result with Z != 0: without it, points of
    order 3 and 11 pass."""
    Pt = from_affine(FP_OPS, xy)
    t = scalar_mul_const(FP_OPS, scalar_mul_const(FP_OPS, Pt, _X_ABS - 1),
                         _X_ABS + 1)
    finite = jnp.logical_not(t[3] | F.fp_is_zero(t[2]))
    return finite & jac_eq(FP_OPS, from_affine(FP_OPS, phi_affine(xy)), t)


# ---------------------------------------------------------------------------
# Host codecs: oracle affine points <-> device arrays
# ---------------------------------------------------------------------------


def g1_encode(points) -> tuple:
    """Host: list of oracle affine G1 points (no infinities) -> affine
    device pytree (x, y)."""
    xs = [p[0].v for p in points]
    ys = [p[1].v for p in points]
    return (F.lfp_encode(xs), F.lfp_encode(ys))


def g2_encode(points) -> tuple:
    x = T.fp2_encode([p[0] for p in points])
    y = T.fp2_encode([p[1] for p in points])
    return (x, y)


def g1_decode_jac(p) -> list:
    """Device Jacobian G1 (X, Y, Z, inf) -> oracle affine points
    (None for infinity)."""
    from .. import curve as C
    from .. import fields as O

    X = F.decode_mont(p[0])
    Y = F.decode_mont(p[1])
    Z = F.decode_mont(p[2])
    inf = np.asarray(p[3]).reshape(-1)
    out = []
    for x, y, z, isinf in zip(X, Y, Z, inf):
        if isinf or z == 0:
            out.append(None)
        else:
            out.append(C.from_jacobian((O.Fp(x), O.Fp(y), O.Fp(z)), O.Fp))
    return out


def g2_decode_jac(p) -> list:
    from .. import curve as C
    from .. import fields as O

    Xs = T.fp2_decode(p[0])
    Ys = T.fp2_decode(p[1])
    Zs = T.fp2_decode(p[2])
    inf = np.asarray(p[3]).reshape(-1)
    out = []
    for x, y, z, isinf in zip(Xs, Ys, Zs, inf):
        if isinf or z.is_zero():
            out.append(None)
        else:
            out.append(C.from_jacobian((x, y, z), O.Fp2))
    return out
