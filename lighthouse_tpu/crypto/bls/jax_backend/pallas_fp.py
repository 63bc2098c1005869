"""Pallas TPU kernel for the Montgomery limb multiply — the hot op.

PERF.md plan item 1: the XLA `mont_mul` lowers to ~3 Horner `lax.scan`s
whose every step materializes a shifted copy of the (52, B) accumulator
(concatenate + two scatter-adds) — the measured kernel is dispatch/copy
bound, not multiply bound.  This kernel runs the whole Montgomery
product — wide schoolbook, P' low product, P wide product, 52-limb carry
normalization — as ONE Pallas program per lane tile with every
intermediate in VMEM, loops unrolled at trace time (static 26/52-step
Python loops), and the shift structure expressed as static-slice
accumulations the Mosaic compiler keeps on-chip.

Same representation contract as fp.mont_mul (fp.py): 26 x 15-bit
quasi-normalized uint32 limbs, Montgomery radix 2^390, inputs with
bound-product <= 2000 in units of P, STRICT limbs out.  The wrapper is a
drop-in for the three-scan body; bound bookkeeping stays in fp.LFp.

Enable with LIGHTHOUSE_TPU_PALLAS=1 (fp.mont_mul routes here on TPU
backends; the lax.scan path remains the CPU/test reference).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import fp as F

LANE_TILE = 512  # lanes per grid step (multiple of 128)
CHAIN_WINDOW = 4  # chain window width: 2^w precomputed powers, w sqr + 1 mul


def pick_tile(n: int) -> int:
    """The ONE tiling rule for lane-padded pallas calls: full LANE_TILE
    when the batch fills it, else the smallest 128-multiple cover —
    every fused kernel family must pad identically or their operands
    misalign."""
    return LANE_TILE if n >= LANE_TILE else max(128, -(-n // 128) * 128)

_P_COLS = np.asarray(F.int_to_limbs(F.P_INT)).reshape(26, 1)
_PP_COLS = np.asarray(F.int_to_limbs(F.PPRIME_INT)).reshape(26, 1)

MASK = np.uint32((1 << 15) - 1)


# The helpers below run at TRACE time once per unrolled limb step — a
# fused Miller step kernel calls them ~10^5 times — so they stage plain
# lax primitives: the jnp wrappers (dtype promotion, index rewriting)
# cost ~5x more host time per op for the same jaxpr.
_ZERO = np.uint32(0)
_SHIFT = np.uint32(15)


def _rows(x, a: int, b: int):
    """Static row slice x[a:b] of a (rows, T) value."""
    return lax.slice_in_dim(x, a, b, axis=0)


def _compress1(cols):
    """One carry pass (fp.compress1, in-kernel): lo < 2^15 plus the
    column below's high bits.  On the wide-product accumulator
    (<= 1,677,799 < 2^20.7) one pass lands <= 32818 and a second
    <= 32768 <= QMAX (intervals range_lint-verified).  Shift expressed
    as pad+slice (Mosaic has no scatter-add)."""
    lo = lax.bitwise_and(cols, MASK)
    hi = lax.shift_right_logical(cols, _SHIFT)
    shifted = lax.pad(_rows(hi, 0, hi.shape[0] - 1), _ZERO,
                      ((1, 0, 0), (0, 0, 0)))
    return lax.add(lo, shifted)


def _acc_add(acc, rows, offset: int):
    """acc += rows placed at row ``offset`` — expressed as a zero-pad to
    the accumulator height plus a full-width add (Mosaic lowers
    pad/concatenate + add; it has neither scatter-add nor value-level
    dynamic_slice)."""
    tail = acc.shape[0] - offset - rows.shape[0]
    return lax.add(acc, lax.pad(rows, _ZERO, ((offset, tail, 0), (0, 0, 0))))


def _limb_times(a, i: int, b):
    """a_i * b: limb row i of ``a`` broadcast over the rows of ``b``."""
    return lax.mul(lax.broadcast_in_dim(_rows(a, i, i + 1), b.shape, (0, 1)),
                   b)


def _wide_product(a, b):
    """Schoolbook sum_i a_i * b * 2^(15 i); a, b (26, T) quasi limbs.
    Returns (52, T) columns, two carry passes applied (<= 32768 <= QMAX,
    range_lint-verified).  All accumulator updates are full-width
    in-bounds slice-adds — the clipped-slice variant lowers to a
    scatter Pallas cannot stage."""
    T = a.shape[1]
    acc = lax.full((52, T), 0, jnp.uint32)
    for i in range(26):
        p = _limb_times(a, i, b)  # (26, T) 32-bit products
        acc = _acc_add(acc, lax.bitwise_and(p, MASK), i)
        acc = _acc_add(acc, lax.shift_right_logical(p, _SHIFT), i + 1)
        # column sums peak at 1,677,799 < 2^20.7 (range_lint): no overflow
    return _compress1(_compress1(acc))


def _wide_square(a):
    """Schoolbook square via the j >= i triangle: cross products doubled,
    diagonal single — 351 limb products instead of 676.  Shapes stay
    static per unrolled i (tail slices), which Mosaic handles."""
    T = a.shape[1]
    acc = lax.full((52, T), 0, jnp.uint32)
    for i in range(26):
        p = _limb_times(a, i, _rows(a, i, 26))  # a_i * a_j, j >= i
        # double the cross terms (j > i); diagonal stays single.
        # products <= QMAX^2 ~ 2^30.01, doubled < 2^31.02 — the
        # repo-wide uint32 high-water mark (range_lint max_acc): the
        # int32 MXU budget is the binding one for any matmul remap.
        # i=25 has no cross terms: p[1:] would be a zero-row vector,
        # which real Mosaic lowering rejects ("vector types must have
        # positive constant sizes") even though interpret mode allows it
        if p.shape[0] > 1:
            cross = _rows(p, 1, p.shape[0])
            p = lax.concatenate([_rows(p, 0, 1), lax.add(cross, cross)], 0)
        acc = _acc_add(acc, lax.bitwise_and(p, MASK), 2 * i)
        acc = _acc_add(acc, lax.shift_right_logical(p, _SHIFT), 2 * i + 1)
    return _compress1(_compress1(acc))


def _mont_reduce(t, pl_, pp):
    """Montgomery reduction of a (52, T) wide product: m = (t·P') mod R
    (the low half of the full product — columns < 26 coincide with the
    low product's), u = m·P, then one full carry normalization whose low
    26 limbs vanish (divisible by R)."""
    m = _rows(_wide_product(_rows(t, 0, 26), pp), 0, 26)
    u = _wide_product(m, pl_)
    s = lax.add(t, u)  # <= 2^16 per column: both double-compressed <= 32768 (range_lint)
    carry = lax.full((1, t.shape[1]), 0, jnp.uint32)
    out_rows = []
    for k in range(52):
        tcol = lax.add(_rows(s, k, k + 1), carry)
        carry = lax.shift_right_logical(tcol, _SHIFT)
        if k >= 26:
            out_rows.append(lax.bitwise_and(tcol, MASK))
    return lax.concatenate(out_rows, 0)


def _mont_sqr_core(a, pl_, pp):
    """Montgomery square: triangle wide product, shared reduction tail."""
    return _mont_reduce(_wide_square(a), pl_, pp)


def _mont_core(a, b, pl_, pp):
    """One full Montgomery product on in-kernel values -> strict limbs."""
    return _mont_reduce(_wide_product(a, b), pl_, pp)


def _mont_kernel(a_ref, b_ref, p_ref, pp_ref, o_ref):
    o_ref[:] = _mont_core(a_ref[:], b_ref[:], p_ref[:], pp_ref[:])


def _mont_kernel_mxu(a_ref, b_ref, p_ref, pp_ref, o_ref):
    """Same operand contract as _mont_kernel, column sums on the MXU
    (13-bit re-limbed dot-product core, pallas_mxu.py)."""
    from . import pallas_mxu

    o_ref[:] = pallas_mxu.mont_core_mxu(
        a_ref[:], b_ref[:], p_ref[:], pp_ref[:]
    )


def _core_pair(mxu: bool):
    """(mul, sqr) cores for a kernel family: the VPU schoolbook pair or
    the MXU dot-product core (which has no triangle trick — sqr is
    mul(a, a); the dot path's win is the matmul, not the product
    count).  Every fused kernel family threads ``mxu`` through its
    lru_cache factory key so both programs can coexist in one
    process."""
    if mxu:
        from . import pallas_mxu

        mont = pallas_mxu.mont_core_mxu
        return mont, lambda a, pl_, pp: mont(a, a, pl_, pp)
    return _mont_core, _mont_sqr_core


def _select_power(d, powers):
    """Value-level one-hot select of powers[d] for a traced digit d —
    Mosaic has no dynamic gather over a trace-time list, so this is
    2^w - 1 vector selects (cheap next to the w Montgomery squares each
    digit already costs)."""
    sel = powers[0]
    for k in range(1, len(powers)):
        sel = jnp.where(d == k, powers[k], sel)
    return sel


def _make_megachain_kernel(w: int, n_digits: int, mxu: bool = False):
    """The WHOLE exponent chain as ONE Pallas program: the MSB-first
    base-2^w digit tape rides in as a scalar-prefetch operand (SMEM),
    the 2^w-entry power table is built in-kernel (2^w - 2 Montgomery
    products), and a fori_loop walks the tape — w squares plus one
    table-selected multiply per digit.  The compiled program depends
    only on (w, n_digits), never on the digit VALUES: the Fermat
    affinization chain, the h2c sqrt chains, and any future exponent of
    equal digit count share one Mosaic program.  The previous design
    stacked one pallas_call per digit (~96 dispatches for Fermat alone)
    and keyed programs per window pattern (~24 distinct programs), which
    is what made the chains+miller composition a pathological >6,700 s
    Mosaic compile — session2 06:52Z.

    Digit 0 multiplies by the Montgomery one (value-preserving), so the
    loop body is uniform and needs no predication."""

    mont, sqr = _core_pair(mxu)

    def megachain_kernel(tape_ref, base_ref, p_ref, pp_ref, one_ref,
                         o_ref):
        base = base_ref[:]
        pl_, pp = p_ref[:], pp_ref[:]
        powers = [one_ref[:], base]
        for _ in range(2, 1 << w):
            powers.append(mont(powers[-1], base, pl_, pp))

        def step(i, acc):
            for _ in range(w):
                acc = sqr(acc, pl_, pp)  # triangle sqr (~-16%) on VPU
            sel = _select_power(tape_ref[i], powers)
            return mont(acc, sel, pl_, pp)

        acc = _select_power(tape_ref[0], powers)
        o_ref[:] = jax.lax.fori_loop(1, n_digits, step, acc)

    return megachain_kernel


@functools.lru_cache(maxsize=64)
def _mont_call(n_padded: int, tile: int, interpret: bool,
               mxu: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    grid = (n_padded // tile,)
    spec = pl.BlockSpec((26, tile), lambda i: (0, i),
                        memory_space=pltpu.VMEM)
    const_spec = pl.BlockSpec((26, tile), lambda i: (0, 0),
                              memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _mont_kernel_mxu if mxu else _mont_kernel,
        out_shape=jax.ShapeDtypeStruct((26, n_padded), jnp.uint32),
        grid=grid,
        in_specs=[spec, spec, const_spec, const_spec],
        out_specs=spec,
        interpret=interpret,
    )


_BIAS2_COLS = np.asarray(F._biased_kp(2)).astype(np.uint32).reshape(26, 1)
_BIAS16_COLS = np.asarray(F._biased_kp(16)).astype(np.uint32).reshape(26, 1)


def _sub_biased(a, b, bias):
    """Value a - b + k·P, limb-safe when every bias limb >= b's quasi
    limbs (fp._biased_kp boosts all non-top limbs past QMAX) and the
    bias's borrowed-from top limb dominates b's top limb
    (fp._sub_top_dominates — ``k >= b's value bound`` alone is NOT
    sufficient; the in-kernel uses here are interval-proven by
    range_lint over the fp2/Miller programs)."""
    return _compress1((a + bias) - b)


def _fp2_sqr_core(a0, a1, pl_, pp, b16, mont=_mont_core):
    """(a0 + a1·u)²: real (a0+a1)(a0-a1), imag 2·a0·a1 (u² = -1).
    Worst-case input is post-mul (a0 <= ~3.2P, a1 <= ~5.2P): the k=16
    bias covers the subtrahend; outputs re-normalize to (<=1.4P, <=2.4P)."""
    s = _compress1(a0 + a1)
    d = _sub_biased(a0, a1, b16)
    r0 = mont(s, d, pl_, pp)
    t = mont(a0, a1, pl_, pp)
    return r0, _compress1(t + t)


def _fp2_mul_core(a0, a1, b0, b1, pl_, pp, b2, mont=_mont_core):
    """Karatsuba: v0 - v1 + (cross - v0 - v1)·u.  The v's are Montgomery
    outputs (< 1.2P), so k=2 biases suffice; outputs stay <= (3.2P, 5.2P)
    — inside the square's envelope above."""
    v0 = mont(a0, b0, pl_, pp)
    v1 = mont(a1, b1, pl_, pp)
    m = mont(_compress1(a0 + a1), _compress1(b0 + b1), pl_, pp)
    r0 = _sub_biased(v0, v1, b2)
    r1 = _sub_biased(_sub_biased(m, v0, b2), v1, b2)
    return r0, r1


def _make_fp2_megachain_kernel(w: int, n_digits: int, mxu: bool = False):
    """Fp2 whole-chain program, same digit-tape design as
    _make_megachain_kernel (the power table is built in-kernel with
    2^w - 2 Karatsuba multiplies; powers[0] is the Montgomery one so a
    0 digit is value-preserving).

    Bounds: table entries and the loop accumulator are worst-case
    post-mul (<=3.2P, <=5.2P), which _fp2_sqr_core's envelope admits;
    every multiply's subtrahends are Montgomery outputs (<1.2P) so the
    k=2 biases hold for any in-envelope operand — the envelope closes
    across fori_loop iterations exactly as it did across the old
    stacked per-digit calls."""

    mont, _ = _core_pair(mxu)

    def fp2_megachain_kernel(tape_ref, a0_ref, a1_ref, p_ref, pp_ref,
                             b16_ref, b2_ref, one_ref, o0_ref, o1_ref):
        a0, a1 = a0_ref[:], a1_ref[:]
        pl_, pp = p_ref[:], pp_ref[:]
        b16, b2 = b16_ref[:], b2_ref[:]
        powers = [(one_ref[:], jnp.zeros_like(a0)), (a0, a1)]
        for _ in range(2, 1 << w):
            p0, p1 = powers[-1]
            powers.append(
                _fp2_mul_core(p0, p1, a0, a1, pl_, pp, b2, mont=mont)
            )
        pow0 = [p[0] for p in powers]
        pow1 = [p[1] for p in powers]

        def step(i, carry):
            c0, c1 = carry
            for _ in range(w):
                c0, c1 = _fp2_sqr_core(c0, c1, pl_, pp, b16, mont=mont)
            d = tape_ref[i]
            return _fp2_mul_core(c0, c1, _select_power(d, pow0),
                                 _select_power(d, pow1), pl_, pp, b2,
                                 mont=mont)

        d0 = tape_ref[0]
        acc = (_select_power(d0, pow0), _select_power(d0, pow1))
        o0_ref[:], o1_ref[:] = jax.lax.fori_loop(1, n_digits, step, acc)

    return fp2_megachain_kernel


@functools.lru_cache(maxsize=32)
def _fp2_megachain_call(n_padded: int, tile: int, w: int, n_digits: int,
                        interpret: bool, mxu: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    spec = pl.BlockSpec((26, tile), lambda i, tape: (0, i),
                        memory_space=pltpu.VMEM)
    const_spec = pl.BlockSpec((26, tile), lambda i, tape: (0, 0),
                              memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((26, n_padded), jnp.uint32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_padded // tile,),
        in_specs=[spec, spec, const_spec, const_spec, const_spec,
                  const_spec, const_spec],
        out_specs=(spec, spec),
    )
    return pl.pallas_call(
        _make_fp2_megachain_kernel(w, n_digits, mxu),
        out_shape=(out_shape, out_shape),
        grid_spec=grid_spec,
        interpret=interpret,
    )


def fp2_pow_chain(a0_limbs, a1_limbs, bits: tuple[int, ...],
                  w: int = CHAIN_WINDOW, interpret: bool | None = None,
                  mxu: bool | None = None):
    """(a0 + a1·u)^e for static MSB-first bits (leading bit must be 1);
    inputs reduced (bound <= 2).  ONE pallas dispatch: the digit tape is
    a scalar-prefetch operand, power table and window walk live in the
    kernel.  Returns raw limb pair (exit bounds <= (3.2P, 5.2P); callers
    re-reduce).  interpret=None resolves by backend (interpret off-TPU),
    so forced device paths still execute on CPU."""
    assert bits and bits[0] == 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mxu is None:
        mxu = F.mxu_enabled()
    n = a0_limbs.shape[-1]
    tile = pick_tile(n)
    n_padded = -(-n // tile) * tile
    if n_padded != n:
        pad = ((0, 0), (0, n_padded - n))
        a0_limbs = jnp.pad(a0_limbs, pad)
        a1_limbs = jnp.pad(a1_limbs, pad)
    consts = [
        jnp.broadcast_to(jnp.asarray(c, dtype=jnp.uint32), (26, tile))
        for c in (_P_COLS, _PP_COLS, _BIAS16_COLS, _BIAS2_COLS)
    ]
    one0 = jnp.broadcast_to(
        jnp.asarray(np.asarray(F.int_to_limbs(F.R1_INT)).reshape(26, 1),
                    dtype=jnp.uint32), (26, tile))
    digits = _window_digits(
        "".join("1" if b else "0" for b in bits), w)
    tape = jnp.asarray(digits, dtype=jnp.int32)
    call = _fp2_megachain_call(n_padded, tile, w, len(digits), interpret,
                               mxu)
    acc0, acc1 = call(tape, a0_limbs, a1_limbs, *consts, one0)
    if n_padded != n:
        return acc0[:, :n], acc1[:, :n]
    return acc0, acc1


@functools.lru_cache(maxsize=64)
def _megachain_call(n_padded: int, tile: int, w: int, n_digits: int,
                    interpret: bool, mxu: bool = False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    spec = pl.BlockSpec((26, tile), lambda i, tape: (0, i),
                        memory_space=pltpu.VMEM)
    const_spec = pl.BlockSpec((26, tile), lambda i, tape: (0, 0),
                              memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_padded // tile,),
        in_specs=[spec, const_spec, const_spec, const_spec],
        out_specs=spec,
    )
    return pl.pallas_call(
        _make_megachain_kernel(w, n_digits, mxu),
        out_shape=jax.ShapeDtypeStruct((26, n_padded), jnp.uint32),
        grid_spec=grid_spec,
        interpret=interpret,
    )


def _window_digits(bitstr: str, w: int) -> list[int]:
    """MSB-aligned base-2^w digits of a binary string (shared by both
    chain families — the decomposition must never drift between them)."""
    pad = (-len(bitstr)) % w
    bitstr = "0" * pad + bitstr
    return [int(bitstr[i:i + w], 2) for i in range(0, len(bitstr), w)]


def pow_chain_limbs(base_limbs, exponent: int,
                    interpret: bool | None = None, w: int = CHAIN_WINDOW,
                    mxu: bool | None = None):
    """base^exponent (Montgomery domain) as ONE pallas dispatch: the
    MSB-first base-2^w digit tape is a scalar-prefetch operand, the
    power table is built in-kernel, and a fori_loop runs w squares + one
    table-selected multiply per digit (digit 0 multiplies by the
    Montgomery one — value-preserving, keeps the loop body uniform).
    For the 381-bit Fermat exponent this is ~475 in-kernel products in
    one program/dispatch, vs ~96 stacked dispatches over ~24 distinct
    programs for the old per-window design.

    base must be strict/quasi limbs of a value bounded < 4.3P (mont
    outputs and reduced values qualify: every in-kernel product is then
    strict×strict, far under the bound-product ceiling).  interpret=None
    resolves by backend (interpret off-TPU), so forced device paths
    still execute on CPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if mxu is None:
        mxu = F.mxu_enabled()
    digits = _window_digits(bin(exponent)[2:], w)
    tape = jnp.asarray(digits, dtype=jnp.int32)

    n = base_limbs.shape[-1]
    tile = pick_tile(n)
    n_padded = -(-n // tile) * tile
    if n_padded != n:
        base_limbs = jnp.pad(base_limbs, ((0, 0), (0, n_padded - n)))
    p_tile = jnp.broadcast_to(
        jnp.asarray(_P_COLS, dtype=jnp.uint32), (26, tile)
    )
    pp_tile = jnp.broadcast_to(
        jnp.asarray(_PP_COLS, dtype=jnp.uint32), (26, tile)
    )
    one = jnp.broadcast_to(
        jnp.asarray(
            np.asarray(F.int_to_limbs(F.R1_INT)).reshape(26, 1),
            dtype=jnp.uint32),
        (26, tile))
    call = _megachain_call(n_padded, tile, w, len(digits), interpret, mxu)
    acc = call(tape, base_limbs, p_tile, pp_tile, one)
    return acc[:, :n] if n_padded != n else acc


def mont_mul_limbs(a_limbs, b_limbs, interpret: bool = False,
                   mxu: bool | None = None):
    """(26, N) x (26, N) quasi limbs -> (26, N) strict Montgomery product.
    Pads N up to a lane multiple; slices back.  mxu=None resolves from
    the LIGHTHOUSE_TPU_MXU gate (fp.mxu_enabled); True routes the column
    accumulation through the 13-bit dot-product core (pallas_mxu.py)."""
    if mxu is None:
        mxu = F.mxu_enabled()
    n = a_limbs.shape[-1]
    tile = pick_tile(n)
    n_padded = -(-n // tile) * tile
    if n_padded != n:
        pad = ((0, 0), (0, n_padded - n))
        a_limbs = jnp.pad(a_limbs, pad)
        b_limbs = jnp.pad(b_limbs, pad)
    p_tile = jnp.broadcast_to(
        jnp.asarray(_P_COLS, dtype=jnp.uint32), (26, tile)
    )
    pp_tile = jnp.broadcast_to(
        jnp.asarray(_PP_COLS, dtype=jnp.uint32), (26, tile)
    )
    out = _mont_call(n_padded, tile, interpret, mxu)(
        a_limbs, b_limbs, p_tile, pp_tile
    )
    return out[:, :n] if n_padded != n else out
