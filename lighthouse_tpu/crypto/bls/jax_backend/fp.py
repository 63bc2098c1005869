"""Batched BLS12-381 base-field arithmetic in JAX — the TPU limb kernel core.

This is the device analog of blst's C/assembly fp arithmetic (the reference
consumes it via crypto/bls/src/impls/blst.rs); every higher layer of the TPU
backend (Fp2/Fp6/Fp12 tower, curve ops, pairing) is built on the ops here and
is differentially tested against the pure-Python oracle (fields.py).

Representation: lazy reduction with static bound tracking
---------------------------------------------------------
An Fp element is an ``LFp(limbs, bound)``: 26 x 15-bit limbs, little-endian,
each in a uint32 lane, shape ``(26, *batch)`` — the limb axis LEADS so the
trailing batch axis rides the TPU's 128-wide vector lanes.  Montgomery domain
with R = 2^390.  ``bound`` is a STATIC (trace-time) upper bound on the value
in units of P; it is pytree aux data, so it travels through jit/scan/select
and mismatches surface as loud trace-time errors, never silent corruption.

Limbs are only *quasi-normalized* (<= 2^15 + 2^7) and values are bounded by
small multiples of P rather than reduced mod P.  This removes every
sequential carry chain from additions and subtractions (one vector add plus
a two-op carry "compress"), which keeps both the XLA graph and the VPU work
per op small.  Op contracts:

* ``fp_add``: value a+b, bound a.bound + b.bound.
* ``fp_sub``: value a - b + k*P where k (a power of two >= b.bound) is
  chosen automatically; the precomputed biased k*P has every non-top limb
  >= any quasi limb, and ``_k_for`` additionally requires the (borrowed)
  top bias limb to dominate the subtrahend's value-capped top limb, so no
  column subtraction can go negative.
* ``mont_mul``: requires a.bound * b.bound <= 2000 (checked at trace time);
  output has STRICT limbs and bound a.bound*b.bound/625 + 1.1 (< 4.3).
  (P/R ~ 2^-9.3 ~ 1/625.)
* ``fp_reduce(x) = mont_mul(x, R mod P)``: value-preserving mod P, bound
  back to < 2 — inserted at op boundaries (tower/point outputs) so bounds
  cannot creep and scan carries keep a stable static bound.
* Canonical form (value < P) exists only at the edges: ``fp_canon`` for
  equality tests, host codecs for I/O.

Multiplication is schoolbook via a Horner scan (acc = acc*2^15 + a_i*b) with
32-bit partial products split at 15 bits before accumulation (column sums
< 2^21, no uint32 overflow).  Montgomery reduction is m = T*P' mod R;
(T + m*P)/R, with ONE sequential carry normalization at the end — the only
per-limb chain in the hot path.
"""

from __future__ import annotations

import functools
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import params

# ---------------------------------------------------------------------------
# Constants
# ---------------------------------------------------------------------------

BITS = 15
N = 26  # 26 * 15 = 390 bits >= 381
MASK = (1 << BITS) - 1
QMAX = (1 << BITS) + (1 << 7)  # quasi-normalized limb bound
U32 = jnp.uint32

MAX_MUL_PRODUCT = 2000.0  # max a.bound * b.bound entering mont_mul
MAX_BOUND = 500.0  # max value bound anywhere (keeps top limb small)

# Montgomery output-bound model: mont_mul emits bound
# prod / MONT_DIVISOR + MONT_EPS where prod = a.bound * b.bound.  The
# exact bound is prod * P/R + 1 with R/P = 630.0525..., so divisor 625
# with intercept 1.1 over-covers by 2.9% at prod = MAX_MUL_PRODUCT —
# machine-checked by analysis/range_lint ("mont-output-bound").
MONT_DIVISOR = 625.0
MONT_EPS = 1.1
# fp_reduce pins its output label here; the exact worst case is
# MAX_BOUND * P/R + 1 = 1.794 (range_lint "reduce-pin").
REDUCE_PIN = 2.0

P_INT = params.P
R_INT = 1 << (BITS * N)  # Montgomery radix 2^390
assert R_INT > 512 * P_INT
R1_INT = R_INT % P_INT  # 1 in Montgomery form
R2_INT = R_INT * R_INT % P_INT
PPRIME_INT = (-pow(P_INT, -1, R_INT)) % R_INT  # -P^-1 mod R

_BIAS_KS = (2, 4, 8, 16, 32, 64, 128, 256)


class LFp:
    """Lazy field element: quasi-normalized limbs + static value bound (in
    units of P).  Registered as a pytree with ``bound`` as aux data."""

    __slots__ = ("limbs", "bound")

    def __init__(self, limbs, bound: float):
        self.limbs = limbs
        self.bound = bound

    def __repr__(self):
        return f"LFp(shape={getattr(self.limbs, 'shape', None)}, bound={self.bound})"


def _lfp_flatten(x):
    return (x.limbs,), x.bound


def _lfp_unflatten(bound, children):
    return LFp(children[0], bound)


jax.tree_util.register_pytree_node(LFp, _lfp_flatten, _lfp_unflatten)


def int_to_limbs(x: int) -> np.ndarray:
    """Host codec: non-negative int < 2^390 -> (N,) uint32 strict limbs."""
    assert 0 <= x < R_INT
    return np.array([(x >> (BITS * i)) & MASK for i in range(N)], dtype=np.uint32)


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs, dtype=np.uint64)
    return sum(int(v) << (BITS * i) for i, v in enumerate(arr))


def ints_to_limbs(xs) -> np.ndarray:
    """Host codec, vectorized: ints -> (N, B) limb columns.  int.to_bytes
    is C-speed; the 8-bit -> 15-bit regrouping is one unpackbits reshape
    (the per-int Python limb loop was a marshal bottleneck at B=4096)."""
    B = len(xs)
    if B == 0:
        return np.zeros((N, 0), dtype=np.uint32)
    raw = np.frombuffer(
        b"".join(x.to_bytes(49, "little") for x in xs), dtype=np.uint8
    ).reshape(B, 49)
    # bits in little-endian significance order per value
    bits = np.unpackbits(raw, axis=1, bitorder="little")[:, : N * BITS]
    weights = (1 << np.arange(BITS, dtype=np.uint32))
    limbs = (bits.reshape(B, N, BITS) * weights[None, None, :]).sum(
        axis=2, dtype=np.uint32
    )
    return np.ascontiguousarray(limbs.T)


def limbs_to_ints(limbs) -> list[int]:
    arr = np.asarray(limbs)
    flat = arr.reshape(N, -1)
    return [limbs_to_int(flat[:, j]) for j in range(flat.shape[1])]


def _biased_kp(k: int) -> np.ndarray:
    """k*P with every non-top limb boosted to >= QMAX by borrowing from the
    limb above, so (a + bias - b) is column-wise non-negative for quasi b.

    The boosting borrows exactly one unit into the top limb, lowering it
    to floor(k*P / 2^375) - 1 — which is why ``k >= b.bound`` alone does
    NOT guarantee top-column domination; ``_k_for`` additionally enforces
    ``_sub_top_dominates`` (machine-checked by range_lint "bias-k*")."""
    limbs = [int(v) for v in int_to_limbs(k * P_INT)]
    for i in range(N - 1):
        while limbs[i] < QMAX:
            limbs[i] += 1 << BITS
            limbs[i + 1] -= 1
    assert limbs[N - 1] >= 0, f"bias top limb underflow for k={k}"
    assert sum(v << (BITS * i) for i, v in enumerate(limbs)) == k * P_INT
    return np.array(limbs, dtype=np.uint32)


P_LIMBS = jnp.asarray(int_to_limbs(P_INT))
PPRIME_LIMBS = jnp.asarray(int_to_limbs(PPRIME_INT))
ONE_MONT = jnp.asarray(int_to_limbs(R1_INT))
_BIAS_NP = {k: _biased_kp(k) for k in _BIAS_KS}
BIAS = {k: jnp.asarray(v) for k, v in _BIAS_NP.items()}


def bcast(const, batch_shape) -> jnp.ndarray:
    return jnp.broadcast_to(
        const.reshape((N,) + (1,) * len(batch_shape)), (N,) + tuple(batch_shape)
    )


def zero_like(a: LFp) -> LFp:
    return LFp(jnp.zeros_like(a.limbs), 0.0)


def one_like(a: LFp) -> LFp:
    return LFp(bcast(ONE_MONT, a.limbs.shape[1:]), 1.0)


def batch_shape(a: LFp):
    return a.limbs.shape[1:]


# ---------------------------------------------------------------------------
# Carry handling (raw limb arrays)
# ---------------------------------------------------------------------------


def compress1(cols):
    """One carry pass: quasi-normalizes column sums < 2^16.6 (worst case
    is fp_sub: quasi a + boosted bias limb <= 32896 + 65663 = 98559, so
    hi <= 3 and outputs stay <= MASK + 3 <= QMAX).  The top limb's carry
    is statically impossible: any value < MAX_BOUND*P has top column
    <= floor(MAX_BOUND*P / 2^375) = 26142 < 2^15 (range_lint
    "compress1-top-carry")."""
    lo = cols & MASK
    hi = cols >> BITS
    return lo.at[1:].add(hi[:-1])


def compress2(cols):
    """Two passes: quasi-normalizes column sums < 2^21 (Horner output)."""
    return compress1(compress1(cols))


def full_chain(cols):
    """Sequential full normalization to strict limbs — the one per-limb
    chain, used once per mont_mul."""
    init = jnp.zeros(cols.shape[1:], dtype=U32)

    def step(c, col):
        t = col + c
        return t >> BITS, t & MASK

    carry, limbs = lax.scan(step, init, cols)
    del carry
    return limbs


def sub_chain(x, y):
    """Limb-wise x - y with borrow (strict inputs); (diff, borrow)."""
    init = jnp.zeros(x.shape[1:], dtype=U32)

    def step(bor, xy):
        x_k, y_k = xy
        t = x_k + U32(1 << BITS) - y_k - bor
        return U32(1) - (t >> BITS), t & MASK

    borrow, limbs = lax.scan(step, init, (x, y))
    return limbs, borrow


# ---------------------------------------------------------------------------
# Add / sub / neg (chain-free)
# ---------------------------------------------------------------------------


def _check_bound(b: float, who: str):
    assert b <= MAX_BOUND, f"{who}: value bound {b} exceeds {MAX_BOUND}P"


def fp_add(a: LFp, b: LFp) -> LFp:
    out = a.bound + b.bound
    _check_bound(out, "fp_add")
    return LFp(compress1(a.limbs + b.limbs), out)


def _sub_top_dominates(bound: float, k: int) -> bool:
    """Exact (Fraction) check that the k bias dominates every quasi
    subtrahend of value bound ``bound`` in the TOP column too: such a
    value's limb 25 is at most floor(bound*P / 2^375), which must not
    exceed the bias top limb.  ``k >= bound`` alone is insufficient —
    ``_biased_kp`` borrows one unit out of the top limb, so e.g. a
    bound-2.0 subtrahend can carry top limb 104 against the k=2 bias
    top of 103, wrapping the uint32 column."""
    top = int(_BIAS_NP[k][N - 1])
    return Fraction(bound) * P_INT < (top + 1) << (BITS * (N - 1))


@functools.lru_cache(maxsize=None)
def _k_for(bound: float) -> int:
    """Smallest bias constant k with k >= bound AND top-limb domination
    (see _sub_top_dominates).  Shared by the XLA ops and the fused
    Pallas kernels — both paths must pick identical k or the fused/XLA
    bit-equality contract breaks."""
    for k in _BIAS_KS:
        if k >= bound and _sub_top_dominates(bound, k):
            return k
    raise AssertionError(f"no safe bias constant for bound {bound}")


@functools.lru_cache(maxsize=None)
def sub_bias_max_bound(k: int) -> float:
    """Largest float subtrahend bound the k bias provably dominates (and
    thus the largest _k_for routes to k).  The range prover quantifies
    the per-k fp_sub/ksub proof obligations at exactly this edge."""
    top = int(_BIAS_NP[k][N - 1])
    f = min(float(k), float(Fraction((top + 1) << (BITS * (N - 1)), P_INT)))
    while f > 0 and not (f <= k and _sub_top_dominates(f, k)):
        f = float(np.nextafter(f, 0.0))
    return f


def fp_sub(a: LFp, b: LFp) -> LFp:
    """Value a - b + k*P, k auto-chosen so the bias dominates b column-
    wise (k >= b.bound for the value, _sub_top_dominates for limb 25)."""
    k = _k_for(b.bound)
    out = a.bound + k
    _check_bound(out, "fp_sub")
    bias = bcast(BIAS[k], a.limbs.shape[1:])
    return LFp(compress1(a.limbs + bias - b.limbs), out)


def fp_neg(a: LFp) -> LFp:
    k = _k_for(a.bound)
    bias = bcast(BIAS[k], a.limbs.shape[1:])
    return LFp(compress1(bias - a.limbs), float(k))


def fp_dbl(a: LFp) -> LFp:
    return fp_add(a, a)


def fp_select(mask, a: LFp, b: LFp) -> LFp:
    """mask over batch shape: a where mask else b (bound = max)."""
    return LFp(jnp.where(mask[None], a.limbs, b.limbs), max(a.bound, b.bound))


def relabel(a: LFp, bound: float) -> LFp:
    """Weaken the bound label (bound may only increase)."""
    assert bound >= a.bound
    return LFp(a.limbs, bound)


# ---------------------------------------------------------------------------
# Multiplication
# ---------------------------------------------------------------------------


def _mul_cols_wide(a_limbs, b_limbs):
    nb = a_limbs.shape[1:]
    acc0 = jnp.zeros((2 * N,) + nb, dtype=U32)

    def step(acc, a_i):
        p = a_i[None] * b_limbs
        plo = p & MASK
        phi = p >> BITS
        acc = jnp.concatenate([jnp.zeros_like(acc[:1]), acc[:-1]], axis=0)
        acc = acc.at[:N].add(plo)
        acc = acc.at[1 : N + 1].add(phi)
        return acc, None

    acc, _ = lax.scan(step, acc0, jnp.flip(a_limbs, 0))
    return compress2(acc)


def _mul_cols_low(a_limbs, b_limbs):
    nb = a_limbs.shape[1:]
    acc0 = jnp.zeros((N,) + nb, dtype=U32)

    def step(acc, a_i):
        p = a_i[None] * b_limbs
        plo = p & MASK
        phi = p >> BITS
        acc = jnp.concatenate([jnp.zeros_like(acc[:1]), acc[:-1]], axis=0)
        acc = acc + plo
        acc = acc.at[1:].add(phi[:-1])
        return acc, None

    acc, _ = lax.scan(step, acc0, jnp.flip(a_limbs, 0))
    return compress2(acc)


_PALLAS_MODE: bool | None = None


def pallas_enabled() -> bool:
    """Route mont_mul through the fused Pallas kernel (pallas_fp.py).

    Default: ON for TPU backends (12.5x measured over the scan path,
    PERF.md), OFF on CPU where the scan path is the fast oracle and
    Pallas would run interpreted.  LIGHTHOUSE_TPU_PALLAS=1/0 overrides."""
    global _PALLAS_MODE
    if _PALLAS_MODE is None:
        import os

        val = os.environ.get("LIGHTHOUSE_TPU_PALLAS")
        if val is not None:
            _PALLAS_MODE = val == "1"
        else:
            _PALLAS_MODE = jax.default_backend() == "tpu"
    return _PALLAS_MODE


def set_pallas(enabled: bool) -> None:
    global _PALLAS_MODE
    _PALLAS_MODE = enabled


_FORCE_DEVICE_PATHS = False


def set_force_device_paths(enabled: bool) -> None:
    """Treat the backend as a TPU for routing decisions (the *_active()
    gates) regardless of jax.default_backend().  For CPU-side tracing and
    auditing of the exact device composition (tools/dispatch_audit.py):
    pallas calls reached this way must run with interpret=True or be
    abstractly traced, never Mosaic-compiled."""
    global _FORCE_DEVICE_PATHS
    _FORCE_DEVICE_PATHS = enabled


def _device_backend() -> bool:
    return _FORCE_DEVICE_PATHS or jax.default_backend() == "tpu"


_CHAINS_MODE: bool | None = None


def chains_enabled() -> bool:
    """LIGHTHOUSE_TPU_CHAINS=1 routes static-exponent chains through the
    chunked Pallas chain kernels (interpret-proven; flips to default-on
    once measured on hardware)."""
    global _CHAINS_MODE
    if _CHAINS_MODE is None:
        import os

        _CHAINS_MODE = os.environ.get("LIGHTHOUSE_TPU_CHAINS", "") == "1"
    return _CHAINS_MODE


def set_chains(enabled: bool) -> None:
    """In-process A/B toggle (mirrors set_pallas)."""
    global _CHAINS_MODE
    _CHAINS_MODE = enabled


def chains_active() -> bool:
    """The ONE gate for chain-kernel routing (fp_pow, h2c fp2 chains):
    pallas on + chains opted in + a real TPU backend."""
    return (
        pallas_enabled() and chains_enabled() and _device_backend()
    )


_WSM_MODE: bool | None = None


def wsm_enabled() -> bool:
    """LIGHTHOUSE_TPU_WSM=1 routes the 64-bit weight scalar muls through
    the fused double-and-add step kernels (pallas_wsm.py;
    interpret-proven — flips to default-on once measured on hardware).
    After the fused Miller loop these became the dispatch leader
    (~900 stacked pallas calls per batch)."""
    global _WSM_MODE
    if _WSM_MODE is None:
        import os

        _WSM_MODE = os.environ.get("LIGHTHOUSE_TPU_WSM", "") == "1"
    return _WSM_MODE


def set_wsm(enabled: bool) -> None:
    """In-process A/B toggle (mirrors set_chains)."""
    global _WSM_MODE
    _WSM_MODE = enabled


def wsm_fused_active() -> bool:
    """Gate for the fused scalar-mul step kernels: pallas on + opted in
    + a real TPU backend (interpret mode is reached explicitly by
    tests)."""
    return (
        pallas_enabled() and wsm_enabled() and _device_backend()
    )


_MILLER_MODE: bool | None = None


def miller_enabled() -> bool:
    """Fused Miller-step kernels (pallas_miller.py): DEFAULT ON since the
    r5 on-chip A/B (3,061 vs 2,607 sets/s at B=512; 6,221 at B=8192,
    BENCH_HISTORY.jsonl).  LIGHTHOUSE_TPU_MILLER=0 reverts to the
    stacked per-op pallas calls."""
    global _MILLER_MODE
    if _MILLER_MODE is None:
        import os

        _MILLER_MODE = os.environ.get("LIGHTHOUSE_TPU_MILLER", "1") == "1"
    return _MILLER_MODE


def set_miller(enabled: bool) -> None:
    """In-process A/B toggle (mirrors set_chains)."""
    global _MILLER_MODE
    _MILLER_MODE = enabled


def miller_fused_active() -> bool:
    """Gate for the fused Miller-step kernels: pallas on + opted in + a
    real TPU backend (interpret mode is reached explicitly by tests)."""
    return (
        pallas_enabled() and miller_enabled() and _device_backend()
    )


_MXU_MODE: bool | None = None
_MXU_PLAN: dict | None = None


def mxu_enabled() -> bool:
    """Routes every Montgomery product — the standalone mont_mul kernel,
    the megachains, and the fused Miller loop — through the 13-bit
    re-limbed dot-product core (pallas_mxu.py) that runs the schoolbook
    column accumulation on the MXU instead of the VPU.  Interpret-proven
    byte-identical to the VPU kernels and range-proven under the int32
    2^31 MXU budget.

    Resolution precedence: ``set_mxu`` in-process override (A/B sweeps)
    > ``LIGHTHOUSE_TPU_MXU`` env flag (explicit operator override) >
    installed autotuned plan default (``install_mxu_plan``, the largest
    tuned shape's arm) > off.  The env flag is read live — an unset flag
    never latches, so a plan installed later (prewarm) is not shadowed."""
    if _MXU_MODE is not None:
        return _MXU_MODE
    import os

    env = os.environ.get("LIGHTHOUSE_TPU_MXU")
    if env is not None:
        return env == "1"
    if _MXU_PLAN is not None:
        default = _MXU_PLAN.get("*")
        if default is not None:
            return bool(default)
    return False


def set_mxu(enabled: bool | None) -> bool | None:
    """In-process A/B override (mirrors set_chains).  Beats both the env
    flag and any installed autotuned plan; ``None`` clears the override.
    Returns the previous override so callers can restore it exactly."""
    global _MXU_MODE
    prev = _MXU_MODE
    _MXU_MODE = None if enabled is None else bool(enabled)
    return prev


def install_mxu_plan(shapes: dict | None) -> None:
    """Install the autotuned per-shape arm plan (autotune.install_plan's
    seam): ``shapes`` maps padded batch size -> route-through-MXU, plus
    an optional ``"*"`` default for off-plan shapes.  ``None`` clears.
    Overrides (``set_mxu`` / ``LIGHTHOUSE_TPU_MXU``) still win — see
    ``mxu_enabled``."""
    global _MXU_PLAN
    _MXU_PLAN = dict(shapes) if shapes else None


def mxu_planned(batch) -> bool | None:
    """The installed plan's arm for padded batch ``batch``, or ``None``
    when no plan binds that shape or an explicit override (set_mxu / env
    flag) is active — overrides force one arm for *every* shape."""
    if _MXU_MODE is not None:
        return None
    import os

    if os.environ.get("LIGHTHOUSE_TPU_MXU") is not None:
        return None
    if _MXU_PLAN is None:
        return None
    routed = _MXU_PLAN.get(batch)
    if routed is None:
        routed = _MXU_PLAN.get("*")
    return None if routed is None else bool(routed)


def mxu_for_batch(batch) -> bool:
    """The arm the compiled program for padded batch ``batch`` should
    trace under: the planned arm when a plan binds, the process-wide
    gate otherwise.  This is what ``JaxBackend._kernel`` keys its cache
    and fingerprints on — the plan is resolved here, at lookup/compile
    time, never per dispatched batch."""
    planned = mxu_planned(batch)
    return mxu_enabled() if planned is None else planned


def mxu_active() -> bool:
    """Gate for the MXU dot-product Montgomery core: pallas on + opted
    in + a real TPU backend (interpret mode is reached explicitly by
    tests and the CPU bench fallback)."""
    return (
        pallas_enabled() and mxu_enabled() and _device_backend()
    )


def mont_mul(a: LFp, b: LFp) -> LFp:
    """Montgomery product a*b*R^-1 mod P (strict limbs out)."""
    prod = a.bound * b.bound
    assert prod <= MAX_MUL_PRODUCT, (
        f"mont_mul input bound product {prod} > {MAX_MUL_PRODUCT}; "
        "insert fp_reduce on an operand"
    )
    if pallas_enabled():
        from . import pallas_fp

        batch = a.limbs.shape[1:]
        flat = pallas_fp.mont_mul_limbs(
            a.limbs.reshape(N, -1),
            b.limbs.reshape(N, -1),
            # the kernel is Mosaic/TPU-only: interpret everywhere else
            interpret=jax.default_backend() != "tpu",
        )
        return LFp(flat.reshape((N,) + batch), prod / MONT_DIVISOR + MONT_EPS)
    t = _mul_cols_wide(a.limbs, b.limbs)
    m = _mul_cols_low(t[:N], bcast(PPRIME_LIMBS, a.limbs.shape[1:]))
    u = _mul_cols_wide(m, bcast(P_LIMBS, a.limbs.shape[1:]))
    s = full_chain(t + u)  # low N limbs are exactly zero (divisible by R)
    return LFp(s[N:], prod / MONT_DIVISOR + MONT_EPS)


def mont_sqr(a: LFp) -> LFp:
    return mont_mul(a, a)


def fp_reduce(x: LFp) -> LFp:
    """Value-preserving (mod P) reduction.  The output bound is pinned to
    REDUCE_PIN = 2.0 (exact worst case MAX_BOUND*P/R + 1 = 1.794; the
    formula bound x.bound/MONT_DIVISOR + MONT_EPS <= 1.9 for in-range x)
    so reduced values have a STABLE static bound — required for lax.scan
    carries, whose pytree aux must match between iterations."""
    out = mont_mul(x, one_like(x))
    assert out.bound <= REDUCE_PIN
    return LFp(out.limbs, REDUCE_PIN)


def guard_le(x: LFp, m: float) -> LFp:
    """Reduce x iff its bound exceeds m (trace-time decision)."""
    return fp_reduce(x) if x.bound > m else x


def fp_canon(x: LFp):
    """Canonical raw limbs (strict, value < P) for equality/serialization."""
    if x.bound > 2.0:
        x = fp_reduce(x)
    limbs = x.limbs
    p = bcast(P_LIMBS, limbs.shape[1:])
    d, borrow = sub_chain(limbs, p)
    return jnp.where((borrow == 0)[None], d, limbs)


def fp_eq(a: LFp, b: LFp):
    return jnp.all(fp_canon(a) == fp_canon(b), axis=0)


def fp_is_zero(a: LFp):
    return jnp.all(fp_canon(a) == 0, axis=0)


def fp_pow(a: LFp, e: int) -> LFp:
    """a^e for a static exponent.  The scan carry keeps a stable bound by
    reducing nothing: mont outputs of (reduced x reduced) stay < 2."""
    assert e >= 0
    if e == 0:
        return one_like(a)
    if a.bound > 4.0:
        a = fp_reduce(a)
    # chunked in-kernel chains only on real TPU, and only opt-in until
    # validated on hardware (the relay wedged before the A/B completed;
    # the mont_mul kernel is hardware-proven, the chain variants are
    # interpret-proven): LIGHTHOUSE_TPU_CHAINS=1
    if e > 3 and chains_active():
        from . import pallas_fp

        batch = a.limbs.shape[1:]
        flat = pallas_fp.pow_chain_limbs(a.limbs.reshape(N, -1), e)
        fixp = MAX_MUL_PRODUCT / MONT_DIVISOR + MONT_EPS
        return LFp(flat.reshape((N,) + batch), fixp)
    bits = jnp.array([int(c) for c in bin(e)[2:]], dtype=U32)
    # stabilize the carried bound: sqr of <=4.3 would grow, so pin to the
    # fixpoint bound of mont outputs (range_lint "pow-fix-closure")
    fix = MAX_MUL_PRODUCT / MONT_DIVISOR + MONT_EPS  # 4.3, closed? no:
    # 4.3*4.3 = 18.5 <= 2000 ok, out = 18.5/625+1.1 = 1.13 < 4.3 ✓ and
    # mul with a (<= 4.3): 1.13*4.3 ok, out < 1.11 < 4.3 ✓  => 4.3 is stable.

    def step(acc, bit):
        acc = mont_sqr(acc)
        withmul = mont_mul(acc, a)
        sel = fp_select(bit == 1, withmul, acc)
        return relabel(sel, fix), None

    acc, _ = lax.scan(step, relabel(one_like(a), fix), bits)
    return acc


def fp_inv(a: LFp) -> LFp:
    """Inverse by Fermat: a^(P-2).  a ≡ 0 maps to 0."""
    return fp_pow(a, P_INT - 2)


# ---------------------------------------------------------------------------
# Host codecs
# ---------------------------------------------------------------------------


def encode_mont(xs) -> np.ndarray:
    """Host: ints (standard domain) -> (N, B) canonical Montgomery limbs."""
    return ints_to_limbs([x * R_INT % P_INT for x in xs])


def lfp_encode(xs) -> LFp:
    return LFp(jnp.asarray(encode_mont(xs)), 1.0)


def decode_mont(x) -> list[int]:
    """Host: LFp or raw limb array (any lazy form) -> standard-domain ints."""
    limbs = x.limbs if isinstance(x, LFp) else x
    rinv = pow(R_INT, -1, P_INT)
    return [v * rinv % P_INT for v in limbs_to_ints(np.asarray(limbs))]
