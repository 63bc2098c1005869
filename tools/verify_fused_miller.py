#!/usr/bin/env python3
"""Standalone fused-Miller equality proofs (invoked by
tests/test_pallas_miller.py in a SUBPROCESS: the eager proof is stable
in a fresh interpreter but segfaults inside a long pytest process that
already ran ~80 JAX compiles — an XLA:CPU process-state crash, not a
kernel bug; isolation sidesteps it and matches how the kernels run in
production anyway: one process, one trace).

Default mode: the dbl half and the add half (both bit arms, chained on
live outputs) against the XLA formulas, canonical equality on every
lane.  The kernel bodies run on plain arrays with their Montgomery
cores compiled once each (see ``run_kernel``): about 40 s on an 8-core
CPU host.  The same halves as interpret-mode ``pallas_call`` programs
(``--step``, and the full loop in ``--loop``/``--bilinear``) are one
XLA:CPU compile of ~10^5 primitives each: ``--step`` took 47 minutes
on that host."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import contextlib  # noqa: E402

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from lighthouse_tpu.crypto.bls.jax_backend.backend import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

from lighthouse_tpu.crypto.bls import params  # noqa: E402
from lighthouse_tpu.crypto.bls.curve import (  # noqa: E402
    Fp,
    Fp2,
    G1_GENERATOR,
    G2_GENERATOR,
    affine_mul,
)
from lighthouse_tpu.crypto.bls.jax_backend import fp as F  # noqa: E402
from lighthouse_tpu.crypto.bls.jax_backend import pairing as JP  # noqa: E402
from lighthouse_tpu.crypto.bls.jax_backend import (  # noqa: E402
    pallas_fp as PF,
)
from lighthouse_tpu.crypto.bls.jax_backend import (  # noqa: E402
    pallas_miller as PM,
)
from lighthouse_tpu.crypto.bls.jax_backend import points as P  # noqa: E402
from lighthouse_tpu.crypto.bls.jax_backend import tower as T  # noqa: E402


def build_fixture():
    """Shared inputs + reference values for the equality proofs (used by
    main() and step_proof() — ONE copy of the lane layout, so a
    kernel-signature change cannot desynchronize them)."""
    pairs = [
        (affine_mul(G1_GENERATOR, 20250730, Fp),
         affine_mul(G2_GENERATOR, 424242, Fp2)),
        (affine_mul(G1_GENERATOR, 31337, Fp),
         affine_mul(G2_GENERATOR, 987654321, Fp2)),
    ]
    p_aff = P.g1_encode([p for p, _ in pairs])
    q_aff = P.g2_encode([q for _, q in pairs])

    def pin(c):
        return F.relabel(F.guard_le(c, 2.0), 2.0)

    xp, yp = pin(p_aff[0]), pin(p_aff[1])
    q0 = (pin(q_aff[0][0]), pin(q_aff[0][1]))
    q1 = (pin(q_aff[1][0]), pin(q_aff[1][1]))
    one2 = tuple(F.relabel(c, 2.0) for c in T.fp2_one_like(q0))
    zero = F.zero_like(xp)
    f = (
        (one2, (zero, zero), (zero, zero)),
        ((zero, zero), (zero, zero), (zero, zero)),
    )
    Tpt = (q0, q1, one2)

    # XLA halves, one compiled program (eagerly, every stacked mont_mul
    # shape would compile on its own: ~5x slower)
    @jax.jit
    def xla_halves(f, Tpt, xp, yp, q0, q1):
        line, T_mid = JP._line_dbl(Tpt, xp, yp)
        f_mid = T.fp12_mul_by_023(T.fp12_sqr(f), *line)
        line_a, T_add = JP._line_add(T_mid, (q0, q1), xp, yp)
        return f_mid, T_mid, T.fp12_mul_by_023(f_mid, *line_a), T_add

    ref_f_mid, ref_T_mid, ref_f1, ref_T1 = xla_halves(f, Tpt, xp, yp, q0, q1)
    # bit = 0: the add half keeps its inputs
    ref_f0, ref_T0 = ref_f_mid, ref_T_mid

    def flat(x):
        return x.limbs.reshape(F.N, -1)

    n = flat(xp).shape[-1]
    tile = max(128, -(-n // 128) * 128)
    all_in, n0, n_padded = PM._pad_flat(
        [flat(v) for v in PM._f12_lanes(f)]
        + [flat(c) for pt in Tpt for c in pt]
        + [flat(q0[0]), flat(q0[1]), flat(q1[0]), flat(q1[1])]
        + [flat(xp), flat(yp)],
        tile,
    )
    f_arr = all_in[:12]
    T_arr = all_in[12:18]
    q_arr = all_in[18:22]
    xp_a, yp_a = all_in[22], all_in[23]
    consts = PM._const_arrays(tile)
    return {
        "f_arr": f_arr, "T_arr": T_arr, "q_arr": q_arr,
        "xp_a": xp_a, "yp_a": yp_a, "consts": consts,
        "n0": n0, "n_padded": n_padded, "tile": tile,
        "batch": xp.limbs.shape[1:],
        "ref_f_mid": ref_f_mid, "ref_T_mid": ref_T_mid,
        "ref_f1": ref_f1, "ref_T1": ref_T1,
        "ref_f0": ref_f0, "ref_T0": ref_T0,
    }


def canon(lfp):
    return np.asarray(F.fp_canon(lfp))


def unflat(a, n0, batch):
    import jax.numpy as jnp

    return F.LFp(jnp.asarray(a)[:, :n0].reshape((F.N,) + batch), 2.0)


def check_lanes(tag, ref_f, ref_T, outs, n0, batch):
    for i, (r, g) in enumerate(
        zip([canon(v) for v in PM._f12_lanes(ref_f)],
            [canon(unflat(a, n0, batch)) for a in outs[:12]])
    ):
        assert np.array_equal(r, g), f"{tag}: f lane {i} diverges"
    ref_T_lanes = [canon(c) for pt in ref_T for c in pt]
    for i, (r, g) in enumerate(
        zip(ref_T_lanes, [canon(unflat(a, n0, batch)) for a in outs[12:]])
    ):
        assert np.array_equal(r, g), f"{tag}: T lane {i} diverges"


def step_proof() -> None:
    """One full fused step — dbl kernel chained into add kernel on live
    outputs — against the XLA step (canonical equality, every lane)."""
    import jax.numpy as jnp

    fx = build_fixture()
    dbl = PM._dbl_call(fx["n_padded"], fx["tile"], True)
    add = PM._add_call(fx["n_padded"], fx["tile"], True)
    outs = dbl(*fx["f_arr"], *fx["T_arr"], fx["xp_a"], fx["yp_a"],
               *fx["consts"])
    bit_row = jnp.full((1, fx["n_padded"]), 1, dtype=jnp.uint32)
    outs = add(*list(outs[:12]), *list(outs[12:]), *fx["q_arr"],
               fx["xp_a"], fx["yp_a"], bit_row, *fx["consts"])
    check_lanes("step", fx["ref_f1"], fx["ref_T1"],
                list(outs[:12]) + list(outs[12:]), fx["n0"], fx["batch"])
    print("fused-miller step OK")


def loop_proof() -> None:
    """Full 63-step fused loop vs the XLA loop + host oracle (the
    interpret compile is >40 min on one core)."""
    import random

    import jax

    from lighthouse_tpu.crypto.bls import pairing as OP
    from lighthouse_tpu.crypto.bls.jax_backend import points as Pt
    from lighthouse_tpu.crypto.bls.jax_backend import tower as T

    rng = random.Random(0xF05ED)
    pairs = []
    for _ in range(2):
        a = rng.randrange(1, params.R)
        b = rng.randrange(1, params.R)
        pairs.append((affine_mul(G1_GENERATOR, a, Fp),
                      affine_mul(G2_GENERATOR, b, Fp2)))
    p_aff = Pt.g1_encode([p for p, _ in pairs])
    q_aff = Pt.g2_encode([q for _, q in pairs])
    ref = jax.jit(JP.miller_loop)(p_aff, q_aff)
    fused = jax.jit(PM.miller_loop_fused)(p_aff, q_aff)
    assert T.fp12_decode(fused) == T.fp12_decode(ref), \
        "fused Miller loop diverges from XLA path"
    for (pp, qq), dev in zip(pairs, T.fp12_decode(fused)):
        want = OP.final_exponentiation(OP.miller_loop(pp, qq))
        assert OP.final_exponentiation(dev) == want
    print("fused-miller loop OK")


def bilinear_proof() -> None:
    """e(P,Q)·e(-P,Q) == 1 through the fused loop."""
    import random

    import jax

    from lighthouse_tpu.crypto.bls.curve import affine_neg
    from lighthouse_tpu.crypto.bls.jax_backend import points as Pt

    rng = random.Random(0xF05ED)
    a = rng.randrange(1, params.R)
    b = rng.randrange(1, params.R)
    P_ = affine_mul(G1_GENERATOR, a, Fp)
    Q_ = affine_mul(G2_GENERATOR, b, Fp2)
    pairs = [(P_, Q_), (affine_neg(P_), Q_)]
    p_aff = Pt.g1_encode([p for p, _ in pairs])
    q_aff = Pt.g2_encode([q for _, q in pairs])

    def check(p, q):
        f = PM.miller_loop_fused(p, q)
        return JP.final_exp_is_one(JP.gt_product(f))

    assert bool(jax.jit(check)(p_aff, q_aff)) is True
    print("fused-miller bilinear OK")


class ArrayRef:
    """Stands in for a kernel Ref on a plain array: ``r[:]`` reads the
    whole block, ``r[:] = v`` replaces it."""

    def __init__(self, value=None):
        self.value = value

    def __getitem__(self, idx):
        return self.value[idx]

    def __setitem__(self, idx, value):
        assert idx == slice(None), idx
        self.value = value


@contextlib.contextmanager
def compiled_cores():
    """The kernels' VPU Montgomery cores as two compiled programs.  Run
    op by op, the ~160 unrolled products of a half are ~10^5 dispatches;
    compiled, they are ~160 calls of two small programs."""
    mont, sqr = jax.jit(PF._mont_core), jax.jit(PF._mont_sqr_core)
    saved = PF._core_pair

    def core_pair(mxu):
        assert not mxu
        return mont, sqr

    PF._core_pair = core_pair
    try:
        yield
    finally:
        PF._core_pair = saved


def run_kernel(kernel, ins, n_out):
    """One grid step of ``kernel`` on arrays that are exactly one block:
    the body ``pallas_call`` traces, evaluated without the one giant
    interpret-mode compile."""
    outs = [ArrayRef() for _ in range(n_out)]
    with compiled_cores():
        kernel(*[ArrayRef(a) for a in ins], *outs)
    return [r.value for r in outs]


def main() -> None:
    fx = build_fixture()
    f_arr, T_arr, q_arr = fx["f_arr"], fx["T_arr"], fx["q_arr"]
    xp_a, yp_a, consts = fx["xp_a"], fx["yp_a"], fx["consts"]
    n_padded = fx["n_padded"]
    assert n_padded == fx["tile"], "one block per operand"
    n_out = len(f_arr) + len(T_arr)

    mid = run_kernel(PM._step_dbl_kernel,
                     [*f_arr, *T_arr, xp_a, yp_a, *consts], n_out)

    def run_add(bit):
        import jax.numpy as jnp

        bit_row = jnp.full((1, n_padded), bit, dtype=jnp.uint32)
        return run_kernel(PM._step_add_kernel,
                          [*mid[:12], *mid[12:], *q_arr, xp_a, yp_a,
                           bit_row, *consts], n_out)

    out1 = run_add(1)
    out0 = run_add(0)

    n0, batch = fx["n0"], fx["batch"]
    check_lanes("dbl", fx["ref_f_mid"], fx["ref_T_mid"], mid, n0, batch)
    check_lanes("add/bit=1", fx["ref_f1"], fx["ref_T1"], out1, n0, batch)
    check_lanes("add/bit=0", fx["ref_f0"], fx["ref_T0"], out0, n0, batch)
    print("fused-miller halves OK")


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else ""
    if mode == "--step":
        step_proof()
    elif mode == "--loop":
        loop_proof()
    elif mode == "--bilinear":
        bilinear_proof()
    else:
        main()
