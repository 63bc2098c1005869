"""Fused Miller-step Pallas kernels: interpret-mode bit-equality vs the
stacked-XLA Miller step (the same proof standard the chain kernels met
before their hardware A/B).

Proof structure: the fused loop reuses the SAME two kernels (dbl half,
add half) for all 63 iterations, and both paths reduce every carried
value to the stable bound class between steps — so step-level canonical
equality on live inputs, iterated twice (covering both bit arms and the
carry path), proves the loop.  The full 63-step loop equality test is
kept under `slow` (its interpret-mode XLA graph takes >40 min to compile
on this 1-core image)."""

import os
import random

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from lighthouse_tpu.crypto.bls import pairing as OP
from lighthouse_tpu.crypto.bls import params
from lighthouse_tpu.crypto.bls.curve import (
    Fp,
    Fp2,
    G1_GENERATOR,
    G2_GENERATOR,
    affine_mul,
    affine_neg,
)
from lighthouse_tpu.crypto.bls.jax_backend import fp as F
from lighthouse_tpu.crypto.bls.jax_backend import pairing as JP
from lighthouse_tpu.crypto.bls.jax_backend import pallas_miller as PM
from lighthouse_tpu.crypto.bls.jax_backend import points as P
from lighthouse_tpu.crypto.bls.jax_backend import tower as T

rng = random.Random(0xF05ED)

pytestmark = [pytest.mark.compile]

# the fused kernels are the largest single compiles in the repo (~160
# unrolled Montgomery multiplies per kernel): persistent cache makes the
# SECOND run of any variant instant
from lighthouse_tpu.crypto.bls.jax_backend.backend import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()


def rand_pairs(n):
    out = []
    for _ in range(n):
        a = rng.randrange(1, params.R)
        b = rng.randrange(1, params.R)
        out.append(
            (affine_mul(G1_GENERATOR, a, Fp), affine_mul(G2_GENERATOR, b, Fp2))
        )
    return out


def encode(pairs):
    return (
        P.g1_encode([p for p, _ in pairs]),
        P.g2_encode([q for _, q in pairs]),
    )


def _canon(lfp):
    return np.asarray(F.fp_canon(lfp))


def _canon_f12(f):
    return [_canon(v) for v in PM._f12_lanes(f)]


_MILLER_OPTIN = pytest.mark.skipif(
    os.environ.get("LIGHTHOUSE_TPU_MILLER_PROOFS", "") != "1",
    reason="each isolated fused-miller proof is a ~45-55 min fresh-process "
    "interpret compile (the XLA:CPU persistent cache does not cover "
    "them); run standalone with LIGHTHOUSE_TPU_MILLER_PROOFS=1",
)


def _run_tool(mode: str, timeout: int = 3600):
    """Every slow fused-miller proof runs in a FRESH interpreter via
    tools/verify_fused_miller.py: the eager proofs are stable standalone
    but an XLA:CPU process-state bug segfaults them inside a pytest
    process that already ran dozens of compiles (reproduced: the r5
    slow tier crashed at exactly this point twice).  Isolation matches
    production anyway — one process, one trace.  Reruns are NOT cheap:
    the XLA:CPU persistent cache does not cover these interpret-mode
    compiles, so every invocation pays the full ~45-55 min — hence the
    opt-in gate above."""
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(
            os.path.dirname(__file__), "..", "tools",
            "verify_fused_miller.py", mode,
        )],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout


@pytest.mark.slow
@_MILLER_OPTIN
def test_fused_step_matches_xla_step_both_arms():
    """One full fused step (dbl kernel chained into add kernel on live
    outputs) vs the XLA step, subprocess-isolated."""
    assert "fused-miller step OK" in _run_tool("--step")


@pytest.mark.slow
@_MILLER_OPTIN
def test_fused_loop_matches_xla_loop():
    """Full 63-step loop equality vs the XLA loop + host oracle
    (interpret compile is >40 min on one core), subprocess-isolated."""
    assert "fused-miller loop OK" in _run_tool("--loop", timeout=5400)


@pytest.mark.slow
@_MILLER_OPTIN
def test_fused_pairing_check_bilinear():
    """e(P,Q)*e(-P,Q) == 1 through the fused loop, subprocess-isolated."""
    assert "fused-miller bilinear OK" in _run_tool("--bilinear",
                                                   timeout=5400)


def test_fused_kernel_halves_match_xla_halves():
    """Per-kernel-half canonical equality vs the XLA formulas, run in a
    SUBPROCESS (tools/verify_fused_miller.py): the eager proof is stable
    in a fresh interpreter but an XLA:CPU process-state bug segfaults it
    inside a pytest process that already ran ~80 compiles — isolation
    matches production anyway (one process, one trace)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(
            os.path.dirname(__file__), "..", "tools",
            "verify_fused_miller.py",
        )],
        capture_output=True,
        text=True,
        timeout=3600,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "fused-miller halves OK" in proc.stdout
