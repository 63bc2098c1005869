"""Multi-chip batch verification: data-parallel sharding over a device mesh.

The reference scales batch BLS verification across CPU cores with rayon
chunking (consensus/state_processing/src/per_block_processing/
block_signature_verifier.rs:396-405: sets/threads chunks, AND-reduce).  The
TPU analog shards the signature-set batch across the mesh's data axis with
``shard_map``: every device runs subgroup checks, weight scalar muls, and
Miller loops for its local shard; the tiny combine — the GT partial products
(one Fp12 per device) and the local signature accumulators (one G2 point per
device) — crosses ICI via all_gather, and the single final exponentiation is
computed replicated.  The GT accumulation is associative, exactly the
property SURVEY.md §2.8 calls out for mesh reduction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS

from ....parallel.mesh import (
    allgather_tree,
    and_reduce,
    batch_spec,
    shard_map,
    ring_reduce,
)
from . import fp as F
from . import pairing as PR
from . import points as P
from . import tower as T
from .backend import _neg_gen_const, _tree_reduce_g2



def _trailing_extent(tree) -> int:
    """Trailing-axis extent of the first leaf — the global batch size."""
    return int(jax.tree.leaves(tree)[0].shape[-1])


def _pad_tail_cols(tree, pad: int):
    """Append ``pad`` copies of column 0 to every leaf's trailing axis.

    Column 0 is an arbitrary *real* entry, so the padding is well-formed
    field data; whether it is verdict-neutral depends on the kernel's
    combine — AND-reduce kernels tolerate it as-is (a duplicate of a
    valid set stays valid; of an invalid set, the verdict was already
    False), product-combine kernels must additionally mask the padded
    lanes out (see make_pair_sharded_aggregate_verify).
    """
    if pad <= 0:
        return tree
    return jax.tree.map(
        lambda a: jnp.concatenate(
            [a, jnp.repeat(a[..., :1], pad, axis=-1)], axis=-1
        ),
        tree,
    )


def make_verify_sharded(mesh: Mesh, axis: str = "batch"):
    """Build a jitted, mesh-sharded verify kernel.

    Returns fn(pk_aff, sig_aff, h_aff, wbits) -> bool where all inputs
    carry the global batch on the trailing axis.  Batches not divisible
    by the mesh size are padded up with duplicates of set 0 (AND-safe:
    padding cannot flip the conjunction) — each distinct padded extent
    traces its own program, same as any new batch size.
    """
    in_spec = batch_spec(2, axis=axis)  # (limbs, B) arrays split on B

    def local_part(pk_aff, sig_aff, h_aff, wbits):
        # --- per-device heavy compute on the local shard ---
        ok_sub = jnp.all(P.g2_subgroup_check(sig_aff))
        wpk = P.scalar_mul_bits(P.FP_OPS, P.from_affine(P.FP_OPS, pk_aff), wbits)
        wsig = P.scalar_mul_bits(
            P.FP2_OPS, P.from_affine(P.FP2_OPS, sig_aff), wbits
        )
        S_local = _tree_reduce_g2(wsig)  # batch-1 G2 jacobian
        wpk_aff = P.to_affine(P.FP_OPS, wpk, F.fp_inv)
        f_local = PR.miller_loop(wpk_aff, h_aff)
        g_local = PR.gt_product(f_local)  # batch-1 fp12
        # --- tiny cross-device combine over ICI (parallel/mesh.py) ---
        g_all = allgather_tree(g_local, axis)
        S_all = allgather_tree(S_local, axis)
        ok_all = and_reduce(ok_sub, axis)
        # --- replicated epilogue: fold in (-G1, S) and final-exponentiate ---
        g = PR.gt_product(g_all)
        S = _tree_reduce_g2(S_all)
        s_inf = P.pt_is_infinity(P.FP2_OPS, S)
        S_aff = P.to_affine(P.FP2_OPS, S, T.fp2_inv)
        neg_gen = _neg_gen_const()
        f_last = PR.miller_loop(neg_gen, S_aff)
        one = PR._fp12_one_like_from_fp2(S_aff[0])
        f_last = T.fp12_select(jnp.broadcast_to(s_inf, (1,)), one, f_last)
        total = T.fp12_mul(g, f_last)
        ok_pair = PR.final_exp_is_one(total)
        return jnp.reshape(ok_pair & ok_all, ())

    # check_vma=False — re-verified against this jax version (r5): with
    # check_vma=True the first field-core scan fails typing with
    #   "input carry acc has type uint32[52,18] but the corresponding
    #    output carry component has type uint32[52,18]{V:batch} ...
    #    might be fixed by applying jax.lax.pcast(..., ('batch',),
    #    to='varying') to the initial carry value"
    # because every Horner/Montgomery scan in fp.py initializes its carry
    # from a replicated zero/constant while the loop body mixes in
    # batch-varying limbs.  Fixing it "properly" means pcast at every
    # carry init — but those inits live in the limb library, which is
    # used both inside and outside shard_map, and pcast with an axis
    # name is an error outside a mesh context.  Threading an
    # inside-a-mesh flag through fp.py buys type checking and costs a
    # second code path in the hottest code; correctness is instead
    # pinned by the shard-vs-single bit-equality tests
    # (test_multichip.py), the poisoned-batch rejection in the
    # driver's dryrun, and — statically — the spmd audit family
    # (analysis/spmd_lint.py), whose own replication check proves the
    # scan-with-replicated-carry pattern device-identical through
    # exactly the typing gap check_vma trips over here.
    sharded = shard_map(
        local_part,
        mesh=mesh,
        in_specs=(in_spec, in_spec, in_spec, in_spec),
        out_specs=PS(),
    )
    jitted = jax.jit(sharded)
    n = int(mesh.devices.size)

    def verify(pk_aff, sig_aff, h_aff, wbits):
        pad = (-_trailing_extent(pk_aff)) % n
        if pad:
            pk_aff, sig_aff, h_aff, wbits = _pad_tail_cols(
                (pk_aff, sig_aff, h_aff, wbits), pad
            )
        return jitted(pk_aff, sig_aff, h_aff, wbits)

    return verify


def make_pair_sharded_aggregate_verify(mesh: Mesh, axis: str = "batch"):
    """Shard the PAIRS of one large accumulation across the mesh — the
    SURVEY §2.8/§5 "sequence scaling" axis.  One aggregate-verify
    (blst.rs:244-255: distinct messages, ONE signature) whose (pk_i, H_i)
    pairs spread over devices; each device Miller-loops its pair shard and
    multiplies its local GT partial, the partials combine with an fp12
    RING-reduction over ICI (the exact ring-attention accumulation shape —
    the GT product is associative), and the single final exponentiation
    runs replicated.

    Returns fn(pk_aff, h_aff, sig_aff) -> bool: pk/h carry the global pair
    count on the trailing axis; sig is the batch-1 aggregate signature,
    replicated.  Pair counts not divisible by the mesh size are padded up
    with duplicates of pair 0 plus a sharded pad mask — unlike the
    AND-reduce kernel, a padded pair's Miller factor would multiply into
    the single GT product and change the verdict, so padded lanes are
    selected to fp12 one before the local reduce."""
    pair_spec = batch_spec(2, axis=axis)

    def local_part(pk_aff, h_aff, sig_aff, pad_mask):
        ok_sub = jnp.all(P.g2_subgroup_check(sig_aff))
        f_local = PR.miller_loop(pk_aff, h_aff)
        one = PR._fp12_one_like_from_fp2(f_local[0][0])
        f_local = T.fp12_select(pad_mask, one, f_local)
        g_local = PR.gt_product(f_local)  # one fp12 partial per device
        # --- the ring: N-1 ppermute hops, each folding the neighbour's
        # partial into the accumulator (ICI traffic = one fp12 per hop) ---
        g = ring_reduce(g_local, T.fp12_mul, axis)
        # --- replicated epilogue: fold e(-G1, sig), final exp ----------
        neg_gen = _neg_gen_const()
        f_last = PR.miller_loop(neg_gen, sig_aff)
        total = T.fp12_mul(PR.gt_product(g), f_last)
        ok_pair = PR.final_exp_is_one(total)
        return jnp.reshape(ok_pair & ok_sub, ())

    sharded = shard_map(
        local_part,
        mesh=mesh,
        in_specs=(pair_spec, pair_spec, PS(), batch_spec(1, axis=axis)),
        out_specs=PS(),
    )
    jitted = jax.jit(sharded)
    n = int(mesh.devices.size)

    def aggregate_verify(pk_aff, h_aff, sig_aff):
        pairs = _trailing_extent(pk_aff)
        pad = (-pairs) % n
        if pad:
            pk_aff, h_aff = _pad_tail_cols((pk_aff, h_aff), pad)
        pad_mask = jnp.arange(pairs + pad) >= pairs
        return jitted(pk_aff, h_aff, sig_aff, pad_mask)

    return aggregate_verify
