"""Generic mesh / data-parallel utilities — the framework's SPMD toolkit.

The reference's parallelism inventory (SURVEY §2.8) has one distributed
axis that matters on TPU: data-parallel batch sharding with an
associative combine (the rayon chunk-AND-reduce of
block_signature_verifier.rs:396-405).  These helpers are the generic
form used by the crypto multichip path (crypto/bls/jax_backend/
multichip.py) and available to any batched workload (the epoch pipeline
at multi-host registry scale, KZG blob batches):

* ``make_mesh(n)`` — a 1-D device mesh over the first n devices,
* ``batch_spec(ndim, axis_pos)`` — PartitionSpec splitting one axis,
* ``dp_shard_map(fn, mesh)`` — shard_map a local-compute function over
  the batch axis with everything-sharded in / replicated out,
* ``allgather_tree(tree, axis)`` — gather a pytree's trailing axis
  across the mesh (the tiny ICI combine),
* ``and_reduce(ok, axis)`` — the global conjunction,
* ``shard_map`` — the one shard_map call every mesh program in the
  repo routes through.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as PS

BATCH_AXIS = "batch"


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma`` off: every Horner/Montgomery
    scan in fp.py initializes its carry from a replicated constant while
    the loop body mixes in batch-varying limbs, which the vma checker
    rejects (see the scan-carry note in multichip.make_verify_sharded —
    correctness is pinned by the shard-vs-single byte-equality tests
    instead)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(n_devices: int | None = None, axis: str = BATCH_AXIS) -> Mesh:
    """1-D mesh over the first n devices (all by default)."""
    devices = jax.devices()
    n = len(devices) if n_devices is None else n_devices
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]), (axis,))


def batch_spec(ndim: int, axis_pos: int = -1, axis: str = BATCH_AXIS) -> PS:
    """PartitionSpec for an ndim array sharded on one axis; scalars
    (ndim 0) are replicated."""
    if ndim == 0:
        return PS()
    pos = axis_pos % ndim
    return PS(*[axis if i == pos else None for i in range(ndim)])


def allgather_tree(tree, axis: str = BATCH_AXIS):
    """All-gather every leaf's trailing axis across the mesh (tiled) —
    the ICI combine for small per-device partials."""
    return jax.tree.map(
        lambda a: jax.lax.all_gather(a, axis, axis=a.ndim - 1, tiled=True),
        tree,
    )


def ring_reduce(tree, combine, axis: str = BATCH_AXIS):
    """Ring-reduction of a per-device pytree with an arbitrary associative
    ``combine(acc, incoming)`` — the accumulation pattern ring attention
    uses for softmax partials (SURVEY §2.8/§5 "sequence scaling"): N-1
    ppermute hops around the ring, each device folding its neighbour's
    partial into its accumulator; after the loop every device holds the
    full product.  For non-commutative-friendly shapes prefer this over
    all_gather when the partials are large (one hop in flight instead of
    an N-way gather).

    Replication of the result is *proved*, not assumed: jax's own
    check_rep/check_vma cannot see that N-1 uniform-ring hops of a
    commutative fold cover every shard, so the spmd audit family
    (``analysis/spmd_lint.py``, ``ring_reduce_w*`` programs) tracks the
    offset set through the ppermute chain and fails the audit if the
    fold ever comes up a hop short."""
    try:
        n = jax.lax.axis_size(axis)  # static: the mesh extent
    except AttributeError:
        # older jax (<0.5) has no lax.axis_size; psum of a Python
        # literal over a named axis folds to a static int
        n = jax.lax.psum(1, axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(t):
        return jax.tree.map(
            lambda a: jax.lax.ppermute(a, axis, perm=perm), t
        )

    acc = tree
    incoming = tree
    for _ in range(n - 1):
        incoming = hop(incoming)
        acc = combine(acc, incoming)
    return acc


def and_reduce(ok, axis: str = BATCH_AXIS):
    """Global conjunction of per-device booleans (the AND-reduce of the
    reference's chunked batch verification)."""
    return jnp.all(jax.lax.all_gather(ok, axis))


def dp_shard_map(local_fn, mesh: Mesh, axis: str = BATCH_AXIS,
                 trailing_batch: bool = True):
    """shard_map ``local_fn`` data-parallel: every input pytree leaf is
    split on its TRAILING axis (the framework's batch convention: limb
    arrays are (26, B), bit arrays (64, B)); outputs are replicated —
    local_fn must end with its own collective combine (allgather_tree /
    and_reduce) so every device holds the full result."""

    def spec_for(x):
        return batch_spec(jnp.ndim(x), -1 if trailing_batch else 0, axis)

    def wrapped(*args):
        in_specs = jax.tree.map(spec_for, args)
        return shard_map(
            local_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=PS(),
        )(*args)

    return wrapped
