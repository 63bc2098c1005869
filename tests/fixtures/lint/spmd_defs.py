"""Seeded spmd-family registry: the ``spmd_defs`` audit config key
points here, replacing the live staged-program registry with twelve
tiny broken programs — two per theorem class — built from the bodies in
``spmd_bad.py`` (the donation shapes in that file are found by the AST
half of the family, which scans the corpus, not this registry).

Loaded by ``spmd_lint._load_defs`` via importlib, so sibling fixture
modules are loaded by path too (the corpus is not a package on
``sys.path``).
"""

import importlib.util
import os

from lighthouse_tpu.analysis.spmd_lint import SpmdProgram, trace_mesh
from lighthouse_tpu.parallel.mesh import shard_map

_HERE = os.path.dirname(os.path.abspath(__file__))
_REL = "tests/fixtures/lint"
_BAD = f"{_REL}/spmd_bad.py"

DECLARED_AXES = ("batch",)


def _load(stem):
    spec = importlib.util.spec_from_file_location(
        f"spmd_fixture_{stem}", os.path.join(_HERE, stem + ".py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ps(*parts):
    from jax.sharding import PartitionSpec as PS

    return PS(*parts)


def _mesh_prog(local, axes, in_specs, mk_args):
    def build():
        amesh = trace_mesh(axes)
        fn = shard_map(
            local, amesh, in_specs=in_specs, out_specs=_ps()
        )
        return fn, mk_args()
    return build


def _pad_prog(pad_fn, pad):
    def build():
        import jax.numpy as jnp

        return (lambda a: pad_fn(a, pad)), (jnp.zeros((2, 5), jnp.int32),)
    return build


def build_programs():
    import jax.numpy as jnp

    bad = _load("spmd_bad")
    b1 = (("batch", 2),)

    def vec4():
        return (jnp.ones((4,), jnp.int32),)

    def fvec4():
        return (jnp.ones((4,), jnp.float32),)

    def reg_slots():
        return (jnp.zeros((3, 8), jnp.uint32), jnp.zeros((4,), jnp.int32))

    return [
        SpmdProgram(
            "fixture_bad_axis_psum", _BAD,
            _mesh_prog(bad.bad_axis_psum, (("batch", 2), ("rows", 2)),
                       _ps("batch"), vec4),
            note="psum over an axis missing from the declared registry",
        ),
        SpmdProgram(
            "fixture_bad_axis_gather", _BAD,
            _mesh_prog(bad.bad_axis_gather, (("batch", 2), ("cols", 2)),
                       _ps("batch"), vec4),
            note="all_gather over an undeclared axis",
        ),
        SpmdProgram(
            "fixture_cond_psum_varying", _BAD,
            _mesh_prog(bad.cond_psum_varying, b1, _ps("batch"), vec4),
            note="psum under an axis_index-dependent conditional",
        ),
        SpmdProgram(
            "fixture_cond_gather_varying", _BAD,
            _mesh_prog(bad.cond_gather_varying, b1, _ps("batch"), fvec4),
            note="all_gather under a data-dependent (shard-varying) "
                 "conditional",
        ),
        SpmdProgram(
            "fixture_gather_unmasked", _BAD,
            _mesh_prog(bad.gather_unmasked, b1,
                       (_ps(None, "batch"), _ps("batch")), reg_slots),
            domains={1: (0, 7)},
            note="registry take without the out-of-shard mask",
        ),
        SpmdProgram(
            "fixture_gather_wrong_bound", _BAD,
            _mesh_prog(bad.gather_wrong_bound, b1,
                       (_ps(None, "batch"), _ps("batch")), reg_slots),
            domains={1: (0, 7)},
            note="mask bound off by two columns",
        ),
        SpmdProgram(
            "fixture_rep_axis_index_leak", _BAD,
            _mesh_prog(bad.rep_axis_index_leak, b1, _ps("batch"), vec4),
            note="axis_index leaks into an out_specs-replicated output",
        ),
        SpmdProgram(
            "fixture_rep_partial_ring", _BAD,
            _mesh_prog(bad.rep_partial_ring, (("batch", 4),),
                       _ps("batch"), vec4),
            note="ring fold one hop short of full coverage",
        ),
        SpmdProgram(
            "fixture_sum_combine", _BAD,
            _mesh_prog(bad.sum_combine_verdict, b1, _ps("batch"), vec4),
            note="verdict reduced with a sum (pad lanes double-count)",
        ),
        SpmdProgram(
            "fixture_prod_combine", _BAD,
            _mesh_prog(bad.prod_combine_verdict, b1, _ps("batch"), vec4),
            note="verdict reduced with a product",
        ),
        SpmdProgram(
            "fixture_pad_zero_fill", _BAD,
            _pad_prog(bad.pad_zero_fill, 3), kind="pad", n_real=5,
            note="zero-filled pad lanes are not duplicates",
        ),
        SpmdProgram(
            "fixture_pad_mean_fill", _BAD,
            _pad_prog(bad.pad_mean_fill, 3), kind="pad", n_real=5,
            note="mean-filled pad lanes lose column provenance",
        ),
    ]
