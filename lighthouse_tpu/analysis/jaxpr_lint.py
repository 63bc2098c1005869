"""Jaxpr hygiene: program-budget walk, zero-dim guard, host-sync lint.

This module is the single home for the device-code health checks that
previously lived in two places:

* the ≤6-distinct-chain-program Mosaic compile budget walk from
  ``tools/dispatch_audit.py`` (that tool is now a thin wrapper);
* the zero-sized-vector abstract-eval guard from ``test_pallas_fp.py``
  (interpret mode tolerates zero-row intermediates; real Mosaic lowering
  rejects them — the i=25 ``_wide_square`` bug class).

Plus one new *AST-level* family that needs no tracing: **host-sync
lint** over the jax_backend dispatch hot path.  ``dispatch`` must stay
non-blocking (the PipelinedVerifier overlaps marshal workers with device
execution), so calls that force a device↔host round-trip —
``block_until_ready``, ``np.asarray`` on device values, ``.item()``,
``float()``/``int()`` on non-constant values — are banned inside the
registered hot-path functions.

The jaxpr helpers import jax lazily so the static audit itself never
pays (or requires) a jax import.
"""

from __future__ import annotations

import ast

from .report import Violation

DEFAULT_CHAIN_BUDGET = 6

# file -> functions whose bodies must not host-sync.  dispatch and every
# jitted kernel composition on the verify path.
DEFAULT_HOT_PATH = {
    "lighthouse_tpu/crypto/bls/jax_backend/backend.py": (
        "dispatch",
        "_verify_kernel",
        "_verify_kernel_h2c",
        "_aggregate_verify_kernel",
        "_epoch_verify_kernel",
        "_segment_aggregate_g1",
        "_tree_reduce_g2",
    ),
}

_HOST_SYNC_ATTRS = {"block_until_ready", "item"}
_NUMPY_ALIASES = {"np", "numpy", "onp"}
_SCALARIZERS = {"float", "int", "bool"}


# ---------------------------------------------------------------------------
# Jaxpr walk (compile-budget audit) — used by tools/dispatch_audit.py
# ---------------------------------------------------------------------------


def iter_jaxprs(obj):
    """Yield every Jaxpr reachable from a params value (ClosedJaxpr,
    Jaxpr, or containers thereof)."""
    import jax.extend.core as jcore

    if isinstance(obj, jcore.ClosedJaxpr):
        yield obj.jaxpr
    elif isinstance(obj, jcore.Jaxpr):
        yield obj
    elif isinstance(obj, (list, tuple)):
        for item in obj:
            yield from iter_jaxprs(item)


def pallas_fingerprint(eqn):
    """Identity of one staged Pallas program: kernel name + source line
    (``name_and_src_info`` reprs as ``_mont_kernel at .../pallas_fp.py:135``),
    operand avals, grid.  Two eqns with equal fingerprints lower to one
    Mosaic program (the compile cache keys on the same data)."""
    params = eqn.params
    nsi = str(params.get("name_and_src_info", params.get("name", "?")))
    gm = params.get("grid_mapping")
    grid = tuple(getattr(gm, "grid", ()) or ())
    avals = tuple(str(v.aval) for v in eqn.invars)
    return (nsi, grid, avals)


def _walk(jaxpr, seen_jaxprs, programs, counts):
    if id(jaxpr) in seen_jaxprs:
        return
    seen_jaxprs.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            fp = pallas_fingerprint(eqn)
            programs.setdefault(fp, 0)
            programs[fp] += 1
            counts[0] += 1
        for val in eqn.params.values():
            for sub in iter_jaxprs(val):
                _walk(sub, seen_jaxprs, programs, counts)


def audit_jaxpr(closed):
    """(distinct pallas program fingerprints -> eqn count, total static
    pallas_call equation count) for a ClosedJaxpr."""
    programs: dict[tuple, int] = {}
    counts = [0]
    _walk(closed.jaxpr, set(), programs, counts)
    return programs, counts[0]


def is_chain_program(fp) -> bool:
    """Chain programs are the megachain kernels (pallas_fp.py); the
    budget bounds how many DISTINCT ones a composition stages."""
    return "megachain_kernel" in fp[0]


def chain_programs(programs) -> list:
    return [fp for fp in programs if is_chain_program(fp)]


# ---------------------------------------------------------------------------
# Zero-sized-vector abstract-eval guard (the i=25 _wide_square bug class)
# ---------------------------------------------------------------------------


def collect_zero_dim_avals(jaxpr, seen, bad):
    """Walk every equation of every staged sub-jaxpr, appending a
    description for each zero-sized operand/result aval."""
    if id(jaxpr) in seen:
        return
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape and 0 in shape:
                bad.append(f"{eqn.primitive.name}: {aval}")
        for val in eqn.params.values():
            for sub in iter_jaxprs(val):
                collect_zero_dim_avals(sub, seen, bad)


def zero_dim_avals(fn, *args) -> list:
    """Trace `fn` (abstract eval only — nothing executes, nothing is
    Mosaic-compiled) and return descriptions of any zero-sized shapes."""
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    bad: list = []
    collect_zero_dim_avals(closed.jaxpr, set(), bad)
    return bad


def assert_no_zero_dims(fn, *args):
    bad = zero_dim_avals(fn, *args)
    assert not bad, (
        "zero-sized vector shapes staged (Mosaic rejects these even "
        "though interpret mode tolerates them): " + "; ".join(bad[:5])
    )


# ---------------------------------------------------------------------------
# Host-sync lint (AST-only, runs in the static audit)
# ---------------------------------------------------------------------------


def _host_sync_calls(fn_node):
    """(line, description) for every host-syncing call in a function."""
    out = []
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute):
            if f.attr in _HOST_SYNC_ATTRS:
                out.append((node.lineno, f".{f.attr}() forces a device sync"))
            elif (
                f.attr == "asarray"
                and isinstance(f.value, ast.Name)
                and f.value.id in _NUMPY_ALIASES
            ):
                out.append((
                    node.lineno,
                    f"{f.value.id}.asarray() copies device values to host",
                ))
        elif isinstance(f, ast.Name) and f.id in _SCALARIZERS:
            if node.args and not isinstance(node.args[0], ast.Constant):
                out.append((
                    node.lineno,
                    f"{f.id}() on a non-constant value scalarizes "
                    f"(host sync if the value is traced/on-device)",
                ))
    return out


def host_sync_violations(files, hot_path=None) -> list[Violation]:
    """files: iterable of (display_path, source).  hot_path: mapping of
    display path -> function names whose bodies must stay sync-free."""
    hot_path = dict(DEFAULT_HOT_PATH if hot_path is None else hot_path)
    files = dict(files)
    out = []
    for path, fn_names in sorted(hot_path.items()):
        src = files.get(path)
        if src is None:
            out.append(Violation(
                rule="jaxpr-hygiene", path=path, line=0, symbol=path,
                message="hot-path file not found in scan set "
                        "(hot-path registry drift)",
            ))
            continue
        tree = ast.parse(src, filename=path)
        found = set()
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name not in fn_names:
                continue
            found.add(node.name)
            for line, why in _host_sync_calls(node):
                out.append(Violation(
                    rule="jaxpr-hygiene", path=path, line=line,
                    symbol=node.name,
                    message=f"host-sync call in dispatch hot path: {why}",
                ))
        for missing in sorted(set(fn_names) - found):
            out.append(Violation(
                rule="jaxpr-hygiene", path=path, line=0, symbol=missing,
                message=(
                    f"hot-path function {missing!r} not found "
                    f"(hot-path registry drift)"
                ),
            ))
    return out


def run(files, hot_path=None) -> list[Violation]:
    return host_sync_violations(files, hot_path)
