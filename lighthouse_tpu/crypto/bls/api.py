"""Backend-generic BLS API — the analog of the reference's `bls` crate.

The reference exposes generic `TPublicKey`/`TSignature`/`TAggregateSignature`
traits instantiated per backend (blst, fake_crypto) at compile time
(reference: crypto/bls/src/lib.rs:84-139).  Here the same shape is a runtime
registry: `set_backend("python" | "fake" | "jax")`.  The host-side containers
(compressed bytes + decoded points) are shared; only the *verification engine*
differs — which is exactly the boundary the TPU backend needs (it consumes
marshaled signature sets, reference: consensus/state_processing/src/
per_block_processing/signature_sets.rs).

Semantics mirrored from the reference:
  * PublicKey deserialization rejects the point at infinity and runs
    key_validate (crypto/bls/src/generic_public_key.rs:14-15,70).
    ``PublicKey.from_bytes_unchecked`` defers the subgroup half of
    key_validate, as ``Signature.from_bytes`` defers its own check; the
    key carries the check with it, and every verification engine runs it
    (or has it run on the device) before the key counts.
  * `verify_signature_sets` draws nonzero 64-bit random weights per set,
    subgroup-checks signatures, rejects empty sets, aggregates each set's
    pubkeys, and performs one multi-pairing check
    (crypto/bls/src/impls/blst.rs:35-117).
  * `eth_fast_aggregate_verify` accepts the infinity signature with an empty
    pubkey list (the G2_POINT_AT_INFINITY special case in the spec).
"""

from __future__ import annotations

import secrets
from dataclasses import dataclass, field

from . import params
from .curve import (
    Fp,
    Fp2,
    G1_GENERATOR,
    affine_add,
    affine_mul,
    affine_neg,
    from_jacobian,
    g1_from_bytes,
    g1_subgroup_check,
    g1_to_bytes,
    g2_from_bytes,
    g2_subgroup_check,
    g2_to_bytes,
    jac_add,
    jac_mul,
    to_jacobian,
)
from .hash_to_curve import hash_to_g2
from .pairing import pairing_check

# ---------------------------------------------------------------------------
# Containers
# ---------------------------------------------------------------------------


class BlsError(ValueError):
    pass


class SecretKey:
    __slots__ = ("_sk",)

    def __init__(self, sk: int):
        if not 1 <= sk < params.R:
            raise BlsError("secret key out of range")
        self._sk = sk

    @classmethod
    def random(cls) -> "SecretKey":
        return cls(1 + secrets.randbelow(params.R - 1))

    @classmethod
    def from_bytes(cls, data: bytes) -> "SecretKey":
        if len(data) != params.SCALAR_BYTES:
            raise BlsError("secret key must be 32 bytes")
        v = int.from_bytes(data, "big")
        return cls(v)

    def to_bytes(self) -> bytes:
        return self._sk.to_bytes(params.SCALAR_BYTES, "big")

    def public_key(self) -> "PublicKey":
        return PublicKey(affine_mul(G1_GENERATOR, self._sk, Fp))

    def sign(self, msg: bytes) -> "Signature":
        h = hash_to_g2(msg)
        return Signature(affine_mul(h, self._sk, Fp2))

    @property
    def int_value(self) -> int:
        return self._sk


class PublicKey:
    """A non-infinity G1 point (decompressed).  Subgroup-checked, unless
    decoded by ``from_bytes_unchecked``: such a key holds no verdict
    until ``subgroup_check`` runs on the host or a batched device check
    records one (``record_subgroup_check``); the verdict, either way, is
    kept on the object."""

    __slots__ = ("point", "_in_g1")

    def __init__(self, point, subgroup_checked: bool = True):
        if point is None:
            # Reference rejects infinity pubkeys at deserialize
            # (generic_public_key.rs:14-15).
            raise BlsError("public key cannot be the point at infinity")
        self.point = point
        # True / False once checked, None while the check is pending
        self._in_g1 = True if subgroup_checked else None

    @classmethod
    def from_bytes(cls, data: bytes) -> "PublicKey":
        pt = g1_from_bytes(data, subgroup_check=True)
        return cls(pt)

    @classmethod
    def from_bytes_unchecked(cls, data: bytes) -> "PublicKey":
        """Decompress only (on-curve, not infinity); the subgroup check
        is left to whoever verifies with the key."""
        pt = g1_from_bytes(data, subgroup_check=False)
        return cls(pt, subgroup_checked=False)

    @property
    def subgroup_checked(self) -> bool:
        """Whether the key's subgroup verdict is known."""
        return self._in_g1 is not None

    def subgroup_check(self) -> bool:
        """The key lies in G1: the kept verdict, else the host check."""
        if self._in_g1 is None:
            self._in_g1 = g1_subgroup_check(self.point)
        return self._in_g1

    def record_subgroup_check(self, in_g1: bool) -> None:
        """Keep a verdict computed elsewhere (the device key check)."""
        self._in_g1 = bool(in_g1)

    def to_bytes(self) -> bytes:
        return g1_to_bytes(self.point)

    def __eq__(self, other):
        return isinstance(other, PublicKey) and self.point == other.point

    def __hash__(self):
        return hash(self.to_bytes())

    def __repr__(self):
        return f"PublicKey({self.to_bytes().hex()[:16]}…)"


class AggregatePublicKey:
    """Sum of pubkeys; may be infinity (matches TAggregatePublicKey)."""

    __slots__ = ("point",)

    def __init__(self, point):
        self.point = point

    @classmethod
    def aggregate(cls, pubkeys: list[PublicKey]) -> "AggregatePublicKey":
        if not pubkeys:
            raise BlsError("cannot aggregate an empty pubkey list")
        acc = to_jacobian(None, Fp)
        for pk in pubkeys:
            acc = jac_add(acc, to_jacobian(pk.point, Fp), Fp)
        return cls(from_jacobian(acc, Fp))


class Signature:
    """A G2 point or infinity.  Subgroup checking is deferred to verification
    time, as in the reference (blst.rs:71-81)."""

    __slots__ = ("point", "_subgroup_checked")

    def __init__(self, point, subgroup_checked: bool = True):
        self.point = point
        self._subgroup_checked = subgroup_checked

    @classmethod
    def infinity(cls) -> "Signature":
        return cls(None)

    @classmethod
    def from_bytes(cls, data: bytes) -> "Signature":
        # Decode without subgroup check (deferred), matching lazy signature
        # validation in the reference.
        pt = g2_from_bytes(data, subgroup_check=False)
        return cls(pt, subgroup_checked=False)

    def to_bytes(self) -> bytes:
        return g2_to_bytes(self.point)

    def is_infinity(self) -> bool:
        return self.point is None

    def subgroup_check(self) -> bool:
        if self._subgroup_checked:
            return True
        ok = self.point is None or g2_subgroup_check(self.point)
        if ok:
            self._subgroup_checked = True
        return ok

    def __eq__(self, other):
        return isinstance(other, Signature) and self.point == other.point

    def __repr__(self):
        return f"Signature({self.to_bytes().hex()[:16]}…)"


class AggregateSignature:
    __slots__ = ("signature",)

    def __init__(self, signature: Signature | None = None):
        self.signature = signature if signature is not None else Signature.infinity()

    @classmethod
    def aggregate(cls, signatures: list[Signature]) -> "AggregateSignature":
        if not signatures:
            raise BlsError("cannot aggregate an empty signature list")
        acc = to_jacobian(None, Fp2)
        checked = True
        for s in signatures:
            acc = jac_add(acc, to_jacobian(s.point, Fp2), Fp2)
            checked = checked and s._subgroup_checked
        # Subgroup-checkedness propagates only if every input was checked
        # (G2 is a subgroup, so sums of checked points stay inside it);
        # otherwise the deferred check must still run at verify time.
        return cls(Signature(from_jacobian(acc, Fp2), subgroup_checked=checked))

    def add_assign(self, sig: Signature) -> None:
        pt = affine_add(self.signature.point, sig.point, Fp2)
        checked = self.signature._subgroup_checked and sig._subgroup_checked
        self.signature = Signature(pt, subgroup_checked=checked)

    def to_bytes(self) -> bytes:
        return self.signature.to_bytes()

    @classmethod
    def from_bytes(cls, data: bytes) -> "AggregateSignature":
        return cls(Signature.from_bytes(data))


@dataclass
class SignatureSet:
    """One unit of batch verification: (signature, pubkeys, message).

    Mirrors GenericSignatureSet (reference: crypto/bls/src/
    generic_signature_set.rs:61-121): the signature is valid iff it verifies
    against the aggregate of `signing_keys` over `message`.
    """

    signature: Signature
    signing_keys: list[PublicKey]
    message: bytes  # raw message (for Ethereum: a 32-byte signing root)
    # whether some signing key was not known to lie in G1 when the set
    # was built (decoded unchecked, or checked and rejected): the one
    # flag a verifier reads to skip the per-key walk for every other set
    keys_unvalidated: bool = field(init=False, default=False, compare=False,
                                   repr=False)

    def __post_init__(self):
        self.keys_unvalidated = any(getattr(pk, "_in_g1", True) is not True
                                    for pk in self.signing_keys)

    def verify(self) -> bool:
        return get_backend().verify_signature_sets([self])

    def keys_valid(self) -> bool:
        """Every signing key lies in G1: at once when the set was built
        from checked keys, else key by key (``PublicKey.subgroup_check``,
        the host check for a key no one has checked yet)."""
        if not self.keys_unvalidated:
            return True
        if not all(pk.subgroup_check() for pk in self.signing_keys):
            return False
        self.keys_unvalidated = False
        return True


# ---------------------------------------------------------------------------
# Core verification engines
# ---------------------------------------------------------------------------


class PythonBackend:
    """CPU reference backend (pairing-based, pure Python)."""

    name = "python"

    def verify(self, pubkey: PublicKey, msg: bytes, sig: Signature) -> bool:
        if sig.point is None:
            return False
        if not sig.subgroup_check() or not pubkey.subgroup_check():
            return False
        h = hash_to_g2(msg)
        return pairing_check(
            [(affine_neg(G1_GENERATOR), sig.point), (pubkey.point, h)]
        )

    def aggregate_verify(
        self, pubkeys: list[PublicKey], msgs: list[bytes], sig: Signature
    ) -> bool:
        """Distinct-message aggregate verification (blst.rs:244-255)."""
        if not pubkeys or len(pubkeys) != len(msgs):
            return False
        if sig.point is None or not sig.subgroup_check():
            return False
        if not all(pk.subgroup_check() for pk in pubkeys):
            return False
        pairs = [(affine_neg(G1_GENERATOR), sig.point)]
        for pk, m in zip(pubkeys, msgs):
            pairs.append((pk.point, hash_to_g2(m)))
        return pairing_check(pairs)

    def fast_aggregate_verify(
        self, pubkeys: list[PublicKey], msg: bytes, sig: Signature
    ) -> bool:
        """Same-message aggregate verification (blst.rs:231-242)."""
        if not pubkeys:
            return False
        if sig.point is None or not sig.subgroup_check():
            return False
        if not all(pk.subgroup_check() for pk in pubkeys):
            return False
        agg = AggregatePublicKey.aggregate(pubkeys)
        if agg.point is None:
            return False
        h = hash_to_g2(msg)
        return pairing_check(
            [(affine_neg(G1_GENERATOR), sig.point), (agg.point, h)]
        )

    def verify_signature_sets(self, sets: list[SignatureSet]) -> bool:
        """Random-linear-combination multi-set verification
        (blst.rs:35-117; SURVEY.md §3.5)."""
        if not sets:
            return False
        pairs = []
        sig_acc = to_jacobian(None, Fp2)  # Σ r_i · sig_i
        for s in sets:
            # Nonzero 64-bit random weight (blst.rs:52-66).
            r = 0
            while r == 0:
                r = secrets.randbits(params.RAND_BITS)
            if s.signature.point is None:
                return False
            if not s.signature.subgroup_check():
                return False
            if not s.signing_keys:
                return False
            # key_validate for keys decoded unchecked (the reference's
            # blst validates every key before it aggregates)
            if not all(pk.subgroup_check() for pk in s.signing_keys):
                return False
            agg = AggregatePublicKey.aggregate(s.signing_keys)
            if agg.point is None:
                return False
            sig_acc = jac_add(
                sig_acc,
                jac_mul(to_jacobian(s.signature.point, Fp2), r, Fp2),
                Fp2,
            )
            pairs.append(
                (affine_mul(agg.point, r, Fp), hash_to_g2(s.message))
            )
        pairs.append((affine_neg(G1_GENERATOR), from_jacobian(sig_acc, Fp2)))
        return pairing_check(pairs)


class FakeBackend:
    """Always-valid backend for crypto-independent logic tests — the analog of
    fake_crypto (reference: crypto/bls/src/impls/fake_crypto.rs)."""

    name = "fake"

    def verify(self, pubkey, msg, sig) -> bool:
        return True

    def aggregate_verify(self, pubkeys, msgs, sig) -> bool:
        return True

    def fast_aggregate_verify(self, pubkeys, msg, sig) -> bool:
        return True

    def verify_signature_sets(self, sets) -> bool:
        return True


_BACKENDS: dict[str, object] = {}
_ACTIVE: list[object] = []


def register_backend(backend) -> None:
    _BACKENDS[backend.name] = backend


def set_backend(name: str) -> None:
    if name == "jax" and name not in _BACKENDS:
        # Lazy registration so importing the api never pulls in jax.
        from .jax_backend.backend import register as _register_jax

        _register_jax()
    if name not in _BACKENDS:
        raise KeyError(f"unknown BLS backend {name!r}; have {sorted(_BACKENDS)}")
    _ACTIVE[0] = _BACKENDS[name]


def get_backend():
    return _ACTIVE[0]


def cpu_backend() -> PythonBackend:
    """The always-available pure-Python engine, regardless of which backend
    is active — the degraded-mode fallback the CircuitBreaker routes to
    when the device backend is tripping."""
    return _BACKENDS["python"]


register_backend(PythonBackend())
register_backend(FakeBackend())
_ACTIVE.append(_BACKENDS["python"])


# ---------------------------------------------------------------------------
# Module-level convenience API (the `bls::` free functions of the reference)
# ---------------------------------------------------------------------------


def verify(pubkey: PublicKey, msg: bytes, sig: Signature) -> bool:
    return get_backend().verify(pubkey, msg, sig)


def aggregate_verify(pubkeys, msgs, sig) -> bool:
    return get_backend().aggregate_verify(pubkeys, msgs, sig)


def fast_aggregate_verify(pubkeys, msg, sig) -> bool:
    return get_backend().fast_aggregate_verify(pubkeys, msg, sig)


def eth_fast_aggregate_verify(pubkeys, msg, sig) -> bool:
    """Spec variant: infinity signature over zero pubkeys is valid
    (used by sync-committee verification)."""
    if not pubkeys and sig.is_infinity():
        return True
    return fast_aggregate_verify(pubkeys, msg, sig)


def verify_signature_sets(sets: list[SignatureSet]) -> bool:
    return get_backend().verify_signature_sets(sets)
