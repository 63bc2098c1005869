"""Plain BLS12-381 in pure Python: the benchmark's own yardstick.

Independent of the system under test (it imports nothing of
``lighthouse_tpu``).  It serves two callers:

* the traffic generator, which signs with it (``sign_scalar``, the
  precomputed multiples in ``G2Multiples``) and compresses points to the
  wire format;
* the reference verifier, which decides what every signature set's
  verdict must be: decompress and validate each key and the signature,
  aggregate the keys, hash the message to G2, and check
  ``e(pk, H(m)) * e(-G1, sig) == 1`` with a plain Miller loop and a final
  exponentiation by ``(p^12 - 1) / r``.

Every constant is a published value: the curve (IETF BLS signature draft,
section 4.2.1), the generators, the hash-to-curve suite
``BLS12381G2_XMD:SHA-256_SSWU_RO_`` and its 3-isogeny (RFC 9380, section 8.8.2
and appendix E.3), the Ethereum proof-of-possession DST, and the ZCash point
encoding.  ``benchmark/tests/test_bls_ref.py`` checks the code against the
published RFC 9380 vectors and the Ethereum signing vector.

Representation: Fp elements are ints mod ``P``; Fp2 elements are
``(c0, c1)`` meaning ``c0 + c1*i`` with ``i^2 = -1``; G1 and G2 points are
Jacobian ``(X, Y, Z)`` with ``Z == 0`` the point at infinity, or affine
``(x, y)`` with ``None`` for infinity.
"""

from __future__ import annotations

import hashlib

P = 0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB
R = 0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001
#: |z| for the BLS parameter z = -0xd201000000010000
Z_ABS = 0xD201000000010000

G1_X = 0x17F1D3A73197D7942695638C4FA9AC0FC3688C4F9774B905A14E3A3F171BAC586C55E83FF97A1AEFFB3AF00ADB22C6BB
G1_Y = 0x08B3F481E3AAA0F1A09E30ED741D8AE4FCF5E095D5D00AF600DB18CB2C04B3EDD03CC744A2888AE40CAA232946C5E7E1
G2_X = (0x024AA2B2F08F0A91260805272DC51051C6E47AD4FA403B02B4510B647AE3D1770BAC0326A805BBEFD48056C8C121BDB8,
        0x13E02B6052719F607DACD3A088274F65596BD0D09920B61AB5DA61BBDC7F5049334CF11213945D57E5AC7D055D042B7E)
G2_Y = (0x0CE5D527727D6E118CC9CDC6DA2E351AADFD9BAA8CBDD3A76D429A695160D12C923AC9CC3BACA289E193548608B82801,
        0x0606C4A02EA734CC32ACD2B02BC28B99CB3E287E85A763AF267492AB572E99AB3F370D275CEC1DA1AAA9075FF05F79BE)

#: the cofactor multiple that clears G2 (RFC 9380, section 8.8.2)
H_EFF_G2 = 0xBC69F08F2EE75B3584C6A0EA91B352888E2A8E9145AD7689986FF031508FFE1329C2F178731DB956D82BF015D1212B02EC0EC69D7477C1AE954CBC06689F6A359894C0ADEBBF6B4E8020005AAA95551

#: Ethereum's proof-of-possession ciphersuite tag
DST_POP = b"BLS_SIG_BLS12381G2_XMD:SHA-256_SSWU_RO_POP_"

HALF_P = (P - 1) // 2

# ---------------------------------------------------------------------------
# Fp2
# ---------------------------------------------------------------------------

F2_ZERO = (0, 0)
F2_ONE = (1, 0)


def f2_add(a, b):
    return ((a[0] + b[0]) % P, (a[1] + b[1]) % P)


def f2_sub(a, b):
    return ((a[0] - b[0]) % P, (a[1] - b[1]) % P)


def f2_neg(a):
    return (-a[0] % P, -a[1] % P)


def f2_mul(a, b):
    t0 = a[0] * b[0]
    t1 = a[1] * b[1]
    return ((t0 - t1) % P, ((a[0] + a[1]) * (b[0] + b[1]) - t0 - t1) % P)


def f2_sqr(a):
    return ((a[0] + a[1]) * (a[0] - a[1]) % P, 2 * a[0] * a[1] % P)


def f2_scale(a, k):
    return (a[0] * k % P, a[1] * k % P)


def f2_inv(a):
    n = pow((a[0] * a[0] + a[1] * a[1]) % P, -1, P)
    return (a[0] * n % P, -a[1] * n % P)


def f2_mul_xi(a):
    """Multiply by xi = 1 + i."""
    return ((a[0] - a[1]) % P, (a[0] + a[1]) % P)


def f2_is_square(a) -> bool:
    n = (a[0] * a[0] + a[1] * a[1]) % P
    return n == 0 or pow(n, HALF_P, P) == 1


def fp_sqrt(a: int):
    """A square root of ``a`` in Fp, or None (p = 3 mod 4)."""
    s = pow(a, (P + 1) // 4, P)
    return s if s * s % P == a % P else None


def f2_sqrt(a):
    """A square root of ``a`` in Fp2, or None."""
    a0, a1 = a[0] % P, a[1] % P
    if a1 == 0:
        s = fp_sqrt(a0)
        if s is not None:
            return (s, 0)
        s = fp_sqrt(-a0 % P)
        return None if s is None else (0, s)
    alpha = fp_sqrt((a0 * a0 + a1 * a1) % P)
    if alpha is None:
        return None
    inv2 = (P + 1) // 2
    delta = (a0 + alpha) * inv2 % P
    x0 = fp_sqrt(delta)
    if x0 is None:
        delta = (a0 - alpha) * inv2 % P
        x0 = fp_sqrt(delta)
        if x0 is None:
            return None
    x1 = a1 * pow(2 * x0, -1, P) % P
    root = (x0, x1)
    return root if f2_sqr(root) == (a0, a1) else None


def f2_sgn0(a) -> int:
    sign_0 = a[0] % 2
    zero_0 = a[0] == 0
    sign_1 = a[1] % 2
    return sign_0 | (zero_0 and sign_1)


# ---------------------------------------------------------------------------
# Fp6 = Fp2[v] / (v^3 - xi), Fp12 = Fp6[w] / (w^2 - v)
# ---------------------------------------------------------------------------

F6_ZERO = (F2_ZERO, F2_ZERO, F2_ZERO)
F6_ONE = (F2_ONE, F2_ZERO, F2_ZERO)
F12_ONE = (F6_ONE, F6_ZERO)


def f6_add(a, b):
    return (f2_add(a[0], b[0]), f2_add(a[1], b[1]), f2_add(a[2], b[2]))


def f6_sub(a, b):
    return (f2_sub(a[0], b[0]), f2_sub(a[1], b[1]), f2_sub(a[2], b[2]))


def f6_neg(a):
    return (f2_neg(a[0]), f2_neg(a[1]), f2_neg(a[2]))


def f6_mul(a, b):
    a0, a1, a2 = a
    b0, b1, b2 = b
    t0 = f2_mul(a0, b0)
    t1 = f2_mul(a1, b1)
    t2 = f2_mul(a2, b2)
    c0 = f2_add(t0, f2_mul_xi(f2_sub(f2_sub(
        f2_mul(f2_add(a1, a2), f2_add(b1, b2)), t1), t2)))
    c1 = f2_add(f2_sub(f2_sub(
        f2_mul(f2_add(a0, a1), f2_add(b0, b1)), t0), t1), f2_mul_xi(t2))
    c2 = f2_add(f2_sub(f2_sub(
        f2_mul(f2_add(a0, a2), f2_add(b0, b2)), t0), t2), t1)
    return (c0, c1, c2)


def f6_mul_v(a):
    return (f2_mul_xi(a[2]), a[0], a[1])


def f6_inv(a):
    c0, c1, c2 = a
    t0 = f2_sub(f2_sqr(c0), f2_mul_xi(f2_mul(c1, c2)))
    t1 = f2_sub(f2_mul_xi(f2_sqr(c2)), f2_mul(c0, c1))
    t2 = f2_sub(f2_sqr(c1), f2_mul(c0, c2))
    den = f2_add(f2_mul(c0, t0),
                 f2_mul_xi(f2_add(f2_mul(c2, t1), f2_mul(c1, t2))))
    inv = f2_inv(den)
    return (f2_mul(t0, inv), f2_mul(t1, inv), f2_mul(t2, inv))


def f12_mul(a, b):
    t0 = f6_mul(a[0], b[0])
    t1 = f6_mul(a[1], b[1])
    c1 = f6_sub(f6_sub(f6_mul(f6_add(a[0], a[1]), f6_add(b[0], b[1])), t0), t1)
    return (f6_add(t0, f6_mul_v(t1)), c1)


def f12_sqr(a):
    a0, a1 = a
    t = f6_mul(a0, a1)
    c0 = f6_sub(f6_sub(f6_mul(f6_add(a0, a1), f6_add(a0, f6_mul_v(a1))), t),
                f6_mul_v(t))
    return (c0, f6_add(t, t))


def f12_conj(a):
    return (a[0], f6_neg(a[1]))


def f12_inv(a):
    a0, a1 = a
    den = f6_inv(f6_sub(f6_mul(a0, a0), f6_mul_v(f6_mul(a1, a1))))
    return (f6_mul(a0, den), f6_neg(f6_mul(a1, den)))


def f12_pow(a, e: int):
    out = F12_ONE
    for bit in bin(e)[2:]:
        out = f12_sqr(out)
        if bit == "1":
            out = f12_mul(out, a)
    return out


# ---------------------------------------------------------------------------
# Curve arithmetic: G1 over Fp (y^2 = x^3 + 4), G2 over Fp2 (y^2 = x^3 + 4(1+i))
# ---------------------------------------------------------------------------

B1 = 4
B2 = (4, 4)


def g1_double(pt):
    X, Y, Z = pt
    if Z == 0 or Y == 0:
        return (1, 1, 0)
    A = X * X % P
    B = Y * Y % P
    C = B * B % P
    D = 2 * ((X + B) * (X + B) - A - C) % P
    E = 3 * A % P
    X3 = (E * E - 2 * D) % P
    return (X3, (E * (D - X3) - 8 * C) % P, 2 * Y * Z % P)


def g1_add(p1, p2):
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    if Z1 == 0:
        return p2
    if Z2 == 0:
        return p1
    Z1Z1 = Z1 * Z1 % P
    Z2Z2 = Z2 * Z2 % P
    U1 = X1 * Z2Z2 % P
    U2 = X2 * Z1Z1 % P
    S1 = Y1 * Z2 * Z2Z2 % P
    S2 = Y2 * Z1 * Z1Z1 % P
    H = (U2 - U1) % P
    r = 2 * (S2 - S1) % P
    if H == 0:
        return g1_double(p1) if r == 0 else (1, 1, 0)
    I = 4 * H * H % P
    J = H * I % P
    V = U1 * I % P
    X3 = (r * r - J - 2 * V) % P
    Y3 = (r * (V - X3) - 2 * S1 * J) % P
    Z3 = ((Z1 + Z2) * (Z1 + Z2) - Z1Z1 - Z2Z2) * H % P
    return (X3, Y3, Z3)


def g1_mul(pt, k: int):
    out = (1, 1, 0)
    for bit in bin(k)[2:] if k > 0 else "":
        out = g1_double(out)
        if bit == "1":
            out = g1_add(out, pt)
    return out


def g1_affine(pt):
    X, Y, Z = pt
    if Z == 0:
        return None
    zi = pow(Z, -1, P)
    zi2 = zi * zi % P
    return (X * zi2 % P, Y * zi2 * zi % P)


def g1_jac(aff):
    return (1, 1, 0) if aff is None else (aff[0], aff[1], 1)


def g2_double(pt):
    X, Y, Z = pt
    if Z == F2_ZERO or Y == F2_ZERO:
        return (F2_ONE, F2_ONE, F2_ZERO)
    A = f2_sqr(X)
    B = f2_sqr(Y)
    C = f2_sqr(B)
    D = f2_scale(f2_sub(f2_sub(f2_sqr(f2_add(X, B)), A), C), 2)
    E = f2_scale(A, 3)
    X3 = f2_sub(f2_sqr(E), f2_scale(D, 2))
    Y3 = f2_sub(f2_mul(E, f2_sub(D, X3)), f2_scale(C, 8))
    return (X3, Y3, f2_scale(f2_mul(Y, Z), 2))


def g2_add(p1, p2):
    X1, Y1, Z1 = p1
    X2, Y2, Z2 = p2
    if Z1 == F2_ZERO:
        return p2
    if Z2 == F2_ZERO:
        return p1
    Z1Z1 = f2_sqr(Z1)
    Z2Z2 = f2_sqr(Z2)
    U1 = f2_mul(X1, Z2Z2)
    U2 = f2_mul(X2, Z1Z1)
    S1 = f2_mul(f2_mul(Y1, Z2), Z2Z2)
    S2 = f2_mul(f2_mul(Y2, Z1), Z1Z1)
    H = f2_sub(U2, U1)
    r = f2_scale(f2_sub(S2, S1), 2)
    if H == F2_ZERO:
        return g2_double(p1) if r == F2_ZERO else (F2_ONE, F2_ONE, F2_ZERO)
    I = f2_scale(f2_sqr(H), 4)
    J = f2_mul(H, I)
    V = f2_mul(U1, I)
    X3 = f2_sub(f2_sub(f2_sqr(r), J), f2_scale(V, 2))
    Y3 = f2_sub(f2_mul(r, f2_sub(V, X3)), f2_scale(f2_mul(S1, J), 2))
    Z3 = f2_mul(f2_sub(f2_sub(f2_sqr(f2_add(Z1, Z2)), Z1Z1), Z2Z2), H)
    return (X3, Y3, Z3)


def g2_neg(pt):
    return (pt[0], f2_neg(pt[1]), pt[2])


def g2_mul(pt, k: int):
    out = (F2_ONE, F2_ONE, F2_ZERO)
    for bit in bin(k)[2:] if k > 0 else "":
        out = g2_double(out)
        if bit == "1":
            out = g2_add(out, pt)
    return out


def g2_affine(pt):
    X, Y, Z = pt
    if Z == F2_ZERO:
        return None
    zi = f2_inv(Z)
    zi2 = f2_sqr(zi)
    return (f2_mul(X, zi2), f2_mul(f2_mul(Y, zi2), zi))


def g2_jac(aff):
    return (F2_ONE, F2_ONE, F2_ZERO) if aff is None else (aff[0], aff[1], F2_ONE)


G1_GEN = (G1_X, G1_Y, 1)
G2_GEN = (G2_X, G2_Y, F2_ONE)


# ---------------------------------------------------------------------------
# ZCash compressed encoding (48-byte G1, 96-byte G2)
# ---------------------------------------------------------------------------


def g1_compress(aff) -> bytes:
    if aff is None:
        return bytes([0xC0]) + bytes(47)
    x, y = aff
    out = bytearray(x.to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if y > HALF_P else 0)
    return bytes(out)


def g2_compress(aff) -> bytes:
    if aff is None:
        return bytes([0xC0]) + bytes(95)
    x, y = aff
    big = y[1] > HALF_P if y[1] != 0 else y[0] > HALF_P
    out = bytearray(x[1].to_bytes(48, "big") + x[0].to_bytes(48, "big"))
    out[0] |= 0x80 | (0x20 if big else 0)
    return bytes(out)


class DecodeError(ValueError):
    pass


def _flags(data: bytes, size: int):
    if len(data) != size:
        raise DecodeError(f"expected {size} bytes, got {len(data)}")
    c, inf, s = data[0] >> 7 & 1, data[0] >> 6 & 1, data[0] >> 5 & 1
    if not c:
        raise DecodeError("not compressed")
    body = bytes([data[0] & 0x1F]) + data[1:]
    if inf:
        if s or any(body):
            raise DecodeError("bad infinity encoding")
        return None, s
    return body, s


def g1_decompress(data: bytes):
    """Affine G1 point (None for infinity); raises DecodeError when the
    bytes name no point of the curve or one outside the subgroup."""
    body, s = _flags(data, 48)
    if body is None:
        return None
    x = int.from_bytes(body, "big")
    if x >= P:
        raise DecodeError("x >= p")
    y = fp_sqrt((x * x * x + B1) % P)
    if y is None:
        raise DecodeError("not on the curve")
    if (y > HALF_P) != bool(s):
        y = P - y
    if g1_mul((x, y, 1), R)[2] != 0:
        raise DecodeError("not in the subgroup")
    return (x, y)


def g2_decompress(data: bytes):
    """Affine G2 point (None for infinity); raises DecodeError as for G1."""
    body, s = _flags(data, 96)
    if body is None:
        return None
    x1 = int.from_bytes(body[:48], "big")
    x0 = int.from_bytes(body[48:], "big")
    if x0 >= P or x1 >= P:
        raise DecodeError("x >= p")
    x = (x0, x1)
    y = f2_sqrt(f2_add(f2_mul(f2_sqr(x), x), B2))
    if y is None:
        raise DecodeError("not on the curve")
    big = y[1] > HALF_P if y[1] != 0 else y[0] > HALF_P
    if big != bool(s):
        y = f2_neg(y)
    if g2_mul((x, y, F2_ONE), R)[2] != F2_ZERO:
        raise DecodeError("not in the subgroup")
    return (x, y)


# ---------------------------------------------------------------------------
# hash_to_curve: BLS12381G2_XMD:SHA-256_SSWU_RO_ (RFC 9380)
# ---------------------------------------------------------------------------


def expand_message_xmd(msg: bytes, dst: bytes, len_in_bytes: int) -> bytes:
    ell = (len_in_bytes + 31) // 32
    if ell > 255 or len(dst) > 255:
        raise ValueError("expand_message_xmd: input too long")
    dst_prime = dst + bytes([len(dst)])
    msg_prime = (bytes(64) + msg + len_in_bytes.to_bytes(2, "big")
                 + b"\x00" + dst_prime)
    b0 = hashlib.sha256(msg_prime).digest()
    b = [hashlib.sha256(b0 + b"\x01" + dst_prime).digest()]
    for i in range(2, ell + 1):
        mixed = bytes(x ^ y for x, y in zip(b0, b[-1]))
        b.append(hashlib.sha256(mixed + bytes([i]) + dst_prime).digest())
    return b"".join(b)[:len_in_bytes]


def hash_to_field_fp2(msg: bytes, count: int, dst: bytes):
    L = 64
    uniform = expand_message_xmd(msg, dst, count * 2 * L)
    out = []
    for i in range(count):
        e = [int.from_bytes(uniform[L * (j + 2 * i):L * (j + 2 * i + 1)], "big") % P
             for j in range(2)]
        out.append((e[0], e[1]))
    return out


# E2': y^2 = x^3 + A' x + B', isogenous to E2 (RFC 9380, section 8.8.2)
ISO_A = (0, 240)
ISO_B = (1012, 1012)
SSWU_Z = (-2 % P, -1 % P)

# 3-isogeny map E2' -> E2 (RFC 9380, appendix E.3), lowest degree first
ISO_X_NUM = [
    (0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6,
     0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97D6),
    (0,
     0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71A),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71E,
     0x08AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38D),
    (0x171D6541FA38CCFAED6DEA691F5FB614CB14B4E7F4E810AA22D6108F142B85757098E38D0F671C7188E2AAAAAAAA5ED1,
     0),
]
ISO_X_DEN = [
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA63),
    (0x0C,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA9F),
    (1, 0),
]
ISO_Y_NUM = [
    (0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706,
     0x1530477C7AB4113B59A4C18B076D11930F7DA5D4A07F649BF54439D87D27E500FC8C25EBF8C92F6812CFC71C71C6D706),
    (0,
     0x05C759507E8E333EBB5B7A9A47D7ED8532C52D39FD3A042A88B58423C50AE15D5C2638E343D9C71C6238AAAAAAAA97BE),
    (0x11560BF17BAA99BC32126FCED787C88F984F87ADF7AE0C7F9A208C6B4F20A4181472AAA9CB8D555526A9FFFFFFFFC71C,
     0x08AB05F8BDD54CDE190937E76BC3E447CC27C3D6FBD7063FCD104635A790520C0A395554E5C6AAAA9354FFFFFFFFE38F),
    (0x124C9AD43B6CF79BFBF7043DE3811AD0761B0F37A1E26286B0E977C69AA274524E79097A56DC4BD9E1B371C71C718B10,
     0),
]
ISO_Y_DEN = [
    (0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA8FB),
    (0,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFA9D3),
    (0x12,
     0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAA99),
    (1, 0),
]


def _poly(coeffs, x):
    acc = F2_ZERO
    for c in reversed(coeffs):
        acc = f2_add(f2_mul(acc, x), c)
    return acc


def map_to_curve_sswu(u):
    """Simplified SWU onto E2' (RFC 9380, section 6.6.2), affine."""
    u2 = f2_sqr(u)
    zu2 = f2_mul(SSWU_Z, u2)
    tv = f2_add(f2_sqr(zu2), zu2)
    if tv == F2_ZERO:
        x1 = f2_mul(ISO_B, f2_inv(f2_mul(SSWU_Z, ISO_A)))
    else:
        x1 = f2_mul(f2_mul(f2_neg(ISO_B), f2_inv(ISO_A)), f2_add(F2_ONE, f2_inv(tv)))
    gx1 = f2_add(f2_add(f2_mul(f2_sqr(x1), x1), f2_mul(ISO_A, x1)), ISO_B)
    if f2_is_square(gx1):
        x, y = x1, f2_sqrt(gx1)
    else:
        x = f2_mul(zu2, x1)
        gx2 = f2_add(f2_add(f2_mul(f2_sqr(x), x), f2_mul(ISO_A, x)), ISO_B)
        y = f2_sqrt(gx2)
    if f2_sgn0(u) != f2_sgn0(y):
        y = f2_neg(y)
    return (x, y)


def iso_map(pt):
    x, y = pt
    xn, xd = _poly(ISO_X_NUM, x), _poly(ISO_X_DEN, x)
    yn, yd = _poly(ISO_Y_NUM, x), _poly(ISO_Y_DEN, x)
    return (f2_mul(xn, f2_inv(xd)), f2_mul(y, f2_mul(yn, f2_inv(yd))))


def hash_to_g2(msg: bytes, dst: bytes = DST_POP):
    """hash_to_curve onto G2, as an affine point."""
    u0, u1 = hash_to_field_fp2(msg, 2, dst)
    q = g2_add(g2_jac(iso_map(map_to_curve_sswu(u0))),
               g2_jac(iso_map(map_to_curve_sswu(u1))))
    return g2_affine(g2_mul(q, H_EFF_G2))


# ---------------------------------------------------------------------------
# Pairing: optimal ate, affine Miller loop on the twist, plain final exp
# ---------------------------------------------------------------------------

#: (p^12 - 1) / r = (p^6 - 1) * FINAL_EXP_REST
FINAL_EXP_REST = (P ** 2 + 1) * (P ** 4 - P ** 2 + 1) // R


def _line(lam, xt, yt, xp: int, yp: int):
    """The line of slope ``lam`` through twist point (xt, yt), evaluated
    at G1 point (xp, yp) and scaled by w^3 (a factor the final
    exponentiation removes): (lam*xt - yt) - lam*xp*v + yp*v*w."""
    c00 = f2_sub(f2_mul(lam, xt), yt)
    c01 = f2_neg(f2_scale(lam, xp))
    return ((c00, c01, F2_ZERO), (F2_ZERO, (yp, 0), F2_ZERO))


def miller_loop(p_aff, q_aff):
    """f_{|z|,Q}(P), conjugated for z < 0.  P in G1, Q in G2, both affine
    and not infinity."""
    xp, yp = p_aff
    xq, yq = q_aff
    xt, yt = xq, yq
    f = F12_ONE
    for bit in bin(Z_ABS)[3:]:
        lam = f2_mul(f2_scale(f2_sqr(xt), 3), f2_inv(f2_scale(yt, 2)))
        f = f12_mul(f12_sqr(f), _line(lam, xt, yt, xp, yp))
        x3 = f2_sub(f2_sqr(lam), f2_scale(xt, 2))
        yt = f2_sub(f2_mul(lam, f2_sub(xt, x3)), yt)
        xt = x3
        if bit == "1":
            lam = f2_mul(f2_sub(yq, yt), f2_inv(f2_sub(xq, xt)))
            f = f12_mul(f, _line(lam, xt, yt, xp, yp))
            x3 = f2_sub(f2_sub(f2_sqr(lam), xt), xq)
            yt = f2_sub(f2_mul(lam, f2_sub(xt, x3)), yt)
            xt = x3
    return f12_conj(f)


def final_exponentiation(f):
    f = f12_mul(f12_conj(f), f12_inv(f))        # f^(p^6 - 1)
    return f12_pow(f, FINAL_EXP_REST)


def pairing(p_aff, q_aff):
    return final_exponentiation(miller_loop(p_aff, q_aff))


# ---------------------------------------------------------------------------
# Signatures
# ---------------------------------------------------------------------------


def public_key(sk: int) -> bytes:
    return g1_compress(g1_affine(g1_mul(G1_GEN, sk % R)))


def sign_scalar(sk: int, h_aff) -> bytes:
    """Compressed signature ``sk * H`` for a message point ``H``."""
    return g2_compress(g2_affine(g2_mul(g2_jac(h_aff), sk % R)))


def sign(sk: int, msg: bytes) -> bytes:
    return sign_scalar(sk, hash_to_g2(msg))


def verify_set(signature: bytes, pubkeys, message: bytes,
               key_cache: dict | None = None) -> bool:
    """The verdict of one signature set: every key decodes to a G1
    subgroup point that is not infinity, the signature decodes to a G2
    subgroup point, the keys' sum is not infinity, and
    ``e(sum pk, H(m)) == e(G1, sig)``.  ``key_cache`` maps key bytes to
    decoded points (or the exception) across calls."""
    if not pubkeys:
        return False
    acc = (1, 1, 0)
    for raw in pubkeys:
        raw = bytes(raw)
        if key_cache is not None and raw in key_cache:
            pt = key_cache[raw]
        else:
            try:
                pt = g1_decompress(raw)
            except DecodeError:
                pt = False
            if key_cache is not None:
                key_cache[raw] = pt
        if not pt:
            return False
        acc = g1_add(acc, g1_jac(pt))
    agg = g1_affine(acc)
    if agg is None:
        return False
    try:
        sig = g2_decompress(bytes(signature))
    except DecodeError:
        return False
    h = hash_to_g2(bytes(message))
    f = miller_loop(agg, h)
    if sig is not None:
        neg_g1 = (G1_X, P - G1_Y)
        f = f12_mul(f, miller_loop(neg_g1, sig))
    return final_exponentiation(f) == F12_ONE


# ---------------------------------------------------------------------------
# Signing helpers for the traffic generator
# ---------------------------------------------------------------------------


class G2Multiples:
    """Affine ``k * H`` for ``k`` in ``1..n`` and the doublings
    ``2^j * H``, so a walk over sorted secret keys costs one addition per
    signature."""

    def __init__(self, h_aff, n: int, bits: int = 48):
        self.h = h_aff
        small = [None, h_aff]
        pt = g2_jac(h_aff)
        for _ in range(2, n + 1):
            pt = g2_add(pt, g2_jac(h_aff))
            small.append(g2_affine(pt))
        self.small = small
        dbl = [g2_jac(h_aff)]
        for _ in range(1, bits):
            dbl.append(g2_double(dbl[-1]))
        self.dbl = dbl

    def mul(self, k: int):
        """Jacobian ``k * H`` for ``0 <= k < 2^bits``."""
        if k < len(self.small):
            return g2_jac(self.small[k]) if k else (F2_ONE, F2_ONE, F2_ZERO)
        out = (F2_ONE, F2_ONE, F2_ZERO)
        j = 0
        while k:
            if k & 1:
                out = g2_add(out, self.dbl[j])
            k >>= 1
            j += 1
        return out


def affine_add_g2(a, b):
    """Affine sum of two G2 points (either may be None)."""
    return g2_affine(g2_add(g2_jac(a), g2_jac(b)))
