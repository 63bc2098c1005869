"""The plain reference against published vectors and the algebra's laws."""

import random

import pytest

from benchmark import bls_ref as B

# RFC 9380 appendix J.10.1 (BLS12381G2_XMD:SHA-256_SSWU_RO_): msg ->
# ((x.c0, x.c1), (y.c0, y.c1)); and the Ethereum consensus-spec BLS test key
# with its public key and its signature over 0xabab..ab (POP DST).

RFC_DST = b"QUUX-V01-CS02-with-BLS12381G2_XMD:SHA-256_SSWU_RO_"

# RFC 9380 J.10.1: msg -> ((x.c0, x.c1), (y.c0, y.c1))
RFC_J10_1 = {
    b"": (
        (
            0x0141EBFBDCA40EB85B87142E130AB689C673CF60F1A3E98D69335266F30D9B8D4AC44C1038E9DCDD5393FAF5C41FB78A,
            0x05CB8437535E20ECFFAEF7752BADDF98034139C38452458BAEEFAB379BA13DFF5BF5DD71B72418717047F5B0F37DA03D,
        ),
        (
            0x0503921D7F6A12805E72940B963C0CF3471C7B2A524950CA195D11062EE75EC076DAF2D4BC358C4B190C0C98064FDD92,
            0x12424AC32561493F3FE3C260708A12B7C620E7BE00099A974E259DDC7D1F6395C3C811CDD19F1E8DBF3E9ECFDCBAB8D6,
        ),
    ),
    b"abc": (
        (
            0x02C2D18E033B960562AAE3CAB37A27CE00D80CCD5BA4B7FE0E7A210245129DBEC7780CCC7954725F4168AFF2787776E6,
            0x139CDDBCCDC5E91B9623EFD38C49F81A6F83F175E80B06FC374DE9EB4B41DFE4CA3A230ED250FBE3A2ACF73A41177FD8,
        ),
        (
            0x1787327B68159716A37440985269CF584BCB1E621D3A7202BE6EA05C4CFE244AEB197642555A0645FB87BF7466B2BA48,
            0x00AA65DAE3C8D732D10ECD2C50F8A1BAF3001578F71C694E03866E9F3D49AC1E1CE70DD94A733534F106D4CEC0EDDD16,
        ),
    ),
    b"abcdef0123456789": (
        (
            0x121982811D2491FDE9BA7ED31EF9CA474F0E1501297F68C298E9F4C0028ADD35AEA8BB83D53C08CFC007C1E005723CD0,
            0x190D119345B94FBD15497BCBA94ECF7DB2CBFD1E1FE7DA034D26CBBA169FB3968288B3FAFB265F9EBD380512A71C3F2C,
        ),
        (
            0x05571A0F8D3C08D094576981F4A3B8EDA0A8E771FCDCC8ECCEAF1356A6ACF17574518ACB506E435B639353C2E14827C8,
            0x0BB5E7572275C567462D91807DE765611490205A941A5A6AF3B1691BFE596C31225D3AABDF15FAFF860CB4EF17C7C3BE,
        ),
    ),
    b"q128_" + b"q" * 128: (
        (
            0x19A84DD7248A1066F737CC34502EE5555BD3C19F2ECDB3C7D9E24DC65D4E25E50D83F0F77105E955D78F4762D33C17DA,
            0x0934ABA516A52D8AE479939A91998299C76D39CC0C035CD18813BEC433F587E2D7A4FEF038260EEF0CEF4D02AAE3EB91,
        ),
        (
            0x14F81CD421617428BC3B9FE25AFBB751D934A00493524BC4E065635B0555084DD54679DF1536101B2C979C0152D09192,
            0x09BCCCFA036B4847C9950780733633F13619994394C23FF0B32FA6B795844F4A0673E20282D07BC69641CEE04F5E5662,
        ),
    ),
    b"a512_" + b"a" * 512: (
        (
            0x01A6BA2F9A11FA5598B2D8ACE0FBE0A0EACB65DECEB476FBBCB64FD24557C2F4B18ECFC5663E54AE16A84F5AB7F62534,
            0x11FCA2FF525572795A801EED17EB12785887C7B63FB77A42BE46CE4A34131D71F7A73E95FEE3F812AEA3DE78B4D01569,
        ),
        (
            0x0B6798718C8AED24BC19CB27F866F1C9EFFCDBF92397AD6448B5C9DB90D2B9DA6CBABF48ADC1ADF59A1A28344E79D57E,
            0x03A47F8E6D1763BA0CAD63D6114C0ACCBEF65707825A511B251A660A9B3994249AE4E63FAC38B23DA0C398689EE2AB52,
        ),
    ),
}

# Ethereum consensus-spec BLS test key (eth2 interop/EF vectors).
EF_SK = 0x263DBD792F5B1BE47ED85F8938C0F29586AF0D3AC7B977F21C278FE1462040E3
EF_PUBKEY_HEX = (
    "a491d1b0ecd9bb917989f0e74f0dea0422eac4a873e5e2644f368dffb9a6e20f"
    "d6e10c1b77654d067c0618f6e5a7f79a"
)
EF_MSG_ABAB = b"\xab" * 32
EF_SIG_ABAB_HEX = (
    "91347bccf740d859038fcdcaf233eeceb2a436bcaaee9b2aa3bfb70efe29dfb2"
    "677562ccbea1c8e061fb9971b0753c240622fab78489ce96768259fc01360346"
    "da5b9f579e5da0d941e4c6ba18a0e64906082375394f337fa1af2b7127b0d121"
)


@pytest.mark.parametrize("msg", sorted(RFC_J10_1))
def test_hash_to_g2_rfc9380_vectors(msg):
    pt = B.hash_to_g2(msg, RFC_DST)
    assert ((pt[0][0], pt[0][1]), (pt[1][0], pt[1][1])) == RFC_J10_1[msg]


def test_ethereum_signing_vector():
    assert B.public_key(EF_SK).hex() == EF_PUBKEY_HEX
    sig = B.sign(EF_SK, EF_MSG_ABAB)
    assert sig.hex() == EF_SIG_ABAB_HEX
    pk = bytes.fromhex(EF_PUBKEY_HEX)
    assert B.verify_set(sig, [pk], EF_MSG_ABAB)
    assert not B.verify_set(sig, [pk], b"\xac" * 32)


def test_generators_on_curves_and_in_subgroups():
    assert (B.G1_Y ** 2 - B.G1_X ** 3 - 4) % B.P == 0
    lhs = B.f2_sqr(B.G2_Y)
    rhs = B.f2_add(B.f2_mul(B.f2_sqr(B.G2_X), B.G2_X), B.B2)
    assert lhs == rhs
    assert B.g1_mul(B.G1_GEN, B.R)[2] == 0
    assert B.g2_mul(B.G2_GEN, B.R)[2] == B.F2_ZERO


def test_pairing_is_bilinear_and_nondegenerate():
    g1 = B.g1_affine(B.G1_GEN)
    g2 = B.g2_affine(B.G2_GEN)
    e = B.pairing(g1, g2)
    assert e != B.F12_ONE
    a, b = 0x1234567, 0x89ABCDEF
    lhs = B.pairing(B.g1_affine(B.g1_mul(B.G1_GEN, a)),
                    B.g2_affine(B.g2_mul(B.G2_GEN, b)))
    assert lhs == B.f12_pow(e, a * b)
    assert B.f12_pow(e, B.R) == B.F12_ONE


def test_compression_round_trip():
    rng = random.Random(3)
    for _ in range(3):
        k = rng.randrange(1, B.R)
        p1 = B.g1_affine(B.g1_mul(B.G1_GEN, k))
        assert B.g1_decompress(B.g1_compress(p1)) == p1
        p2 = B.g2_affine(B.g2_mul(B.G2_GEN, k))
        assert B.g2_decompress(B.g2_compress(p2)) == p2


def test_decode_rejects_bad_points():
    good = B.public_key(5)
    with pytest.raises(B.DecodeError):
        B.g1_decompress(good[:-1])
    with pytest.raises(B.DecodeError):
        B.g1_decompress(bytes([good[0] & 0x7F]) + good[1:])  # uncompressed flag
    x = 0
    while True:
        x += 1
        if B.fp_sqrt((x ** 3 + 4) % B.P) is None:
            break
    with pytest.raises(B.DecodeError):                       # off the curve
        B.g1_decompress(bytes([0x80]) + x.to_bytes(48, "big")[1:])
    assert B.g1_decompress(bytes([0xC0]) + bytes(47)) is None  # infinity


def test_verify_set_verdicts():
    msg = b"\x42" * 32
    sks = [11, 12, 13]
    pks = [B.public_key(k) for k in sks]
    agg = B.sign_scalar(sum(sks), B.hash_to_g2(msg))
    assert B.verify_set(agg, pks, msg)
    assert not B.verify_set(agg, pks[:2], msg)               # a signer missing
    assert not B.verify_set(agg, pks, b"\x43" * 32)          # another message
    inf_pk = bytes([0xC0]) + bytes(47)
    assert not B.verify_set(B.sign(11, msg), [inf_pk], msg)  # infinity key
    assert not B.verify_set(agg, [], msg)
