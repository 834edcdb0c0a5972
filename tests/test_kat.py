"""Known-answer-test runner over tests/vectors/.

Drives every implementation of each algorithm — pure-Python pyref, native
C++ (ctypes), and batched JAX — through the SAME committed vector files, so
a divergence in any one implementation fails loudly.  File provenance is in
each file's "source" field and docs/correctness.md: current vectors are
self-generated (3-way cross-implementation regression anchor); official
NIST/ACVP files use the same runner when dropped in:

  * qrp2p-kat-v1 JSON (this repo's format, large values as sha256 digests)
  * ACVP-style JSON (testGroups/tests with hex fields) via _iter_acvp
  * NIST PQCgenKAT .rsp files (count/seed/... stanzas) via _iter_rsp, with
    utils/ctr_drbg.py reproducing the harness RNG (DRBG verified against the
    canonical published first-seed value in test_ctr_drbg_known_answer)

Reference analog: liboqs KATs are the reference app's correctness anchor
(BASELINE.json "bit-exact vs liboqs KATs"; vendor/oqs.py:310-390).
"""

from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from quantum_resistant_p2p_tpu import native
from quantum_resistant_p2p_tpu.pyref import (
    frodo_ref,
    hqc_ref,
    mldsa_ref,
    mlkem_ref,
    slhdsa_ref,
)

VECTOR_DIR = Path(__file__).parent / "vectors"



def _load(fname: str) -> dict:
    return json.loads((VECTOR_DIR / fname).read_text())


def _check(rec: dict, key: str, actual: bytes) -> None:
    """Compare against `key` (hex) or `key_sha256` (digest), whichever exists."""
    if key in rec:
        assert actual.hex() == rec[key], f"{key} mismatch"
    elif key + "_sha256" in rec:
        assert hashlib.sha256(actual).hexdigest() == rec[key + "_sha256"], (
            f"{key} digest mismatch"
        )
    else:  # pragma: no cover - malformed vector file
        raise KeyError(f"vector record has neither {key} nor {key}_sha256")


def _b(rec: dict, key: str) -> bytes:
    return bytes.fromhex(rec[key])


# --------------------------------------------------------------------------
# CTR-DRBG: external anchor — this exact value is the first generated seed in
# every published NIST round-3 PQCgenKAT .rsp file (entropy input 00..2F).
# --------------------------------------------------------------------------


def test_ctr_drbg_known_answer():
    from quantum_resistant_p2p_tpu.utils.ctr_drbg import CtrDrbg

    drbg = CtrDrbg(bytes(range(48)))
    assert drbg.random_bytes(48).hex().upper() == (
        "061550234D158C5EC95595FE04EF7A25767F2E24CC2BC479D09D86DC9ABCFDE7"
        "056A8C266F9EF97ED08541DBD2E1FFA1"
    )


# --------------------------------------------------------------------------
# ML-KEM
# --------------------------------------------------------------------------

MLKEM_FILES = ["mlkem_512.json", "mlkem_768.json", "mlkem_1024.json"]


@pytest.mark.parametrize("fname", MLKEM_FILES)
def test_mlkem_kat_pyref_and_native(fname):
    data = _load(fname)
    p = mlkem_ref.PARAMS[data["algorithm"]]
    nat = native.NativeMLKEM(data["algorithm"])
    for rec in data["tests"]:
        d, z, m = _b(rec, "d"), _b(rec, "z"), _b(rec, "m")
        ek, dk = mlkem_ref.keygen(p, d, z)
        _check(rec, "ek", ek)
        _check(rec, "dk", dk)
        key, ct = mlkem_ref.encaps(p, ek, m)
        _check(rec, "ct", ct)
        _check(rec, "ss", key)
        assert mlkem_ref.decaps(p, dk, ct) == key
        bad = bytes([ct[0] ^ 1]) + ct[1:]
        _check(rec, "ss_reject", mlkem_ref.decaps(p, dk, bad))
        nek, ndk = nat.keygen(d, z)
        assert (nek, ndk) == (ek, dk)
        nkey, nct = nat.encaps(ek, m)
        assert (nkey, nct) == (key, ct)
        assert nat.decaps(dk, ct) == key
        assert nat.decaps(dk, bad) == mlkem_ref.decaps(p, dk, bad)


@pytest.mark.parametrize(
    "fname",
    ["mlkem_768.json",
     pytest.param("mlkem_512.json", marks=pytest.mark.slow),
     pytest.param("mlkem_1024.json", marks=pytest.mark.slow)],
)
def test_mlkem_kat_jax(fname):
    from quantum_resistant_p2p_tpu.kem import mlkem as jmlkem

    data = _load(fname)
    kg, enc, dec = jmlkem.get(data["algorithm"])
    recs = data["tests"]
    d = np.stack([np.frombuffer(_b(r, "d"), np.uint8) for r in recs])
    z = np.stack([np.frombuffer(_b(r, "z"), np.uint8) for r in recs])
    m = np.stack([np.frombuffer(_b(r, "m"), np.uint8) for r in recs])
    ek, dk = (np.asarray(a) for a in kg(d, z))
    key, ct = enc(ek, m)
    key, ct = np.asarray(key), np.asarray(ct)
    ss2 = np.asarray(dec(dk, ct))
    for i, rec in enumerate(recs):
        _check(rec, "ek", bytes(ek[i]))
        _check(rec, "dk", bytes(dk[i]))
        _check(rec, "ct", bytes(ct[i]))
        _check(rec, "ss", bytes(key[i]))
        assert bytes(ss2[i]) == bytes(key[i])


# --------------------------------------------------------------------------
# ML-DSA
# --------------------------------------------------------------------------

MLDSA_FILES = ["mldsa_44.json", "mldsa_65.json", "mldsa_87.json"]


@pytest.mark.parametrize("fname", MLDSA_FILES)
def test_mldsa_kat_pyref_and_native(fname):
    data = _load(fname)
    p = mldsa_ref.PARAMS[data["algorithm"]]
    nat = native.NativeMLDSA(data["algorithm"])
    for rec in data["tests"]:
        xi, rnd, msg = _b(rec, "xi"), _b(rec, "rnd"), _b(rec, "msg")
        m_prime = bytes([0, 0]) + msg
        pk, sk = mldsa_ref.keygen(p, xi)
        _check(rec, "pk", pk)
        _check(rec, "sk", sk)
        sig = mldsa_ref.sign_internal(p, sk, m_prime, rnd)
        _check(rec, "sig", sig)
        assert mldsa_ref.verify_internal(p, pk, m_prime, sig)
        assert nat.keygen(xi) == (pk, sk)
        assert nat.sign_internal(sk, m_prime, rnd) == sig
        assert nat.verify_internal(pk, m_prime, sig)


@pytest.mark.parametrize(
    "fname",
    # 44 runs in the fast tier as the JAX coverage for that parameter set
    # (its oracle sign test is slow-tier; see tests/test_mldsa.py).
    ["mldsa_65.json", "mldsa_44.json",
     pytest.param("mldsa_87.json", marks=pytest.mark.slow)],
)
def test_mldsa_kat_jax(fname):
    import hashlib as _hl

    from quantum_resistant_p2p_tpu.sig import mldsa as jmldsa

    data = _load(fname)
    p = mldsa_ref.PARAMS[data["algorithm"]]
    kg, sign_mu, verify_mu = jmldsa.get(data["algorithm"])
    recs = data["tests"]
    xi = np.stack([np.frombuffer(_b(r, "xi"), np.uint8) for r in recs])
    pk, sk = (np.asarray(a) for a in kg(xi))
    mus, rnds = [], []
    for i, rec in enumerate(recs):
        _check(rec, "pk", bytes(pk[i]))
        _check(rec, "sk", bytes(sk[i]))
        tr = bytes(sk[i][64:128])
        m_prime = bytes([0, 0]) + _b(rec, "msg")
        mus.append(np.frombuffer(_hl.shake_256(tr + m_prime).digest(64), np.uint8))
        rnds.append(np.frombuffer(_b(rec, "rnd"), np.uint8))
    sigs, done = sign_mu(sk, np.stack(mus), np.stack(rnds))
    sigs = np.asarray(sigs)
    assert bool(np.asarray(done).all())
    for i, rec in enumerate(recs):
        _check(rec, "sig", bytes(sigs[i]))
    ok = np.asarray(verify_mu(pk, np.stack(mus), sigs))
    assert ok.all()


# --------------------------------------------------------------------------
# SLH-DSA
# --------------------------------------------------------------------------

SLHDSA_FILES = [
    "slhdsa_128s.json", "slhdsa_128f.json",
    pytest.param("slhdsa_192s.json", marks=pytest.mark.slow),
    pytest.param("slhdsa_192f.json", marks=pytest.mark.slow),
    pytest.param("slhdsa_256s.json", marks=pytest.mark.slow),
    pytest.param("slhdsa_256f.json", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("fname", SLHDSA_FILES)
def test_slhdsa_kat_native(fname):
    data = _load(fname)
    nat = native.NativeSLHDSA(data["algorithm"])
    for rec in data["tests"]:
        ss, sp, ps = _b(rec, "sk_seed"), _b(rec, "sk_prf"), _b(rec, "pk_seed")
        msg = _b(rec, "msg")
        pk, sk = nat.keygen(ss, sp, ps)
        _check(rec, "pk", pk)
        sig = nat.sign_internal(msg, sk)
        _check(rec, "sig", sig)
        assert nat.verify_internal(msg, sig, pk)


@pytest.mark.parametrize("fname", ["slhdsa_128f.json"])
def test_slhdsa_kat_pyref(fname):
    """Fast tier on purpose: the only toolchain-independent SPHINCS+ vector
    check (native tests skip without g++, the JAX module is slow-tier)."""
    data = _load(fname)
    p = slhdsa_ref.PARAMS[data["algorithm"]]
    rec = data["tests"][0]
    pk, sk = slhdsa_ref.keygen(p, _b(rec, "sk_seed"), _b(rec, "sk_prf"), _b(rec, "pk_seed"))
    _check(rec, "pk", pk)
    sig = slhdsa_ref.sign_internal(p, _b(rec, "msg"), sk, None)
    _check(rec, "sig", sig)


@pytest.mark.slow
@pytest.mark.parametrize("fname", ["slhdsa_128f.json"])
def test_slhdsa_kat_jax(fname):
    from quantum_resistant_p2p_tpu.sig import sphincs as jslh

    data = _load(fname)
    p = slhdsa_ref.PARAMS[data["algorithm"]]
    kg, sign_digest, verify_digest = jslh.get(data["algorithm"])
    recs = data["tests"]
    ss = np.stack([np.frombuffer(_b(r, "sk_seed"), np.uint8) for r in recs])
    sp = np.stack([np.frombuffer(_b(r, "sk_prf"), np.uint8) for r in recs])
    ps = np.stack([np.frombuffer(_b(r, "pk_seed"), np.uint8) for r in recs])
    pk, sk = (np.asarray(a) for a in kg(ss, sp, ps))
    rs, digests = [], []
    for i, rec in enumerate(recs):
        _check(rec, "pk", bytes(pk[i]))
        msg = _b(rec, "msg")
        skb = bytes(sk[i])
        r = slhdsa_ref.prf_msg(p, skb[p.n:2 * p.n], skb[2 * p.n:3 * p.n], msg)
        rs.append(np.frombuffer(r, np.uint8))
        digests.append(np.frombuffer(
            slhdsa_ref.h_msg(p, r, skb[2 * p.n:3 * p.n], skb[3 * p.n:], msg), np.uint8))
    sigs = np.asarray(sign_digest(sk, np.stack(rs), np.stack(digests)))
    for i, rec in enumerate(recs):
        _check(rec, "sig", bytes(sigs[i]))
    assert np.asarray(verify_digest(pk, np.stack(digests), sigs)).all()


# --------------------------------------------------------------------------
# FrodoKEM / HQC
# --------------------------------------------------------------------------

FRODO_FILES = [
    "frodo_640_aes.json",
    pytest.param("frodo_640_shake.json", marks=pytest.mark.slow),
    pytest.param("frodo_976_aes.json", marks=pytest.mark.slow),
    pytest.param("frodo_976_shake.json", marks=pytest.mark.slow),
    pytest.param("frodo_1344_aes.json", marks=pytest.mark.slow),
    pytest.param("frodo_1344_shake.json", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("fname", FRODO_FILES)
def test_frodo_kat_pyref(fname):
    data = _load(fname)
    p = frodo_ref.PARAMS[data["algorithm"]]
    for rec in data["tests"][:1]:
        pk, sk = frodo_ref.keygen(p, _b(rec, "s"), _b(rec, "seed_se"), _b(rec, "z"))
        _check(rec, "pk", pk)
        _check(rec, "sk", sk)
        ct, ss = frodo_ref.encaps(p, pk, _b(rec, "mu"))
        _check(rec, "ct", ct)
        _check(rec, "ss", ss)
        assert frodo_ref.decaps(p, sk, ct) == ss


HQC_FILES = [
    "hqc_128.json",
    pytest.param("hqc_192.json", marks=pytest.mark.slow),
    pytest.param("hqc_256.json", marks=pytest.mark.slow),
]


@pytest.mark.parametrize("fname", HQC_FILES)
def test_hqc_kat_pyref(fname):
    data = _load(fname)
    p = hqc_ref.PARAMS[data["algorithm"]]
    for rec in data["tests"][:1]:
        pk, sk = hqc_ref.keygen(p, _b(rec, "sk_seed"), _b(rec, "sigma"), _b(rec, "pk_seed"))
        _check(rec, "pk", pk)
        _check(rec, "sk", sk)
        ct, ss = hqc_ref.encaps(p, pk, _b(rec, "m"), _b(rec, "salt"))
        _check(rec, "ct", ct)
        _check(rec, "ss", ss)
        assert hqc_ref.decaps(p, sk, ct) == ss


# --------------------------------------------------------------------------
# Official-format drop-in support: ACVP JSON and NIST .rsp
# --------------------------------------------------------------------------


def _iter_acvp(data: dict):
    """Yield flat test dicts from an ACVP-style {testGroups: [{tests: []}]}."""
    for group in data.get("testGroups", []):
        meta = {k: v for k, v in group.items() if k != "tests"}
        for t in group.get("tests", []):
            yield {**meta, **t}


def _iter_rsp(text: str):
    """Yield stanza dicts from a NIST PQCgenKAT .rsp file."""
    rec: dict = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            if rec:
                yield rec
                rec = {}
            continue
        if "=" in line:
            k, _, v = line.partition("=")
            rec[k.strip()] = v.strip()
    if rec:
        yield rec


def test_acvp_dropin_mlkem():
    """Official ACVP ML-KEM files run through this path; validated here with a
    generated fixture in the same shape (d/z/ek/dk, ek/m/c/k hex fields)."""
    files = sorted(VECTOR_DIR.glob("acvp_mlkem*.json"))
    if not files:
        pytest.skip("no ACVP ML-KEM files present")
    for f in files:
        data = json.loads(f.read_text())
        algo = data.get("algorithm", "ML-KEM-768")
        name = algo if algo.startswith("ML-KEM") else "ML-KEM-768"
        p = mlkem_ref.PARAMS[name]
        for t in _iter_acvp(data):
            if "d" in t and "z" in t:  # keygen case
                ek, dk = mlkem_ref.keygen(p, bytes.fromhex(t["d"]), bytes.fromhex(t["z"]))
                assert ek.hex() == t["ek"].lower() and dk.hex() == t["dk"].lower()
            if "m" in t and "ek" in t:  # encap case
                k, c = mlkem_ref.encaps(p, bytes.fromhex(t["ek"]), bytes.fromhex(t["m"]))
                assert c.hex() == t["c"].lower() and k.hex() == t["k"].lower()
            if "dk" in t and "c" in t:  # decap case
                k = mlkem_ref.decaps(p, bytes.fromhex(t["dk"]), bytes.fromhex(t["c"]))
                assert k.hex() == t["k"].lower()


def test_rsp_parser_roundtrip(tmp_path):
    """The .rsp stanza parser + DRBG path official FrodoKEM/Kyber KAT files
    use; proven on a generated stanza file."""
    from quantum_resistant_p2p_tpu.utils.ctr_drbg import CtrDrbg

    master = CtrDrbg(bytes(range(48)))
    seeds = [master.random_bytes(48) for _ in range(3)]
    lines = ["# generated fixture", ""]
    for i, seed in enumerate(seeds):
        lines += [f"count = {i}", f"seed = {seed.hex().upper()}", ""]
    f = tmp_path / "fixture.rsp"
    f.write_text("\n".join(lines))
    recs = list(_iter_rsp(f.read_text()))
    assert [int(r["count"]) for r in recs] == [0, 1, 2]
    assert [r["seed"].lower() for r in recs] == [s.hex() for s in seeds]
    # per-count DRBG reseed, as PQCgenKAT does before each keypair call
    sub = CtrDrbg(seeds[0])
    assert len(sub.random_bytes(64)) == 64


def test_hqc_official_mismatch_diagnosis():
    """The HQC divergence-diagnosis decision tree pinpoints which seam
    assumption a failing official .rsp refutes: synthesize stanzas with
    each enumerable variant seam and assert the diagnosis names it
    (docs/correctness.md §HQC seam)."""
    from quantum_resistant_p2p_tpu.pyref import hqc_ref
    from quantum_resistant_p2p_tpu.utils.ctr_drbg import CtrDrbg
    from tools.verify_vectors import (
        _hqc_encrypt_order,
        _hqc_keygen_order,
        check_rsp_hqc,
    )

    p = hqc_ref.PARAMS["HQC-128"]
    seed = bytes(range(48))
    # Per-call DRBG semantics (each randombytes call pads to the AES block
    # and rekeys) — the draws must be made exactly like the checker's.
    drbg = CtrDrbg(seed)
    sk_seed, sigma, pk_seed = (
        drbg.random_bytes(40), drbg.random_bytes(p.k), drbg.random_bytes(40)
    )
    m, salt = drbg.random_bytes(p.k), drbg.random_bytes(16)

    def stanza(pk, sk, ct, ss):
        return "\n".join(
            ["count = 0", f"seed = {seed.hex().upper()}",
             f"pk = {pk.hex().upper()}", f"sk = {sk.hex().upper()}",
             f"ct = {ct.hex().upper()}", f"ss = {ss.hex().upper()}", ""]
        )

    # implemented seam reproduces its own stanza (sanity)
    pk, sk = hqc_ref.keygen(p, sk_seed, sigma, pk_seed)
    ct, ss = hqc_ref.encaps(p, pk, m, salt)
    n, ok, errors = check_rsp_hqc(stanza(pk, sk, ct, ss), "PQCgenKAT_hqc128.rsp")
    assert (n, ok) == (1, 1), errors

    # variant: round-3 x-before-y sk draw order
    pk_v = _hqc_keygen_order(p, sk_seed, sigma, pk_seed, x_first=True)
    ct_v, ss_v = hqc_ref.encaps(p, pk_v, m, salt)
    _, ok, errors = check_rsp_hqc(
        stanza(pk_v, sk_seed + sigma + pk_v, ct_v, ss_v), "PQCgenKAT_hqc128.rsp"
    )
    assert ok == 0 and any("ROUND-3 sk-draw order" in e for e in errors), errors

    # variant: pk_seed drawn before sk_seed
    d2 = CtrDrbg(seed)
    pk_seed_b, sk_seed_b, sigma_b = (
        d2.random_bytes(40), d2.random_bytes(40), d2.random_bytes(p.k)
    )
    _, ok, errors = check_rsp_hqc(
        stanza(*hqc_ref.keygen(p, sk_seed_b, sigma_b, pk_seed_b), ct, ss),
        "PQCgenKAT_hqc128.rsp",
    )
    assert ok == 0 and any("drawn FIRST" in e for e in errors), errors

    # variant: theta-expander draw order r1,r2,e instead of r2,e,r1
    theta = hqc_ref._hash_g(m + pk[:32] + salt)
    u, v = _hqc_encrypt_order(p, pk, m, theta, ("r1", "r2", "e"))
    ct_o = (u.to_bytes(p.n_bytes, "little")
            + v.to_bytes(p.n1n2_bytes, "little") + salt)
    ss_o = hqc_ref._hash_k(m + ct_o[:-16])
    _, ok, errors = check_rsp_hqc(stanza(pk, sk, ct_o, ss_o), "PQCgenKAT_hqc128.rsp")
    assert ok == 0 and any(
        "VARIANT" in e and "r1>r2>e" in e for e in errors
    ), errors


def test_verify_vectors_all_families():
    """tools/verify_vectors.py over the committed vector dir: every family
    has at least a fixture exercising its official-format parser + DRBG
    seam, and everything present passes."""
    from tools.verify_vectors import verify_directory

    report = verify_directory(VECTOR_DIR)
    for family, fam in report.items():
        assert fam["files"], f"{family}: no official-format fixture committed"
        assert fam["status"] != "FAIL", (family, fam["errors"])
        assert fam["vectors"] == fam["passed"] > 0
