"""Generate the deterministic regression vectors in tests/vectors/.

Seeds come from the NIST KAT CTR-DRBG (utils/ctr_drbg.py — itself verified
against the canonical published first-seed value), so every vector file is
reproducible from this script alone.  Outputs are produced by the pure-Python
pyref implementations; tests/test_kat.py then drives the native C++ and
batched JAX backends through the SAME files, which is the 3-way
cross-implementation anchor (see docs/correctness.md for what this does and
does not prove; official NIST/ACVP files drop into the same directory and
runner when available).

Large artifacts (keys, signatures, ciphertexts > 128 bytes) are recorded as
SHA-256 digests — equally binding for regression/cross-impl checks, 40x
smaller on disk.

Usage: python -m tools.gen_vectors [--counts 3] [--out tests/vectors]
"""

from __future__ import annotations

import argparse
import hashlib
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from quantum_resistant_p2p_tpu.utils.ctr_drbg import CtrDrbg  # noqa: E402

SOURCE = (
    "self-generated by tools/gen_vectors.py (pyref implementation, CTR-DRBG "
    "seeds); NOT official NIST/ACVP vectors — see docs/correctness.md"
)


def _rec(**kv) -> dict:
    out = {}
    for k, v in kv.items():
        if isinstance(v, bytes):
            if len(v) > 128:
                out[k + "_sha256"] = hashlib.sha256(v).hexdigest()
            else:
                out[k] = v.hex()
        else:
            out[k] = v
    return out


def _write(path: Path, algorithm: str, tests: list[dict]) -> None:
    path.write_text(
        json.dumps(
            {"format": "qrp2p-kat-v1", "algorithm": algorithm,
             "source": SOURCE, "tests": tests},
            indent=1,
        )
    )
    print(f"wrote {path} ({len(tests)} tests)")


def gen_mlkem(out: Path, counts: int) -> None:
    from quantum_resistant_p2p_tpu.pyref import mlkem_ref

    for name, p in mlkem_ref.PARAMS.items():
        drbg = CtrDrbg(bytes(range(48)))
        tests = []
        for i in range(counts):
            d, z, m = drbg.random_bytes(32), drbg.random_bytes(32), drbg.random_bytes(32)
            ek, dk = mlkem_ref.keygen(p, d, z)
            key, ct = mlkem_ref.encaps(p, ek, m)
            assert mlkem_ref.decaps(p, dk, ct) == key
            # implicit-rejection branch: first byte of ct flipped
            bad = bytes([ct[0] ^ 1]) + ct[1:]
            tests.append(
                _rec(count=i, d=d, z=z, m=m, ek=ek, dk=dk, ct=ct, ss=key,
                     ss_reject=mlkem_ref.decaps(p, dk, bad))
            )
        _write(out / f"mlkem_{name.replace('ML-KEM-', '')}.json", name, tests)


def gen_mldsa(out: Path, counts: int) -> None:
    from quantum_resistant_p2p_tpu.pyref import mldsa_ref

    for name, p in mldsa_ref.PARAMS.items():
        drbg = CtrDrbg(bytes(range(48)))
        tests = []
        for i in range(counts):
            xi, rnd = drbg.random_bytes(32), drbg.random_bytes(32)
            msg = drbg.random_bytes(33 * (i + 1))
            pk, sk = mldsa_ref.keygen(p, xi)
            m_prime = bytes([0, 0]) + msg
            sig = mldsa_ref.sign_internal(p, sk, m_prime, rnd)
            assert mldsa_ref.verify_internal(p, pk, m_prime, sig)
            tests.append(_rec(count=i, xi=xi, rnd=rnd, msg=msg, pk=pk, sk=sk, sig=sig))
        _write(out / f"mldsa_{name.replace('ML-DSA-', '')}.json", name, tests)


def gen_slhdsa(out: Path, counts: int) -> None:
    from quantum_resistant_p2p_tpu.pyref import slhdsa_ref

    # pyref sign for the 's' sets takes minutes; the native core is bit-exact
    # against pyref (tests/test_native.py), so it generates those.
    from quantum_resistant_p2p_tpu import native

    for name, p in slhdsa_ref.PARAMS.items():
        drbg = CtrDrbg(bytes(range(48)))
        tests = []
        for i in range(counts):
            ss, sp, ps = (drbg.random_bytes(p.n) for _ in range(3))
            msg = drbg.random_bytes(40 + i)
            ns = native.NativeSLHDSA(name)
            pk, sk = ns.keygen(ss, sp, ps)
            sig = ns.sign_internal(msg, sk)
            assert ns.verify_internal(msg, sig, pk)
            tests.append(
                _rec(count=i, sk_seed=ss, sk_prf=sp, pk_seed=ps, msg=msg, pk=pk, sig=sig)
            )
        short = name.replace("SPHINCS+-SHA2-", "").replace("-simple", "")
        _write(out / f"slhdsa_{short}.json", name, tests)


def gen_frodo(out: Path, counts: int) -> None:
    from quantum_resistant_p2p_tpu.pyref import frodo_ref

    for name, p in frodo_ref.PARAMS.items():
        drbg = CtrDrbg(bytes(range(48)))
        tests = []
        for i in range(counts):
            s, se, z = (drbg.random_bytes(p.len_sec) for _ in range(3))
            mu = drbg.random_bytes(p.len_sec)
            pk, sk = frodo_ref.keygen(p, s, se, z)
            ct, ss_ = frodo_ref.encaps(p, pk, mu)
            assert frodo_ref.decaps(p, sk, ct) == ss_
            tests.append(_rec(count=i, s=s, seed_se=se, z=z, mu=mu, pk=pk, sk=sk,
                              ct=ct, ss=ss_))
        _write(out / f"frodo_{name.replace('FrodoKEM-', '').replace('-', '_').lower()}.json",
               name, tests)


def gen_hqc(out: Path, counts: int) -> None:
    from quantum_resistant_p2p_tpu.pyref import hqc_ref

    for name, p in hqc_ref.PARAMS.items():
        drbg = CtrDrbg(bytes(range(48)))
        tests = []
        for i in range(counts):
            sk_seed, pk_seed = drbg.random_bytes(40), drbg.random_bytes(40)
            sigma = drbg.random_bytes(p.k)
            m, salt = drbg.random_bytes(p.k), drbg.random_bytes(16)
            pk, sk = hqc_ref.keygen(p, sk_seed, sigma, pk_seed)
            ct, ss_ = hqc_ref.encaps(p, pk, m, salt)
            assert hqc_ref.decaps(p, sk, ct) == ss_
            tests.append(_rec(count=i, sk_seed=sk_seed, sigma=sigma, pk_seed=pk_seed,
                              m=m, salt=salt, pk=pk, sk=sk, ct=ct, ss=ss_))
        _write(out / f"hqc_{name.replace('HQC-', '')}.json", name, tests)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--counts", type=int, default=3)
    ap.add_argument("--out", default="tests/vectors")
    ap.add_argument("--families", nargs="*",
                    default=["mlkem", "mldsa", "slhdsa", "frodo", "hqc"])
    args = ap.parse_args(argv)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    for fam in args.families:
        {"mlkem": gen_mlkem, "mldsa": gen_mldsa, "slhdsa": gen_slhdsa,
         "frodo": gen_frodo, "hqc": gen_hqc}[fam](out, args.counts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
