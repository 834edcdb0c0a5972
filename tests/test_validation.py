"""Input-length validation at the provider boundary (ADVICE r1, high).

Attacker-controlled public keys / ciphertexts of the wrong length must raise
ValueError at the plugin boundary — BEFORE reaching the native C++ core
(which reads fixed lengths from the buffer it is handed: a short pk would be
a heap out-of-bounds read) or the JAX backends (opaque reshape errors).
The reference gets this for free from liboqs's internal checks
(vendor/oqs.py:332-381); here it is the provider's job.
"""

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.provider import get_kem, get_signature


@pytest.mark.parametrize("name", ["ML-KEM-768", "FrodoKEM-640-AES", "HQC-128"])
def test_kem_scalar_rejects_bad_lengths(name):
    kem = get_kem(name, "cpu")
    pk, sk = kem.generate_keypair()
    ct, _ = kem.encapsulate(pk)

    with pytest.raises(ValueError):
        kem.encapsulate(pk[:-1])
    with pytest.raises(ValueError):
        kem.encapsulate(pk + b"\x00")
    with pytest.raises(ValueError):
        kem.encapsulate(b"")
    with pytest.raises(ValueError):
        kem.decapsulate(sk, ct[:-1])
    with pytest.raises(ValueError):
        kem.decapsulate(sk[:-1], ct)

    # well-formed input still round-trips
    ss = kem.decapsulate(sk, ct)
    assert len(ss) == kem.shared_secret_len


@pytest.mark.parametrize("name", ["ML-KEM-512"])
def test_kem_batch_rejects_bad_shapes(name):
    kem = get_kem(name, "cpu")
    pks, sks = kem.generate_keypair_batch(2)
    cts, _ = kem.encapsulate_batch(pks)

    with pytest.raises(ValueError):
        kem.encapsulate_batch(pks[:, :-1])
    with pytest.raises(ValueError):
        kem.decapsulate_batch(sks, cts[:, :-1])
    with pytest.raises(ValueError):
        kem.decapsulate_batch(sks[:, 1:], cts)


def test_signature_sign_rejects_bad_sk_and_verify_returns_false():
    sig = get_signature("ML-DSA-44", "cpu")
    pk, sk = sig.generate_keypair()
    with pytest.raises(ValueError):
        sig.sign(sk[:-1], b"msg")
    s = sig.sign(sk, b"msg")
    # verify never raises on malformed input — contract is False
    assert sig.verify(pk[:-1], b"msg", s) is False
    assert sig.verify(pk, b"msg", s[:-1]) is False
    assert sig.verify(pk, b"msg", s) is True


def test_sphincs_tpu_verify_batch_normalizes_2d_signature_elements():
    """Scalar verify wraps operands as (1, L) arrays; verify_batch's digest
    derivation must byte-slice the NORMALIZED rows, not the raw elements
    (a (1, L) element row-slices to the whole signature and poisons h_msg's
    randomizer — the scalar tpu-verify path returned False for every valid
    signature until round 3)."""
    sig_alg = get_signature("SPHINCS+-SHA2-128s-simple", backend="tpu")
    p = sig_alg.params
    rng = np.random.default_rng(3)
    pk = rng.integers(0, 256, (1, p.pk_len), dtype=np.uint8)
    sig_flat = rng.integers(0, 256, (p.sig_len,), dtype=np.uint8)
    seen = []

    def fake_verify(pks, digests, sigs):
        seen.append(np.asarray(digests).copy())
        return np.ones(len(np.asarray(pks)), dtype=bool)

    sig_alg._verify_digest = fake_verify
    sig_alg._mesh = None
    sig_alg.verify_batch(pk, [b"m"], [sig_flat])          # 1-D element
    sig_alg.verify_batch(pk, [b"m"], [sig_flat[None]])    # (1, L) element
    assert (seen[0] == seen[1]).all(), "2-D element changed the derived digest"


def test_sphincs_tpu_sign_batch_sliced_at_compile_ceiling():
    """s-set sign dispatches are capped at the measured compile ceiling
    (_SLH_MAX_SIGN_BATCH): a queue-sized batch must arrive as fixed-size
    slices, never as one giant program the compile helper cannot build."""
    from quantum_resistant_p2p_tpu.provider import sig_providers

    sig_alg = get_signature("SPHINCS+-SHA2-256s-simple", backend="tpu")
    p = sig_alg.params
    cap = sig_providers._SLH_MAX_SIGN_BATCH[p.name]
    assert cap == 32
    batches = []

    def fake_sign(sks, rs, digests):
        batches.append(len(np.asarray(sks)))
        return np.zeros((len(np.asarray(sks)), p.sig_len), np.uint8)

    sig_alg._sign_digest = fake_sign
    sig_alg._mesh = None
    n = 70
    rng = np.random.default_rng(5)
    sks = rng.integers(0, 256, (n, p.sk_len), dtype=np.uint8)
    out = sig_alg.sign_batch(sks, [b"m%d" % i for i in range(n)])
    assert len(out) == n
    assert batches == [cap, cap, cap]  # 70 rows -> 3 padded slices of 32


def test_sphincs_tpu_sign_batch_mesh_keeps_global_cap():
    """With a provider mesh, the sign cap stays a GLOBAL bound: a dispatch
    never holds more than cap rows across the mesh, so the per-device step
    is cap // mesh.size, never cap per device.  Each device traces its own
    shard (shard_map), which is what ``fake_sign`` sees."""
    from quantum_resistant_p2p_tpu.provider import sig_providers

    sig_alg = get_signature("SPHINCS+-SHA2-256s-simple", backend="tpu", devices=8)
    assert sig_alg._mesh is not None and sig_alg._mesh.size == 8
    p = sig_alg.params
    cap = sig_providers._SLH_MAX_SIGN_BATCH[p.name]  # 32
    batches = []

    def fake_sign(sks, rs, digests):
        b = len(sks)
        batches.append(b)
        import jax.numpy as jnp

        return jnp.zeros((b, p.sig_len), jnp.uint8)

    sig_alg._sign_digest = fake_sign
    n = 70
    rng = np.random.default_rng(6)
    sks = rng.integers(0, 256, (n, p.sk_len), dtype=np.uint8)
    out = sig_alg.sign_batch(sks, [b"m%d" % i for i in range(n)])
    assert len(out) == n
    # global dispatch (every device's shard) never exceeds the ceiling
    assert max(batches) * sig_alg._mesh.size <= cap
