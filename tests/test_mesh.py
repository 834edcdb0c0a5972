"""Multi-chip sharding in the PRODUCTION provider path (8-device virtual mesh).

The conftest pins an 8-device virtual CPU platform, so these tests exercise
the same shard_map programs a real multi-chip TPU host would run: providers
constructed with ``devices=8`` shard every device batch across the mesh via
provider.base.mesh_dispatch (computation follows data — no collectives on the
hot path), and results must be BIT-EXACT vs the single-device path, including
batches not divisible by (or smaller than) the mesh.

Reference analog: none — the reference has no device parallelism (SURVEY.md
§2.3); this is the framework's TPU-native scale-out axis.
"""

import numpy as np
import pytest

from quantum_resistant_p2p_tpu.parallel.mesh import make_mesh
from quantum_resistant_p2p_tpu.provider import get_kem, get_signature
from quantum_resistant_p2p_tpu.provider.base import mesh_dispatch, sliced_dispatch

RNG = np.random.default_rng(20260730)
NDEV = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(NDEV)


def test_mesh_dispatch_kernel_bit_exact_nondivisible(mesh):
    """Raw jitted kernels, batch 11 on 8 devices: sharded == unsharded."""
    from quantum_resistant_p2p_tpu.kem import mlkem

    kg, enc, dec = mlkem.get("ML-KEM-512")
    n = 11
    d, z, m = (RNG.integers(0, 256, (n, 32), dtype=np.uint8) for _ in range(3))

    ek_s, dk_s = mesh_dispatch(kg, mesh, d, z)
    ek_r, dk_r = (np.asarray(o) for o in kg(d, z))
    assert np.array_equal(ek_s, ek_r) and np.array_equal(dk_s, dk_r)

    key_s, ct_s = mesh_dispatch(enc, mesh, ek_r, m)
    key_r, ct_r = (np.asarray(o) for o in enc(ek_r, m))
    assert np.array_equal(key_s, key_r) and np.array_equal(ct_s, ct_r)

    key2_s = mesh_dispatch(dec, mesh, dk_r, ct_r)
    assert np.array_equal(key2_s, key_r)


def test_mesh_dispatch_batch_smaller_than_mesh(mesh):
    """3 rows on 8 devices: padded to one row per device, trimmed back."""
    from quantum_resistant_p2p_tpu.kem import mlkem

    kg, _, _ = mlkem.get("ML-KEM-512")
    d, z = (RNG.integers(0, 256, (3, 32), dtype=np.uint8) for _ in range(2))
    ek_s, dk_s = mesh_dispatch(kg, mesh, d, z)
    ek_r, dk_r = (np.asarray(o) for o in kg(d, z))
    assert np.array_equal(ek_s, ek_r) and np.array_equal(dk_s, dk_r)


def test_kem_provider_mesh_bit_exact_vs_single_device():
    """Production ML-KEM provider with devices=8 vs devices=0, batch 11."""
    single = get_kem("ML-KEM-512", backend="tpu")
    sharded = get_kem("ML-KEM-512", backend="tpu", devices=NDEV)
    assert sharded._mesh is not None and single._mesh is None

    n = 11
    eks, dks = single.generate_keypair_batch(n)
    cts, keys = single.encapsulate_batch(eks)
    # decaps is deterministic given (sk, ct): sharded must match bit-for-bit
    assert np.array_equal(sharded.decapsulate_batch(dks, cts), keys)
    # full roundtrip through the sharded provider (encaps draws fresh m)
    cts2, keys2 = sharded.encapsulate_batch(eks)
    assert np.array_equal(sharded.decapsulate_batch(dks, cts2), keys2)


def test_sliced_dispatch_shards_each_slice(mesh, monkeypatch):
    """Per-device cap + mesh: a 20-row batch on cap=1 x 8 devices runs as
    ceil(20/8)=3 sharded dispatches and still matches the unsharded result."""
    from quantum_resistant_p2p_tpu.kem import mlkem

    _, _, dec = mlkem.get("ML-KEM-512")
    single = get_kem("ML-KEM-512", backend="tpu")
    n = 20
    eks, dks = single.generate_keypair_batch(n)
    cts, keys = single.encapsulate_batch(eks)

    calls = []
    real = mesh_dispatch

    def counting(fn, m, *arrays):
        calls.append(arrays[0].shape[0])
        return real(fn, m, *arrays)

    import quantum_resistant_p2p_tpu.provider.base as base

    monkeypatch.setattr(base, "mesh_dispatch", counting)
    got = base.sliced_dispatch(dec, 1, dks, cts, mesh=mesh)
    assert np.array_equal(got, keys)
    assert calls == [8, 8, 8]  # 20 rows -> two full slices + padded tail


def test_mldsa_provider_mesh_sign_verify_bit_exact():
    """ML-DSA sign (fixed rnd) and verify, devices=8 vs devices=0, batch 5."""
    single = get_signature("ML-DSA-44", backend="tpu")
    sharded = get_signature("ML-DSA-44", backend="tpu", devices=NDEV)

    pk, sk = single.generate_keypair()
    n = 5
    sks = np.broadcast_to(np.frombuffer(sk, np.uint8), (n, len(sk)))
    pks = np.broadcast_to(np.frombuffer(pk, np.uint8), (n, len(pk)))
    msgs = [b"mesh msg %d" % i for i in range(n)]
    rnd = [bytes([i]) * 32 for i in range(n)]

    ref = single.sign_batch(sks, msgs, rnd=rnd)
    got = sharded.sign_batch(sks, msgs, rnd=rnd)
    assert [bytes(s) for s in got] == [bytes(s) for s in ref]

    oks = sharded.verify_batch(pks, msgs, got)
    assert np.asarray(oks).all()
    bad = sharded.verify_batch(pks, [m + b"!" for m in msgs], got)
    assert not np.asarray(bad).any()


@pytest.mark.slow
def test_sphincs_provider_mesh_verify_bit_exact():
    """SPHINCS+ verify through the mesh, batch 3 (slow tier: JAX sign)."""
    single = get_signature("SPHINCS+-SHA2-128f-simple", backend="tpu")
    sharded = get_signature("SPHINCS+-SHA2-128f-simple", backend="tpu", devices=NDEV)

    pk, sk = single.generate_keypair()
    n = 3
    sks = np.broadcast_to(np.frombuffer(sk, np.uint8), (n, len(sk)))
    pks = np.broadcast_to(np.frombuffer(pk, np.uint8), (n, len(pk)))
    msgs = [b"slh mesh %d" % i for i in range(n)]
    sigs = single.sign_batch(sks, msgs)  # deterministic variant
    assert [bytes(s) for s in sharded.sign_batch(sks, msgs)] == [
        bytes(s) for s in sigs
    ]
    assert np.asarray(sharded.verify_batch(pks, msgs, sigs)).all()
    assert not np.asarray(
        sharded.verify_batch(pks, [m + b"x" for m in msgs], sigs)
    ).any()


def test_messaging_constructs_with_mesh_devices(tmp_path):
    """Config knob reaches the providers through SecureMessaging."""
    from quantum_resistant_p2p_tpu.app.messaging import SecureMessaging
    from quantum_resistant_p2p_tpu.net.p2p_node import P2PNode

    node = P2PNode(node_id="mesh-test-node", host="127.0.0.1", port=0)
    m = SecureMessaging(node, backend="tpu", mesh_devices=NDEV)
    assert m.kem._mesh is not None and m.kem._mesh.size == NDEV
    assert m.signature._mesh is not None
