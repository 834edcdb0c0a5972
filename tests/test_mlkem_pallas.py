"""Bit-exactness of the fused Pallas SampleNTT pipeline (kem/mlkem_pallas.py).

The kernel body is a pure function over lane-word tiles
(``_sample_ntt_tiles``), so it runs here EAGERLY on plain CPU arrays —
interpret mode executes the ~57k-op body orders of magnitude too slowly,
and XLA-CPU's LLVM backend chokes compiling the fully-unrolled graph.
Native Mosaic compilation + execution of the full ``pallas_call`` is
exercised on the real chip by bench.py / tools/full_bench.py (and was
verified bit-exact vs the jnp path for B=1500 on TPU v5e).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from quantum_resistant_p2p_tpu.core import keccak
from quantum_resistant_p2p_tpu.core.sortnet import bitonic_sort, bitonic_sort_regs
from quantum_resistant_p2p_tpu.kem import mlkem, mlkem_pallas


def test_sort_regs_matches_array_sort():
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1 << 23, (32, 7), dtype=np.int32)
    regs = bitonic_sort_regs([jnp.asarray(x[i]) for i in range(32)])
    got = np.stack([np.asarray(r) for r in regs])
    ref = np.asarray(bitonic_sort(jnp.asarray(x.T))).T
    assert np.array_equal(got, ref)


def test_sample_ntt_tiles_bit_exact_vs_jnp_path():
    rng = np.random.default_rng(7)
    B = 64
    seeds = jnp.asarray(rng.integers(0, 256, (B, 34), dtype=np.uint8))
    ref = np.asarray(mlkem.sample_ntt(seeds))

    # Same padded-block prep as the production sample_ntt pallas branch.
    block = keccak.pad_single_block(seeds, 168, 0x1F)
    ph, plo = keccak._bytes_to_words(block)
    out = mlkem_pallas._sample_ntt_tiles(
        [ph[:, w] for w in range(mlkem_pallas.RATE_WORDS)],
        [plo[:, w] for w in range(mlkem_pallas.RATE_WORDS)],
    )
    got = np.stack([np.asarray(o) for o in out], axis=-1)
    assert np.array_equal(got, ref)
    # Sanity: accepted coefficients are reduced mod q.
    assert got.max() < mlkem.Q


@pytest.mark.parametrize("eta", [2, 3])
def test_cbd_tiles_bit_exact_vs_jnp_path(eta):
    # eta=3 exercises the two-block squeeze (ML-KEM-512's eta1).
    rng = np.random.default_rng(10 + eta)
    B = 48
    s = jnp.asarray(rng.integers(0, 256, (B, 32), dtype=np.uint8))
    n_consts = np.arange(2, dtype=np.uint8)
    ref = np.asarray(mlkem._prf_cbd(s, n_consts, eta))
    seeds = mlkem._prf_seeds(s, n_consts)
    block = keccak.pad_single_block(seeds.reshape(-1, 33), 136, 0x1F)
    ph, plo = keccak._bytes_to_words(block)
    out = mlkem_pallas._cbd_tiles(
        [ph[:, w] for w in range(mlkem_pallas.CBD_RATE_WORDS)],
        [plo[:, w] for w in range(mlkem_pallas.CBD_RATE_WORDS)],
        eta,
    )
    got = np.stack([np.asarray(o) for o in out], axis=-1).reshape(B, 2, 256)
    assert np.array_equal(got, ref)


@pytest.mark.parametrize("ds", ["ML-KEM-512", "ML-KEM-768", "ML-KEM-1024"])
def test_kem_roundtrip_small_batch(ds):
    rng = np.random.default_rng(11)
    kg, enc, dec = mlkem.get(ds)
    d, z, m = (
        jnp.asarray(rng.integers(0, 256, (3, 32), dtype=np.uint8)) for _ in range(3)
    )
    ek, dk = kg(d, z)
    key, ct = enc(ek, m)
    key2 = dec(dk, ct)
    assert np.array_equal(np.asarray(key), np.asarray(key2))


def test_sliced_dispatch_pads_and_trims_non_divisible_tail():
    from quantum_resistant_p2p_tpu.provider.base import sliced_dispatch

    calls = []

    def fn(a, b):
        calls.append(a.shape[0])
        return a * 2, a + b

    rng = np.random.default_rng(5)
    a = rng.integers(0, 100, (11, 3), dtype=np.int64)
    b = rng.integers(0, 100, (11, 3), dtype=np.int64)
    x, y = sliced_dispatch(fn, 4, a, b)
    assert calls == [4, 4, 4]  # tail of 3 padded to a full compiled shape
    assert np.array_equal(x, a * 2) and np.array_equal(y, a + b)
    # Single-output fn, exactly divisible: no padding branch.
    calls.clear()
    z = sliced_dispatch(lambda a: a - 1, 4, a[:8])
    assert calls == [] and np.array_equal(z, a[:8] - 1)


def test_sliced_dispatch_through_kem_provider_past_knee(monkeypatch):
    # Drive a real TPU-backend KEM provider through a batch bigger than its
    # dispatch ceiling (and not a multiple of it), so the pad-and-trim path
    # runs inside the production keygen/encaps/decaps wrappers.
    from quantum_resistant_p2p_tpu.provider import registry

    algo = registry.get_kem("ML-KEM-512", backend="tpu")
    monkeypatch.setattr(algo, "_max_dispatch", 4, raising=True)
    n = 11
    ek, dk = algo.generate_keypair_batch(n)
    ct, key = algo.encapsulate_batch(ek)
    key2 = algo.decapsulate_batch(dk, ct)
    assert key.shape == (n, 32) and np.array_equal(key, key2)
