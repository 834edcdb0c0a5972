"""Bit-exactness of the fused Pallas RejNTTPoly pipeline (sig/mldsa_pallas.py).

Same testing strategy as tests/test_mlkem_pallas.py: the kernel body is a
pure tile-list function run EAGERLY here (interpret mode and XLA-CPU both
choke on the ~110k-op unrolled body); the native pallas_call is exercised
on the real chip by tools/full_bench.py config 4.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from quantum_resistant_p2p_tpu.core import keccak
from quantum_resistant_p2p_tpu.core.sortnet import (
    bitonic_sort_pairs,
    bitonic_sort_pairs_regs,
)
from quantum_resistant_p2p_tpu.sig import mldsa, mldsa_pallas


def test_sort_pairs_regs_matches_array_sort_pairs():
    rng = np.random.default_rng(4)
    n, lanes = 64, 5
    keys = rng.permutation(n * 3)[:n].astype(np.int32)  # unique
    keys = np.stack([rng.permutation(keys) for _ in range(lanes)], axis=1)  # (n, lanes)
    vals = rng.integers(0, 1 << 23, (n, lanes), dtype=np.int32)
    ks, vs = bitonic_sort_pairs_regs(
        [jnp.asarray(keys[i]) for i in range(n)],
        [jnp.asarray(vals[i]) for i in range(n)],
    )
    got_k = np.stack([np.asarray(k) for k in ks])
    got_v = np.stack([np.asarray(v) for v in vs])
    ref_k, ref_v = bitonic_sort_pairs(jnp.asarray(keys.T), jnp.asarray(vals.T))
    assert np.array_equal(got_k, np.asarray(ref_k).T)
    assert np.array_equal(got_v, np.asarray(ref_v).T)


def test_rej_ntt_tiles_bit_exact_vs_jnp_path():
    rng = np.random.default_rng(9)
    B = 32
    seeds = jnp.asarray(rng.integers(0, 256, (B, 34), dtype=np.uint8))
    ref = np.asarray(mldsa.rej_ntt_poly(seeds))

    block = keccak.pad_single_block(seeds, 168, 0x1F)
    ph, plo = keccak._bytes_to_words(block)
    out = mldsa_pallas._rej_ntt_tiles(
        [ph[:, w] for w in range(mldsa_pallas.RATE_WORDS)],
        [plo[:, w] for w in range(mldsa_pallas.RATE_WORDS)],
    )
    got = np.stack([np.asarray(o) for o in out], axis=-1)
    assert np.array_equal(got, ref)
    assert got.max() < mldsa.Q


@pytest.mark.parametrize("eta", [2, 4])
def test_rej_bounded_tiles_bit_exact_vs_jnp_path(eta):
    rng = np.random.default_rng(3 + eta)
    B = 32
    seeds = jnp.asarray(rng.integers(0, 256, (B, 66), dtype=np.uint8))
    ref = np.asarray(mldsa.rej_bounded_poly(eta, seeds))
    block = keccak.pad_single_block(seeds, 136, 0x1F)
    ph, plo = keccak._bytes_to_words(block)
    out = mldsa_pallas._rej_bounded_tiles(
        [ph[:, w] for w in range(mldsa_pallas.RB_RATE_WORDS)],
        [plo[:, w] for w in range(mldsa_pallas.RB_RATE_WORDS)],
        eta,
    )
    z = np.stack([np.asarray(o) for o in out], axis=-1)
    got = (2 - z % 5) % mldsa.Q if eta == 2 else (4 - z) % mldsa.Q
    assert np.array_equal(got, ref)


def test_ntt_tiles_bit_exact_vs_jnp():
    """VMEM NTT/invNTT tile functions (eager) against the jnp transforms,
    plus round-trip."""
    rng = np.random.default_rng(21)
    lanes = 7
    f = rng.integers(0, mldsa.Q, (lanes, 256), dtype=np.int32)
    tiles = [jnp.asarray(f[:, i]) for i in range(256)]

    fwd = mldsa_pallas.ntt_tiles(tiles)
    got_fwd = np.stack([np.asarray(t) for t in fwd], axis=-1)
    ref_fwd = np.asarray(mldsa.ntt(jnp.asarray(f)))
    assert np.array_equal(got_fwd, ref_fwd)

    inv = mldsa_pallas.ntt_inv_tiles(fwd)
    got_inv = np.stack([np.asarray(t) for t in inv], axis=-1)
    ref_inv = np.asarray(mldsa.ntt_inv(jnp.asarray(ref_fwd)))
    assert np.array_equal(got_inv, ref_inv)
    assert np.array_equal(got_inv, f)  # round-trip
