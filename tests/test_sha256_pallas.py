"""Bit-exactness of the Pallas SHA-256 compression kernel body.

Same strategy as tests/test_mlkem_pallas.py: the kernel body is a pure
tile-list function run eagerly here; the native pallas_call is exercised
on the chip by the SPHINCS+ sections of tools/full_bench.py.
"""

import hashlib

import jax.numpy as jnp
import numpy as np

from quantum_resistant_p2p_tpu.core import keccak, sha256, sha256_pallas


def test_compress_tiles_bit_exact_vs_jnp():
    rng = np.random.default_rng(6)
    B = 64
    state = jnp.asarray(rng.integers(0, 2**32, (B, 8), dtype=np.uint32))
    block = jnp.asarray(rng.integers(0, 256, (B, 64), dtype=np.uint8))
    ref = np.asarray(sha256.compress(state, block))
    words = [state.T[i] for i in range(8)] + [
        sha256._block_words(block).T[i] for i in range(16)
    ]
    out = sha256_pallas._compress_tiles(words)
    got = np.stack([np.asarray(o) for o in out], axis=-1)
    assert np.array_equal(got, ref)


def test_compress_kernel_split_semantics():
    # Exercises _compress_kernel's 12/12 hi/lo word split, ref indexing, and
    # the int32 output cast with numpy arrays standing in for VMEM refs.
    # (Pallas interpret mode is unusable here: it re-jits the unrolled body
    # and XLA-CPU's LLVM backend chokes — the same pathology documented in
    # tests/test_mlkem_pallas.py, observed even under jax.disable_jit.)
    rng = np.random.default_rng(8)
    TS, TL = 8, 128
    state = jnp.asarray(rng.integers(0, 2**32, (TS * TL, 8), dtype=np.uint32))
    block = jnp.asarray(rng.integers(0, 256, (TS * TL, 64), dtype=np.uint8))
    ref = np.asarray(sha256.compress(state, block))
    words = jnp.concatenate(
        [state.T, sha256._block_words(block).T], axis=0
    ).reshape(24, TS, TL)
    out_ref = np.zeros((8, TS, TL), np.int32)
    sha256_pallas._compress_kernel(
        np.asarray(words[:12]), np.asarray(words[12:]), out_ref
    )
    got = out_ref.reshape(8, TS * TL).T.astype(np.uint32)
    assert np.array_equal(got, ref)


def test_compress_gate_routes_through_kernel(monkeypatch):
    # The production compress() gate: flat batch >= _PALLAS_MIN_BATCH with
    # the pallas flag on must produce identical state updates through the
    # transpose/reshape round-trip.
    rng = np.random.default_rng(9)
    B = 300
    state = jnp.asarray(rng.integers(0, 2**32, (B, 8), dtype=np.uint32))
    block = jnp.asarray(rng.integers(0, 256, (B, 64), dtype=np.uint8))
    ref = np.asarray(sha256.compress(state, block))
    monkeypatch.setattr(keccak, "_use_pallas", lambda: True)
    def tile_compress_words(sw, bw):
        # stand-in with the real kernel body, skipping only pallas_call
        out = sha256_pallas._compress_tiles(
            [sw[i] for i in range(8)] + [bw[i] for i in range(16)]
        )
        return jnp.stack(out)

    monkeypatch.setattr(sha256_pallas, "compress_words", tile_compress_words)
    got = np.asarray(sha256.compress(state, block))
    assert np.array_equal(got, ref)


def test_full_digest_still_hashlib_anchored():
    rng = np.random.default_rng(7)
    msg = rng.integers(0, 256, (5, 117), dtype=np.uint8)
    d = np.asarray(sha256.sha256(jnp.asarray(msg)))
    for i in range(5):
        assert bytes(d[i]) == hashlib.sha256(msg[i].tobytes()).digest()
