"""Bit-exactness of the Pallas SHA-512 compression kernel body.

Same strategy as tests/test_sha256_pallas.py: the kernel body is a pure
tile-list function run eagerly here; the native pallas_call is exercised
on the chip by the SPHINCS+ 192/256 sections of tools/full_bench.py.
"""

import hashlib

import jax.numpy as jnp
import numpy as np

from quantum_resistant_p2p_tpu.core import keccak, sha512, sha512_pallas


def _rand_state_block(seed, b):
    rng = np.random.default_rng(seed)
    sh = jnp.asarray(rng.integers(0, 2**32, (b, 8), dtype=np.uint32))
    sl = jnp.asarray(rng.integers(0, 2**32, (b, 8), dtype=np.uint32))
    block = jnp.asarray(rng.integers(0, 256, (b, 128), dtype=np.uint8))
    return sh, sl, block


def test_compress_tiles_bit_exact_vs_jnp():
    sh, sl, block = _rand_state_block(6, 64)
    rh, rl = sha512.compress((sh, sl), block)
    bh, bl = sha512._block_words(block)
    words = [(sh.T[i], sl.T[i]) for i in range(8)] + [
        (bh.T[i], bl.T[i]) for i in range(16)
    ]
    out = sha512_pallas._compress_tiles(words)
    got_h = np.stack([np.asarray(o[0]) for o in out], axis=-1)
    got_l = np.stack([np.asarray(o[1]) for o in out], axis=-1)
    assert np.array_equal(got_h, np.asarray(rh))
    assert np.array_equal(got_l, np.asarray(rl))


def test_compress_kernel_split_semantics():
    # Exercises _compress_kernel's 24/24 transport split, ref indexing, and
    # the int32 output cast with numpy arrays standing in for VMEM refs
    # (interpret mode unusable — see tests/test_sha256_pallas.py).
    TS, TL = 8, 128
    sh, sl, block = _rand_state_block(8, TS * TL)
    rh, rl = sha512.compress((sh, sl), block)
    bh, bl = sha512._block_words(block)
    in_hi = jnp.concatenate([sh.T, sl.T, bh.T[:8]], axis=0).reshape(24, TS, TL)
    in_lo = jnp.concatenate([bh.T[8:], bl.T], axis=0).reshape(24, TS, TL)
    out_ref = np.zeros((16, TS, TL), np.int32)
    sha512_pallas._compress_kernel(np.asarray(in_hi), np.asarray(in_lo), out_ref)
    got_h = out_ref[:8].reshape(8, TS * TL).T.astype(np.uint32)
    got_l = out_ref[8:].reshape(8, TS * TL).T.astype(np.uint32)
    assert np.array_equal(got_h, np.asarray(rh))
    assert np.array_equal(got_l, np.asarray(rl))


def test_compress_gate_routes_through_kernel(monkeypatch):
    # The production compress() gate: flat batch >= _PALLAS_MIN_BATCH with
    # the pallas flag on must produce identical state updates through the
    # transpose/reshape round-trip.
    sh, sl, block = _rand_state_block(9, 300)
    rh, rl = (np.asarray(x) for x in sha512.compress((sh, sl), block))
    monkeypatch.setattr(keccak, "_use_pallas", lambda: True)

    def tile_compress_words(swh, swl, bwh, bwl):
        out = sha512_pallas._compress_tiles(
            [(swh[i], swl[i]) for i in range(8)]
            + [(bwh[i], bwl[i]) for i in range(16)]
        )
        return jnp.stack([o[0] for o in out]), jnp.stack([o[1] for o in out])

    monkeypatch.setattr(sha512_pallas, "compress_words", tile_compress_words)
    gh, gl = (np.asarray(x) for x in sha512.compress((sh, sl), block))
    assert np.array_equal(gh, rh)
    assert np.array_equal(gl, rl)


def test_full_digest_still_hashlib_anchored():
    rng = np.random.default_rng(7)
    msg = rng.integers(0, 256, (5, 211), dtype=np.uint8)
    d = np.asarray(sha512.sha512(jnp.asarray(msg)))
    for i in range(5):
        assert bytes(d[i]) == hashlib.sha512(msg[i].tobytes()).digest()
