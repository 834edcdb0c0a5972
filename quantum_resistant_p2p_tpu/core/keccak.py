"""Batched Keccak-f[1600] permutation and SHA-3 / SHAKE sponges in JAX.

TPU-native design notes
-----------------------
TPUs have no 64-bit integer lanes, so each Keccak lane is emulated as a pair of
uint32 arrays ``(hi, lo)``; a 64-bit rotate becomes two shift/or pairs (or a
swap for rotations >= 32).  The 25-lane state is kept as two ``(..., 25)``
uint32 arrays so the whole sponge vectorises over an arbitrary leading batch
shape — thousands of independent hashes run in lockstep on the VPU.

All message and output lengths are static Python ints, so every function here
traces to a fixed-shape XLA program (jit/vmap/pjit friendly; no dynamic
shapes).  The 24 rounds run under ``lax.fori_loop`` with the round constants
held in a (24, 2) uint32 table; the rho/pi lane permutation is unrolled over
the 25 lanes with per-lane constant shifts.

Replaces (reference): the Keccak inside vendored liboqs — loaded via
``vendor/oqs.py:122-183`` and used by every KEM/signature in
``crypto/key_exchange.py`` / ``crypto/signatures.py``.  Oracle for tests:
``hashlib`` (sha3_256 / sha3_512 / shake_128 / shake_256).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# --------------------------------------------------------------------------
# Constants (computed, not transcribed, to avoid copy errors; verified against
# hashlib by tests/test_keccak.py).
# --------------------------------------------------------------------------

# Flat lane index convention: l = x + 5*y  (x = column, y = row).


def _rho_offsets() -> np.ndarray:
    r = np.zeros(25, dtype=np.int64)
    x, y = 1, 0
    for t in range(24):
        r[x + 5 * y] = ((t + 1) * (t + 2) // 2) % 64
        x, y = y, (2 * x + 3 * y) % 5
    return r


def _pi_source() -> np.ndarray:
    """src[dst] such that after rho+pi, out[dst] = rot(in[src], RHO[src])."""
    src = np.zeros(25, dtype=np.int64)
    for x in range(5):
        for y in range(5):
            dst = y + 5 * ((2 * x + 3 * y) % 5)
            src[dst] = x + 5 * y
    return src


def _round_constants() -> np.ndarray:
    """(24, 2) uint32: [:, 0] = hi word, [:, 1] = lo word."""

    def rc_bit(t: int) -> int:
        if t % 255 == 0:
            return 1
        reg = 1
        for _ in range(t % 255):
            reg <<= 1
            if reg & 0x100:
                reg ^= 0x171
        return reg & 1

    out = np.zeros((24, 2), dtype=np.uint64)
    for ir in range(24):
        rc = 0
        for j in range(7):
            if rc_bit(j + 7 * ir):
                rc |= 1 << (2**j - 1)
        out[ir, 0] = rc >> 32
        out[ir, 1] = rc & 0xFFFFFFFF
    return out.astype(np.uint32)


_RHO = _rho_offsets()
_PI_SRC = _pi_source()
_RC = _round_constants()


def _rotl_pair(hi, lo, n: int):
    """Rotate-left a (hi, lo) uint32 pair by constant n (0..63)."""
    n = n % 64
    if n == 0:
        return hi, lo
    if n >= 32:
        hi, lo = lo, hi
        n -= 32
        if n == 0:
            return hi, lo
    return (
        (hi << n) | (lo >> (32 - n)),
        (lo << n) | (hi >> (32 - n)),
    )


def keccak_f1600(hi: jax.Array, lo: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Apply Keccak-f[1600] to a batched state.

    Args:
      hi, lo: uint32 arrays of shape (..., 25) — high/low words of the 25
        64-bit lanes, flat-indexed as l = x + 5*y.
    """
    rc = jnp.asarray(_RC)

    def round_fn(i, state):
        hi, lo = state
        # ---- theta -------------------------------------------------------
        h5 = hi.reshape(hi.shape[:-1] + (5, 5))  # [..., y, x]
        l5 = lo.reshape(lo.shape[:-1] + (5, 5))
        ch = h5[..., 0, :] ^ h5[..., 1, :] ^ h5[..., 2, :] ^ h5[..., 3, :] ^ h5[..., 4, :]
        cl = l5[..., 0, :] ^ l5[..., 1, :] ^ l5[..., 2, :] ^ l5[..., 3, :] ^ l5[..., 4, :]
        # C[x+1] rotated left by 1
        r1h = (ch << 1) | (cl >> 31)
        r1l = (cl << 1) | (ch >> 31)
        dh = jnp.roll(ch, 1, axis=-1) ^ jnp.roll(r1h, -1, axis=-1)
        dl = jnp.roll(cl, 1, axis=-1) ^ jnp.roll(r1l, -1, axis=-1)
        h5 = h5 ^ dh[..., None, :]
        l5 = l5 ^ dl[..., None, :]
        hi = h5.reshape(hi.shape)
        lo = l5.reshape(lo.shape)
        # ---- rho + pi (unrolled: constant shift per lane) ----------------
        bh, bl = [], []
        for dst in range(25):
            src = int(_PI_SRC[dst])
            rh, rl = _rotl_pair(hi[..., src], lo[..., src], int(_RHO[src]))
            bh.append(rh)
            bl.append(rl)
        hi = jnp.stack(bh, axis=-1)
        lo = jnp.stack(bl, axis=-1)
        # ---- chi ---------------------------------------------------------
        h5 = hi.reshape(hi.shape[:-1] + (5, 5))
        l5 = lo.reshape(lo.shape[:-1] + (5, 5))
        h5 = h5 ^ (~jnp.roll(h5, -1, axis=-1) & jnp.roll(h5, -2, axis=-1))
        l5 = l5 ^ (~jnp.roll(l5, -1, axis=-1) & jnp.roll(l5, -2, axis=-1))
        hi = h5.reshape(hi.shape)
        lo = l5.reshape(lo.shape)
        # ---- iota --------------------------------------------------------
        hi = hi.at[..., 0].set(hi[..., 0] ^ rc[i, 0])
        lo = lo.at[..., 0].set(lo[..., 0] ^ rc[i, 1])
        return hi, lo

    return lax.fori_loop(0, 24, round_fn, (hi, lo))


# --------------------------------------------------------------------------
# Byte <-> lane packing (little-endian within each 64-bit lane).
# --------------------------------------------------------------------------


def _bytes_to_words(block: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., 8*n) uint8 -> ((..., n), (..., n)) uint32 hi/lo lane words."""
    b = block.astype(jnp.uint32).reshape(block.shape[:-1] + (-1, 8))
    lo = b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)
    hi = b[..., 4] | (b[..., 5] << 8) | (b[..., 6] << 16) | (b[..., 7] << 24)
    return hi, lo


def pad_single_block(data: jax.Array, rate: int, ds_byte: int) -> jax.Array:
    """Keccak-pad a sub-rate message to one ``rate``-byte block.

    (..., L) uint8 with L < rate -> (..., rate) uint8: message, then the
    domain-separation byte, zeros, and 0x80 in the final byte.  Single
    source of truth for callers that feed one-block sponges directly to a
    Pallas kernel (kem/mlkem.py's fused SampleNTT path) instead of going
    through :func:`sponge`.
    """
    msg_len = data.shape[-1]
    assert msg_len < rate, (msg_len, rate)
    block = jnp.zeros(data.shape[:-1] + (rate,), jnp.uint8)
    block = block.at[..., :msg_len].set(jnp.asarray(data, jnp.uint8))
    block = block.at[..., msg_len].set(jnp.uint8(ds_byte))
    return block.at[..., rate - 1].set(block[..., rate - 1] | jnp.uint8(0x80))


def seed_block_words(seeds: jax.Array, rate: int, ds_byte: int):
    """Flatten, pad, and word-transpose XOF seeds for a fused sampler kernel.

    (..., L) uint8 seeds -> ((rate//8, B), (rate//8, B)) uint32 hi/lo lane
    words with the batch flattened onto the minor axis, plus the original
    batch shape — the input convention of keccak_pallas.sampler_call.
    """
    batch = seeds.shape[:-1]
    b = int(np.prod(batch)) if batch else 1
    flat = jnp.asarray(seeds, jnp.uint8).reshape(b, seeds.shape[-1])
    ph, plo = _bytes_to_words(pad_single_block(flat, rate, ds_byte))
    return ph.T, plo.T, batch


def _words_to_bytes(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """((..., n), (..., n)) uint32 -> (..., 8*n) uint8."""
    parts = [
        lo & 0xFF, (lo >> 8) & 0xFF, (lo >> 16) & 0xFF, (lo >> 24) & 0xFF,
        hi & 0xFF, (hi >> 8) & 0xFF, (hi >> 16) & 0xFF, (hi >> 24) & 0xFF,
    ]
    out = jnp.stack(parts, axis=-1).astype(jnp.uint8)
    return out.reshape(out.shape[:-2] + (-1,))


# --------------------------------------------------------------------------
# Sponge
# --------------------------------------------------------------------------


def _use_pallas() -> bool:
    """Pallas kernels on a TPU, the jnp twins elsewhere (tests run on CPU).

    Decided from the platform alone: a backend that cannot be probed
    raises here rather than quietly selecting the jnp path."""
    return jax.default_backend() == "tpu"


def sponge(data: jax.Array, rate: int, ds_byte: int, out_len: int) -> jax.Array:
    """Keccak sponge with static lengths.

    Args:
      data: (..., L) uint8 message (L static; any leading batch shape).
      rate: rate in bytes (168 SHAKE128, 136 SHAKE256/SHA3-256, 72 SHA3-512).
      ds_byte: domain-separation byte (0x1F for SHAKE, 0x06 for SHA3).
      out_len: number of output bytes (static).

    Returns:
      (..., out_len) uint8.
    """
    data = jnp.asarray(data, dtype=jnp.uint8)
    batch = data.shape[:-1]
    msg_len = data.shape[-1]
    nblocks = msg_len // rate + 1
    padded_len = nblocks * rate

    padded = jnp.zeros(batch + (padded_len,), dtype=jnp.uint8)
    padded = lax.dynamic_update_slice_in_dim(padded, data, 0, axis=-1) if msg_len else padded
    padded = padded.at[..., msg_len].set(jnp.uint8(ds_byte))
    padded = padded.at[..., padded_len - 1].set(padded[..., padded_len - 1] | jnp.uint8(0x80))

    out_nblocks_total = -(-out_len // rate)
    if nblocks + out_nblocks_total <= 16 and _use_pallas():
        from . import keccak_pallas  # deferred: pallas import

        if nblocks + out_nblocks_total <= keccak_pallas.MAX_BLOCKS_FUSED:
            b = int(np.prod(batch)) if batch else 1
            ph, plo = _bytes_to_words(padded.reshape(b, padded_len))
            oh, ol = keccak_pallas.sponge_words(
                ph.T, plo.T, rate_words=rate // 8, n_abs=nblocks,
                n_sq=out_nblocks_total,
            )
            out = _words_to_bytes(oh.T, ol.T)
            return out.reshape(batch + (-1,))[..., :out_len]

    hi = jnp.zeros(batch + (25,), dtype=jnp.uint32)
    lo = jnp.zeros(batch + (25,), dtype=jnp.uint32)
    nwords = rate // 8

    def absorb(state, block):
        hi, lo = state
        bh, bl = _bytes_to_words(block)
        hi = hi.at[..., :nwords].set(hi[..., :nwords] ^ bh)
        lo = lo.at[..., :nwords].set(lo[..., :nwords] ^ bl)
        return keccak_f1600(hi, lo)

    # Unroll short sponges (lower dispatch overhead); lax.scan long ones so
    # graph size / compile time stays O(1) in message length — FrodoKEM and
    # HQC absorb/squeeze hundreds of blocks.
    if nblocks <= 4:
        for b in range(nblocks):
            hi, lo = absorb((hi, lo), padded[..., b * rate : (b + 1) * rate])
    else:
        blocks = jnp.moveaxis(
            padded.reshape(batch + (nblocks, rate)), -2, 0
        )  # (nblocks, ..., rate)
        (hi, lo), _ = lax.scan(lambda s, blk: (absorb(s, blk), None), (hi, lo), blocks)

    out_nblocks = -(-out_len // rate)
    if out_nblocks <= 4:
        out_blocks = []
        for b in range(out_nblocks):
            out_blocks.append(_words_to_bytes(hi[..., :nwords], lo[..., :nwords]))
            if b + 1 < out_nblocks:
                hi, lo = keccak_f1600(hi, lo)
        out = (
            jnp.concatenate(out_blocks, axis=-1) if len(out_blocks) > 1 else out_blocks[0]
        )
    else:
        def squeeze(state, _):
            hi, lo = state
            blk = _words_to_bytes(hi[..., :nwords], lo[..., :nwords])
            return keccak_f1600(hi, lo), blk

        _, blks = lax.scan(squeeze, (hi, lo), None, length=out_nblocks)
        out = jnp.moveaxis(blks, 0, -2).reshape(batch + (out_nblocks * rate,))
    return out[..., :out_len]


def sponge_varlen(data: jax.Array, lengths: jax.Array, rate: int, ds_byte: int,
                  out_len: int) -> jax.Array:
    """Keccak sponge over per-lane VARIABLE-length messages.

    The fixed-shape :func:`sponge` bakes the message length into the traced
    program, which is right for every crypto-internal hash (their lengths
    are parameters of the algorithm).  The fused handshake programs
    (``fused_ops``) sign protocol transcripts whose JSON tail — peer ids,
    timestamp repr — differs per lane, so the absorb must take the true
    byte length as a traced operand:

    * ``data`` is a (..., LMAX) uint8 buffer; bytes at index >= ``lengths``
      are ignored (masked to zero before padding, so callers may leave
      garbage there).
    * the domain byte lands at index ``lengths`` and 0x80 at the end of the
      block containing it, both via one-hot selects;
    * the absorb scans over the maximal block count, applying the
      permutation result only to lanes whose message reaches that block —
      lanes with shorter messages carry their final state through unchanged.

    Output matches ``hashlib`` byte-for-byte for every length <= LMAX
    (tests/test_keccak.py sweeps the block boundaries).
    """
    data = jnp.asarray(data, jnp.uint8)
    batch = data.shape[:-1]
    lmax = data.shape[-1]
    mlen = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), batch)
    nblocks = lmax // rate + 1  # always room for the ds byte when mlen == lmax
    padded_len = nblocks * rate
    idx = jnp.arange(padded_len, dtype=jnp.int32)
    buf = jnp.zeros(batch + (padded_len,), dtype=jnp.uint8)
    buf = lax.dynamic_update_slice_in_dim(buf, data, 0, axis=-1) if lmax else buf
    ml = mlen[..., None]
    buf = jnp.where(idx < ml, buf, jnp.uint8(0))
    buf = buf ^ jnp.where(idx == ml, jnp.uint8(ds_byte), jnp.uint8(0))
    last_block = mlen // rate  # block index holding the ds byte
    fin = (last_block[..., None] + 1) * rate - 1
    # ds and 0x80 share a byte only when mlen % rate == rate-1; their bits
    # are disjoint so xor == the spec's or
    buf = buf ^ jnp.where(idx == fin, jnp.uint8(0x80), jnp.uint8(0))

    nwords = rate // 8
    hi = jnp.zeros(batch + (25,), dtype=jnp.uint32)
    lo = jnp.zeros(batch + (25,), dtype=jnp.uint32)
    blocks = jnp.moveaxis(buf.reshape(batch + (nblocks, rate)), -2, 0)

    def absorb(state, xs):
        hi, lo = state
        blk, i = xs
        bh, bl = _bytes_to_words(blk)
        nh = hi.at[..., :nwords].set(hi[..., :nwords] ^ bh)
        nl = lo.at[..., :nwords].set(lo[..., :nwords] ^ bl)
        nh, nl = keccak_f1600(nh, nl)
        take = (i <= last_block)[..., None]
        return (jnp.where(take, nh, hi), jnp.where(take, nl, lo)), None

    (hi, lo), _ = lax.scan(
        absorb, (hi, lo), (blocks, jnp.arange(nblocks, dtype=jnp.int32))
    )

    out_nblocks = -(-out_len // rate)
    out_blocks = []
    for b in range(out_nblocks):
        out_blocks.append(_words_to_bytes(hi[..., :nwords], lo[..., :nwords]))
        if b + 1 < out_nblocks:
            hi, lo = keccak_f1600(hi, lo)
    out = (
        jnp.concatenate(out_blocks, axis=-1) if len(out_blocks) > 1 else out_blocks[0]
    )
    return out[..., :out_len]


@functools.partial(jax.jit, static_argnums=(2,))
def shake256_varlen(data: jax.Array, lengths: jax.Array, out_len: int) -> jax.Array:
    """(..., LMAX) uint8 + (...,) int32 true lengths -> (..., out_len) uint8."""
    return sponge_varlen(data, lengths, 136, 0x1F, out_len)


@functools.partial(jax.jit, static_argnums=(1,))
def shake128(data: jax.Array, out_len: int) -> jax.Array:
    return sponge(data, 168, 0x1F, out_len)


@functools.partial(jax.jit, static_argnums=(1,))
def shake256(data: jax.Array, out_len: int) -> jax.Array:
    return sponge(data, 136, 0x1F, out_len)


@jax.jit
def sha3_256(data: jax.Array) -> jax.Array:
    return sponge(data, 136, 0x06, 32)


@jax.jit
def sha3_512(data: jax.Array) -> jax.Array:
    return sponge(data, 72, 0x06, 64)
