"""Pallas TPU kernel for the Keccak sponge — the production hash fast path.

Why a kernel at all: the pure-jnp sponge (core/keccak.py) materialises the
full batched 25-lane state between every one of the 24 rounds, so one
ML-KEM-768 encaps batch reads/writes ~38 MB of HBM per op (measured: 155 GB
per 4096-batch, wholly memory-bound).  This kernel keeps the entire state in
registers/VMEM for the whole absorb-permute-squeeze pipeline; HBM traffic
drops to the message bytes in and digest bytes out.

Layout: batch lives on the two *minor* dimensions — each of the 50 uint32
state words is an ``(8, 128)`` tile (sublanes x lanes, exactly one 32-bit
vector register) across 1024 sponge instances, so theta/chi xors and the
per-lane constant rotations are full-width VPU ops with zero register waste
(a ``(1, B)`` row layout measured 8x slower: 7/8 of every vreg idle).  The
24 rounds and the (static) absorb/squeeze block loops are fully unrolled at
trace time; rho/pi/iota constants are Python ints baked into the program.

Used by core/keccak.py when running on TPU for sponges up to
``MAX_BLOCKS_FUSED`` total blocks (covers every ML-KEM / ML-DSA / SLH-DSA
call site); longer sponges (FrodoKEM/HQC matrix expansion) stay on the
lax.scan jnp path.  Oracle: hashlib via tests/test_keccak.py, which runs
this kernel in interpret mode on CPU and natively on TPU.

Replaces (reference): the Keccak core inside vendored liboqs
(vendor/oqs.py:122-183), reached from every KEM/signature hot call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .keccak import _PI_SRC, _RC, _RHO

#: sponges with more than this many total (absorb + squeeze) blocks fall back
#: to the jnp scan path — the fully-unrolled kernel would compile too slowly.
MAX_BLOCKS_FUSED = 16

#: sponges per grid step: 8 sublanes x 128 lanes = one vreg per state word.
_TS, _TL = 8, 128
BT = _TS * _TL


# The round function is written in lax primitives, not jnp operators: each
# jnp operator on a tracer goes through a jit of its own, and a sign
# program traces ~10k of them per permutation -- most of its trace time.
# The jaxpr is the same either way.
_xor = lax.bitwise_xor
_and = lax.bitwise_and
_or = lax.bitwise_or
_not = lax.bitwise_not


def _rotl(hi, lo, n: int):
    n %= 64
    if n == 0:
        return hi, lo
    if n >= 32:
        hi, lo = lo, hi
        n -= 32
        if n == 0:
            return hi, lo
    up, down = np.uint32(n), np.uint32(32 - n)
    return (
        _or(lax.shift_left(hi, up), lax.shift_right_logical(lo, down)),  # qrkernel: wrapping — uint32 lane words: bits shifted past 32 drop by design, the rotation recovers them from the partner word
        _or(lax.shift_left(lo, up), lax.shift_right_logical(hi, down)),  # qrkernel: wrapping — same wrap-by-design rotation, low word
    )


def _f1600(sh: list, sl: list) -> tuple[list, list]:
    """One Keccak-f[1600] permutation over 50 (8, 128) uint32 tiles."""
    for rnd in range(24):
        # theta
        ch = [_xor(_xor(_xor(_xor(sh[x], sh[x + 5]), sh[x + 10]), sh[x + 15]), sh[x + 20])
              for x in range(5)]
        cl = [_xor(_xor(_xor(_xor(sl[x], sl[x + 5]), sl[x + 10]), sl[x + 15]), sl[x + 20])
              for x in range(5)]
        for x in range(5):
            rh, rl = _rotl(ch[(x + 1) % 5], cl[(x + 1) % 5], 1)
            dh, dl = _xor(ch[(x + 4) % 5], rh), _xor(cl[(x + 4) % 5], rl)
            for y in range(5):
                sh[x + 5 * y] = _xor(sh[x + 5 * y], dh)
                sl[x + 5 * y] = _xor(sl[x + 5 * y], dl)
        # rho + pi
        bh, bl = [None] * 25, [None] * 25
        for dst in range(25):
            src = int(_PI_SRC[dst])
            bh[dst], bl[dst] = _rotl(sh[src], sl[src], int(_RHO[src]))
        # chi
        for y in range(5):
            row_h = [bh[x + 5 * y] for x in range(5)]
            row_l = [bl[x + 5 * y] for x in range(5)]
            for x in range(5):
                sh[x + 5 * y] = _xor(row_h[x], _and(_not(row_h[(x + 1) % 5]), row_h[(x + 2) % 5]))
                sl[x + 5 * y] = _xor(row_l[x], _and(_not(row_l[(x + 1) % 5]), row_l[(x + 2) % 5]))
        # iota
        sh[0] = _xor(sh[0], np.uint32(int(_RC[rnd, 0])))
        sl[0] = _xor(sl[0], np.uint32(int(_RC[rnd, 1])))
    return sh, sl


def absorb_block(in_hi: list, in_lo: list, rate_words: int) -> tuple[list, list]:
    """Single-block absorb: XOR ``rate_words`` lane words into a zero state
    and permute.  Shared preamble of the fused sampler kernels."""
    zero = jnp.zeros_like(in_hi[0])
    sh = [zero] * 25
    sl = [zero] * 25
    for w in range(rate_words):
        sh[w] = sh[w] ^ in_hi[w]
        sl[w] = sl[w] ^ in_lo[w]
    return _f1600(sh, sl)


def block_bytes(sh: list, sl: list, rate_words: int) -> list:
    """Extract the ``8 * rate_words`` rate bytes of a sponge block.

    Input: 25-element hi/lo lane-word tile lists; output: uint32 tiles with
    one byte each (little-endian within each 64-bit lane, matching
    ``core.keccak._words_to_bytes``).  Shared by the fused sampler kernels
    (kem/mlkem_pallas.py, sig/mldsa_pallas.py).
    """
    byts = []
    for w in range(rate_words):
        for b in range(8):
            word = sl[w] if b < 4 else sh[w]
            byts.append(_and(lax.shift_right_logical(word, np.uint32(8 * (b % 4))),
                             np.uint32(0xFF)))
    return byts


def sampler_call(kernel, rate_words: int, n_out: int, in_hi: jax.Array,
                 in_lo: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Shared launcher for fused sampler kernels: words in, int32 regs out.

    Args:
      kernel: pallas kernel (in_hi_ref, in_lo_ref, out_ref) over
        (rate_words|n_out, 8, 128) uint32/int32 blocks.
      in_hi/in_lo: (rate_words, B) uint32 padded seed-block lane words,
        batch minor (B need not be a multiple of the 1024-sponge tile).

    Returns:
      (n_out, B) int32.
    """
    in_words, b = in_hi.shape
    assert in_words == rate_words
    bp = -(-b // BT) * BT
    if bp != b:
        pad = ((0, 0), (0, bp - b))
        in_hi = jnp.pad(in_hi, pad)
        in_lo = jnp.pad(in_lo, pad)
    in_hi = in_hi.reshape(in_words, bp // _TL, _TL)
    in_lo = in_lo.reshape(in_words, bp // _TL, _TL)
    out = pl.pallas_call(
        kernel,
        grid=(bp // BT,),
        in_specs=[
            pl.BlockSpec((in_words, _TS, _TL), lambda i: (0, i, 0)),
            pl.BlockSpec((in_words, _TS, _TL), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((n_out, _TS, _TL), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_out, bp // _TL, _TL), jnp.int32),
        interpret=interpret,
    )(in_hi, in_lo)
    return out.reshape(n_out, bp)[:, :b]


def _sponge_kernel(in_hi_ref, in_lo_ref, out_hi_ref, out_lo_ref,
                   *, rate_words: int, n_abs: int, n_sq: int):
    zero = jnp.zeros((_TS, _TL), jnp.uint32)
    sh = [zero] * 25
    sl = [zero] * 25
    for blk in range(n_abs):
        for w in range(rate_words):
            r = blk * rate_words + w
            sh[w] = sh[w] ^ in_hi_ref[r]
            sl[w] = sl[w] ^ in_lo_ref[r]
        sh, sl = _f1600(sh, sl)
    for blk in range(n_sq):
        for w in range(rate_words):
            r = blk * rate_words + w
            out_hi_ref[r] = sh[w]
            out_lo_ref[r] = sl[w]
        if blk + 1 < n_sq:
            sh, sl = _f1600(sh, sl)


@functools.partial(jax.jit, static_argnames=("rate_words", "n_abs", "n_sq", "interpret"))
def sponge_words(in_hi: jax.Array, in_lo: jax.Array, *, rate_words: int,
                 n_abs: int, n_sq: int, interpret: bool = False):
    """Padded-message sponge over word-transposed batches.

    Args:
      in_hi/in_lo: (n_abs*rate_words, B) uint32 — padded message lane words,
        batch on the minor axis (B need not be a multiple of the tile).
      rate_words: sponge rate in 64-bit lanes (21 SHAKE128, 17 SHAKE256,
        17 SHA3-256, 9 SHA3-512).
      n_abs/n_sq: number of absorb / squeeze blocks (static).

    Returns:
      (out_hi, out_lo): (n_sq*rate_words, B) uint32 squeezed lane words.
    """
    in_words, b = in_hi.shape
    assert in_words == n_abs * rate_words
    bp = -(-b // BT) * BT
    if bp != b:
        pad = ((0, 0), (0, bp - b))
        in_hi = jnp.pad(in_hi, pad)
        in_lo = jnp.pad(in_lo, pad)
    # (W, B) -> (W, B/128, 128): sponge j*128+l sits at [:, j, l]; a grid step
    # covers 8 consecutive j (one full vreg tile per state word).
    in_hi = in_hi.reshape(in_words, bp // _TL, _TL)
    in_lo = in_lo.reshape(in_words, bp // _TL, _TL)
    out_words = n_sq * rate_words
    kern = functools.partial(
        _sponge_kernel, rate_words=rate_words, n_abs=n_abs, n_sq=n_sq
    )
    out_hi, out_lo = pl.pallas_call(
        kern,
        grid=(bp // BT,),
        in_specs=[
            pl.BlockSpec((in_words, _TS, _TL), lambda i: (0, i, 0)),
            pl.BlockSpec((in_words, _TS, _TL), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((out_words, _TS, _TL), lambda i: (0, i, 0)),
            pl.BlockSpec((out_words, _TS, _TL), lambda i: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((out_words, bp // _TL, _TL), jnp.uint32),
            jax.ShapeDtypeStruct((out_words, bp // _TL, _TL), jnp.uint32),
        ],
        interpret=interpret,
    )(in_hi, in_lo)
    out_hi = out_hi.reshape(out_words, bp)[:, :b]
    out_lo = out_lo.reshape(out_words, bp)[:, :b]
    return out_hi, out_lo
