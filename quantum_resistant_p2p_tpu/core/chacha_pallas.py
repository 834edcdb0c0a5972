"""Batched RFC 8439 ChaCha20-Poly1305 — the device DATA plane.

Why a kernel: at fleet scale bulk traffic dwarfs handshakes, and every
AEAD seal/open used to be one scalar CPU call per message
(provider/symmetric.py) while the KEM/signature plane batched thousands of
ops per dispatch.  ChaCha20 is pure ARX — the same add/rotate/xor idioms as
the Keccak sponge kernel (core/keccak_pallas.py) — so the block function
vectorizes across the bulk lane's queued messages with zero cross-lane
traffic: one lane = one 64-byte block of one message.

Layout mirrors keccak_pallas: the batch lives on the two *minor*
dimensions — each of the 16 state words is an ``(8, 128)`` uint32 tile
(exactly one 32-bit vector register) across 1024 block instances; the 20
rounds are fully unrolled at trace time.  Messages are padded to pow2
length buckets with masked tails, so XLA compiles one program per
(batch-bucket, length-bucket, aad-bucket) triple instead of one per
message shape.

Poly1305 runs as vectorized jnp alongside the kernel output: the 130-bit
accumulator is represented as twelve radix-2^11 limbs per lane, so every
partial product of a (≤2^12) x (≤2^11) limb multiply fits a 32-bit vector
register with full carry headroom (comments carry the exact bounds).  The
AEAD MAC input is block-aligned by construction (§2.8 pads AAD and
ciphertext to 16), which is what makes variable lengths maskable: inactive
blocks leave the accumulator untouched via a per-lane select.

Oracle: the pure-Python scalar twin (pyref/chacha_ref.py) and the
``cryptography`` package;
tests/test_chacha_pallas.py pins the RFC 8439 §2.8.2 vector and every
masked-tail bucket edge through both the jnp and (interpret-mode) Pallas
paths.  Used by provider/aead_device.py behind the ``BatchedAEAD``
capability; the Pallas path engages on real TPU only (core.keccak's
``_use_pallas`` policy), the jnp twin is bit-identical elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


#: ChaCha20 constants "expa" "nd 3" "2-by" "te k" (RFC 8439 §2.3)
_CONSTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)

#: block instances per grid step: 8 sublanes x 128 lanes = one vreg per word
_TS, _TL = 8, 128
BT = _TS * _TL

#: column then diagonal quarter-round schedule (§2.3: inner_block)
_QR_SCHEDULE = (
    (0, 4, 8, 12), (1, 5, 9, 13), (2, 6, 10, 14), (3, 7, 11, 15),
    (0, 5, 10, 15), (1, 6, 11, 12), (2, 7, 8, 13), (3, 4, 9, 14),
)

#: Poly1305 r clamp (§2.5): top 4 bits of bytes 3/7/11/15 and bottom 2 of
#: bytes 4/8/12 cleared
_R_CLAMP = (255, 255, 255, 15, 252, 255, 255, 15,
            252, 255, 255, 15, 252, 255, 255, 15)

#: Poly1305 limb radix: 12 limbs x 11 bits = 132 >= 130 accumulator bits.
#: Chosen so the schoolbook multiply below stays inside uint32: limbs are
#: <= 2^12 (lazy) x <= 2^11 (clamped r) -> products <= 2^23, column sums of
#: 12 products <= 12*2^23 < 2^26.6, and the 2^132 === 20 (mod 2^130-5) fold
#: adds at most 20x that: 21 * 2^26.6 < 2^31.  A 13-bit radix would
#: overflow the fold.
_RADIX = 11
_NLIMB = 12
_LMASK = (1 << _RADIX) - 1
#: 2^132 = 4 * 2^130 === 4 * 5 = 20 (mod 2^130 - 5)
_FOLD = 20


# --------------------------------------------------------------------------
# ChaCha20 block function (shared by the Pallas kernel and the jnp twin)
# --------------------------------------------------------------------------


def _rotl(x, n: int):
    """Rotate uint32 lanes left by static ``n`` (1..31)."""
    return (x << n) | (x >> (32 - n))  # qrkernel: wrapping — uint32 lane rotation: bits shifted past 32 drop by design and are recovered by the partner right shift (RFC 8439's <<<)


def _double_round(x: list) -> list:
    """One column+diagonal double round (§2.3 inner_block) over 16 uint32
    arrays.  All additions wrap mod 2^32 by design (RFC 8439 §2.1: "+"
    denotes addition modulo 2^32); uint32 lanes give exactly that."""
    x = list(x)
    for a, b, c, d in _QR_SCHEDULE:
        xa, xb, xc, xd = x[a], x[b], x[c], x[d]
        xa = xa + xb
        xd = _rotl(xd ^ xa, 16)
        xc = xc + xd
        xb = _rotl(xb ^ xc, 12)
        xa = xa + xb
        xd = _rotl(xd ^ xa, 8)
        xc = xc + xd
        xb = _rotl(xb ^ xc, 7)
        x[a], x[b], x[c], x[d] = xa, xb, xc, xd
    return x


def chacha_block_words(state: list) -> list:
    """20-round ChaCha20 block + feedforward, fully unrolled at trace time.

    ``state`` is the 16-word initial state (constants, key, counter,
    nonce), each word an ``(8, 128)`` uint32 VPU tile inside the Pallas
    kernel — unrolling keeps the whole working state in vector registers
    for all 80 quarter rounds, exactly like the keccak kernel's 24 rounds.
    (The jnp twin uses the scanned form below instead: XLA:CPU neither
    fuses nor compiles a 1000-op unrolled chain well.)
    """
    x = list(state)
    for _ in range(10):
        x = _double_round(x)
    return [x[i] + state[i] for i in range(16)]


def _chacha_stream_kernel(in_ref, out_ref):
    """One ChaCha20 block per lane.

    in_ref:  (12, 8, 128) uint32 — rows 0-7 key words, row 8 the per-lane
             block counter, rows 9-11 nonce words.
    out_ref: (16, 8, 128) uint32 — the serialized block state words.

    Blocks are independent (the counter is an input), so arbitrarily long
    messages batch as more lanes instead of an unrolled in-kernel block
    loop — the kernel compiles once per tile geometry, never per message
    length.
    """
    consts = [jnp.full((_TS, _TL), c, jnp.uint32) for c in _CONSTS]
    state = consts + [in_ref[w] for w in range(12)]
    out = chacha_block_words(state)
    for w in range(16):
        out_ref[w] = out[w]


def chacha_blocks(states: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Pallas launcher: ``(12, N)`` uint32 lane states -> ``(16, N)`` blocks.

    Batch on the minor axis (N need not be a multiple of the 1024-lane
    tile); layout and padding mirror keccak_pallas.sampler_call.
    """
    w, b = states.shape
    assert w == 12
    bp = -(-b // BT) * BT
    if bp != b:
        states = jnp.pad(states, ((0, 0), (0, bp - b)))
    states = states.reshape(12, bp // _TL, _TL)
    out = pl.pallas_call(
        _chacha_stream_kernel,
        grid=(bp // BT,),
        in_specs=[pl.BlockSpec((12, _TS, _TL), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((16, _TS, _TL), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((16, bp // _TL, _TL), jnp.uint32),
        interpret=interpret,
    )(states)
    return out.reshape(16, bp)[:, :b]


def chacha_blocks_jnp(states: jax.Array) -> jax.Array:
    """Bit-identical jnp twin of :func:`chacha_blocks` (the CPU/test path).

    The 10 double rounds run under ``lax.scan`` instead of unrolled: the
    same 960 quarter-round ops as one compact 96-op loop body, which
    XLA:CPU compiles in under a second and fuses into one kernel (the
    unrolled form measured ~30 s to compile and 5x slower to run)."""
    consts = [jnp.full(states.shape[1:], c, jnp.uint32) for c in _CONSTS]
    init = jnp.stack(consts + [states[i] for i in range(12)])

    def body(x, _):
        return jnp.stack(_double_round([x[i] for i in range(16)])), None

    out, _ = jax.lax.scan(body, init, None, length=10)
    return out + init


# --------------------------------------------------------------------------
# Poly1305 (vectorized jnp, radix-2^11 limbs)
# --------------------------------------------------------------------------


def _le_words(b: jax.Array) -> jax.Array:
    """(..., 4k) uint8 -> (..., k) uint32 little-endian words."""
    w = b.astype(jnp.uint32).reshape(*b.shape[:-1], -1, 4)
    return w[..., 0] | (w[..., 1] << 8) | (w[..., 2] << 16) | (w[..., 3] << 24)


def _words_to_u8(w: jax.Array) -> jax.Array:
    """(..., k) uint32 -> (..., 4k) uint8 little-endian bytes."""
    b = jnp.stack([w & 0xFF, (w >> 8) & 0xFF, (w >> 16) & 0xFF,
                   (w >> 24) & 0xFF], axis=-1)
    return b.reshape(*w.shape[:-1], -1).astype(jnp.uint8)


def _limbs(w: jax.Array, pad_bit: bool) -> jax.Array:
    """(..., 4) uint32 le words of one 16-byte block -> (..., 12) limbs.

    ``pad_bit`` adds 2^128 (every AEAD MAC block is a full padded 16-byte
    block, §2.8.1), which lands in limb 11 at bit 128 - 11*11 = 7.
    """
    limbs = []
    for a in range(_NLIMB - 1):
        i, off = divmod(_RADIX * a, 32)
        v = w[..., i] >> off
        if off > 32 - _RADIX:
            v = v | (w[..., i + 1] << (32 - off))
        limbs.append(v & _LMASK)
    top = (w[..., 3] >> 25) & 0x7F  # bits 121..127
    if pad_bit:
        top = top | (1 << 7)
    limbs.append(top)
    return jnp.stack(limbs, axis=-1)


def _carry(h: jax.Array) -> jax.Array:
    """One full carry pass over (..., 12) limbs, folding the carry out of
    limb 11 back into limb 0 via 2^132 === 20 (mod p)."""
    out = []
    carry = jnp.zeros_like(h[..., 0])
    for k in range(_NLIMB):
        v = h[..., k] + carry
        out.append(v & _LMASK)
        carry = v >> _RADIX
    out[0] = out[0] + carry * _FOLD
    return jnp.stack(out, axis=-1)


def _poly_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """(h * r) mod 2^130-5 on (..., 12) limb arrays.

    Bounds (see _RADIX): a limbs <= 2^12 (one lazy add of a block on top of
    carried limbs), b limbs <= 2^11 (clamped r), so every column sum plus
    the x20 fold stays under 2^31 — no uint32 wrap anywhere.
    """
    # one (..., 12, 12) outer product, then anti-diagonal column sums via
    # shifted pads — ~60 traced ops instead of the 144-multiply schoolbook
    # expansion, which XLA:CPU runs measurably faster inside the scan
    outer = a[..., :, None] * b[..., None, :]
    pad0 = [(0, 0)] * (outer.ndim - 2)
    t = jnp.pad(outer[..., 0, :], pad0 + [(0, _NLIMB - 1)])
    for i in range(1, _NLIMB):
        t = t + jnp.pad(outer[..., i, :], pad0 + [(i, _NLIMB - 1 - i)])
    c = t[..., :_NLIMB].at[..., : _NLIMB - 1].add(t[..., _NLIMB:] * _FOLD)
    # two carry passes: the first leaves limb 0 <= 2^11 + 20*2^20, the
    # second restores limbs <= 2^11 + _FOLD (< 2^12, the lazy invariant)
    return _carry(_carry(c))


def _poly_final(h: jax.Array, s_bytes: jax.Array) -> jax.Array:
    """Final reduction + s addition: (..., 12) limbs -> (..., 16) u8 tag."""
    h = _carry(_carry(h))
    # fold bits 130/131 (limb 11 bits >= 9): 2^130 === 5 (mod p)
    hi = h[..., 11] >> 9
    h = h.at[..., 11].set(h[..., 11] & 0x1FF)
    h = h.at[..., 0].add(hi * 5)
    h = _carry(h)
    # conditional subtract p: g = h + 5; h >= p  <=>  g >= 2^130
    g = h.at[..., 0].add(5)
    g = _carry(g)
    ge = (g[..., 11] >> 9) > 0
    g = g.at[..., 11].set(g[..., 11] & 0x1FF)
    h = jnp.where(ge[..., None], g, h)
    # tag = (h + s) mod 2^128, byte-serialized little-endian
    out = []
    carry = jnp.zeros_like(s_bytes[..., 0], dtype=jnp.uint32)
    for j in range(16):
        a, off = divmod(8 * j, _RADIX)
        v = h[..., a] >> off
        if off > _RADIX - 8 and a + 1 < _NLIMB:
            v = v | (h[..., a + 1] << (_RADIX - off))
        v = (v & 0xFF) + s_bytes[..., j].astype(jnp.uint32) + carry
        out.append(v & 0xFF)
        carry = v >> 8
    return jnp.stack(out, axis=-1).astype(jnp.uint8)


def poly1305_tags(r_bytes: jax.Array, s_bytes: jax.Array,
                  mac_bytes: jax.Array, active: jax.Array) -> jax.Array:
    """Batched Poly1305 over block-aligned MAC input.

    r_bytes/s_bytes: (B, 16) uint8 halves of the one-time key (r unclamped
    — the clamp is applied here); mac_bytes: (B, 16*n) uint8, every block
    a full padded 16-byte block; active: (B, n) bool — inactive blocks
    leave the accumulator untouched (the masked-variable-length trick).
    Returns (B, 16) uint8 tags.
    """
    r = _limbs(_le_words(r_bytes & jnp.asarray(_R_CLAMP, jnp.uint8)),
               pad_bit=False)
    blocks = _limbs(_le_words(mac_bytes).reshape(r_bytes.shape[0], -1, 4),
                    pad_bit=True)  # (B, n, 12)
    h0 = jnp.zeros_like(r)

    def step(h, x):
        bl, act = x
        nh = _poly_mul(h + bl, r)
        return jnp.where(act[..., None], nh, h), None

    h, _ = jax.lax.scan(step, h0, (jnp.moveaxis(blocks, 1, 0),
                                   jnp.moveaxis(active, 1, 0)))
    return _poly_final(h, s_bytes)


# --------------------------------------------------------------------------
# RFC 8439 AEAD composition (seal/open share one jitted core)
# --------------------------------------------------------------------------


@functools.partial(jax.jit,
                   static_argnames=("seal", "use_pallas", "interpret"))
def aead_core(keys: jax.Array, nonces: jax.Array, data: jax.Array,
              lens: jax.Array, aads: jax.Array, aad_lens: jax.Array, *,
              seal: bool, use_pallas: bool = False,
              interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Batched ChaCha20-Poly1305 seal or open core.

    keys (B, 32) u8, nonces (B, 12) u8, data (B, L) u8 (plaintext when
    sealing, ciphertext when opening; L a multiple of 64), lens (B,) i32
    true byte lengths, aads (B, A) u8 (A a multiple of 16), aad_lens (B,)
    i32.  Returns ``(other, tags)``: ``other`` is the ciphertext (seal) or
    plaintext (open), zero past ``lens``; ``tags`` the (B, 16) u8 Poly1305
    tags computed over the ciphertext either way — the open caller compares
    them against the received tags.

    jit compiles one program per (B, L, A) bucket triple; callers pad to
    pow2 buckets (provider/aead_device.py) so the bucket space stays small.
    """
    b, l = data.shape
    nb = l // 64
    reps = nb + 1  # block 0 is the Poly1305 one-time key (§2.6)
    kw = jnp.repeat(_le_words(keys), reps, axis=0).T          # (8, B*reps)
    nw = jnp.repeat(_le_words(nonces), reps, axis=0).T        # (3, B*reps)
    ctr = jnp.tile(jnp.arange(reps, dtype=jnp.uint32), b)[None]
    states = jnp.concatenate([kw, ctr, nw], axis=0)           # (12, B*reps)
    blocks = (chacha_blocks(states, interpret=interpret) if use_pallas
              else chacha_blocks_jnp(states)).reshape(16, b, reps)
    poly_key = _words_to_u8(jnp.moveaxis(blocks[:8, :, 0], 0, 1))  # (B, 32)
    ks = _words_to_u8(
        jnp.moveaxis(blocks[:, :, 1:], 0, 2).reshape(b, nb * 16))  # (B, L)
    mask = jnp.arange(l) < lens[:, None]
    other = jnp.where(mask, data ^ ks, 0).astype(jnp.uint8)
    ct = other if seal else jnp.where(mask, data, 0).astype(jnp.uint8)
    # MAC input (§2.8): padded AAD || padded ciphertext || le64 lengths —
    # block-aligned by construction, so per-lane lengths mask block-wise
    aad_m = jnp.where(jnp.arange(aads.shape[1]) < aad_lens[:, None],
                      aads, 0).astype(jnp.uint8)
    len_block = jnp.concatenate([_le64(aad_lens), _le64(lens)], axis=-1)
    mac_bytes = jnp.concatenate([aad_m, ct, len_block], axis=1)
    block_starts_aad = jnp.arange(aads.shape[1] // 16) * 16
    block_starts_ct = jnp.arange(l // 16) * 16
    active = jnp.concatenate([
        block_starts_aad < aad_lens[:, None],
        block_starts_ct < lens[:, None],
        jnp.ones((b, 1), bool),  # the length block is always processed
    ], axis=1)
    tags = poly1305_tags(poly_key[:, :16], poly_key[:, 16:], mac_bytes,
                         active)
    return other, tags


def _le64(n: jax.Array) -> jax.Array:
    """(B,) int lengths -> (B, 8) uint8 little-endian (lengths < 2^31)."""
    n = n.astype(jnp.uint32)
    lo = jnp.stack([(n >> (8 * i)) & 0xFF for i in range(4)], axis=-1)
    return jnp.concatenate([lo, jnp.zeros_like(lo)],
                           axis=-1).astype(jnp.uint8)

