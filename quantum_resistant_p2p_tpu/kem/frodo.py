"""Batched FrodoKEM in JAX — dense LWE on the MXU.

TPU-native design
-----------------
FrodoKEM is the most TPU-friendly algorithm in the suite: its cost is dense
n x n (mod 2^16) matrix algebra, which maps directly onto matrix units — no
NTT, no rejection sampling, power-of-two modulus (mod q = free bit-mask).

* The A matrix is never materialised: it is generated (AES-128 counter blocks
  via ``core.aes`` or SHAKE-128 rows via ``core.keccak``) in 16 row-chunks and
  immediately contracted against S / S', keeping memory at
  O(batch * n * n/16) while the matmuls stay MXU-sized.
* All arithmetic is int32 (products bounded by n * 12 * 2^16 < 2^31 — exact),
  masked back to q = 2^D with a bit-and.
* Every op takes an arbitrary leading batch shape; randomness (s, seedSE, z,
  mu) is an explicit input — the deterministic seam the spec defines.

Bit-exactness oracle: ``pyref.frodo_ref`` (tests/test_frodo.py).
Replaces (reference): FrodoKEMKeyExchange's per-call liboqs objects
(crypto/key_exchange.py:312-449); BASELINE.json config 3 names
FrodoKEM-640-AES batch=1024 as the LWE matrix-sampling benchmark.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import aes as jaes
from ..core import aes_bitsliced as jaes_bs
from ..core import keccak
from ..pyref.frodo_ref import NBAR, PARAMS, FrodoParams
from . import frodo_pallas


def _use_bitsliced_aes() -> bool:
    """Bitsliced (table-free) AES by default; QRP2P_AES_GATHER=1 restores the
    gather S-box for A/B runs.  Read at TRACE time (jit caches the choice) —
    flip only in a fresh process, as with every trace-time choice."""
    import os

    return os.environ.get("QRP2P_AES_GATHER", "0") != "1"

N_CHUNKS = 16  # A-matrix row chunks (n is divisible by 16 in all sets)

#: Largest single-dispatch batch (provider/base.py sliced_dispatch).  The
#: cap dates from an earlier platform and awaits a sweep on the chip
#: (ROADMAP queue 1 item 6); tests/test_chip_compile.py compiles the
#: Pallas matmul at this width for a v5e.
MAX_DEVICE_BATCH = 512


def _shake(p: FrodoParams, data: jax.Array, out_len: int) -> jax.Array:
    fn = keccak.shake128 if p.n == 640 else keccak.shake256
    return fn(data, out_len)


def _le16(b: jax.Array) -> jax.Array:
    """(..., 2k) uint8 -> (..., k) int32 little-endian 16-bit."""
    x = b.astype(jnp.int32).reshape(b.shape[:-1] + (-1, 2))
    return x[..., 0] | (x[..., 1] << 8)


def _to_le16(v: jax.Array) -> jax.Array:
    """(..., k) int32 (mod 2^16) -> (..., 2k) uint8."""
    out = jnp.stack([v & 0xFF, (v >> 8) & 0xFF], axis=-1).astype(jnp.uint8)
    return out.reshape(out.shape[:-2] + (-1,))


# -- error sampling (CDF inversion, vectorised) ------------------------------


def _sample(p: FrodoParams, r16: jax.Array) -> jax.Array:
    """(...,) int32 16-bit randoms -> CDF samples mod q."""
    if keccak._use_pallas():
        # Fused compare-sum on device: never materialises the (M, |cdf|)
        # comparison tensor in HBM (bit-identical, tests/test_frodo_pallas).
        return frodo_pallas.cdf_sample(p, r16)
    cdf = jnp.asarray(np.asarray(p.cdf[:-1], dtype=np.int32))
    t = r16 >> 1
    e = jnp.sum(t[..., None] > cdf, axis=-1)
    return jnp.where(r16 & 1 == 1, -e, e) & (p.q - 1)


# -- packing / encoding ------------------------------------------------------


def _pack(p: FrodoParams, v: jax.Array) -> jax.Array:
    """(..., m) int32 -> (..., m*d/8) uint8, d-bit MSB-first per value."""
    bits = (v[..., :, None] >> np.arange(p.d - 1, -1, -1)) & 1
    bits = bits.reshape(v.shape[:-1] + (-1, 8))
    return jnp.sum(bits << np.arange(7, -1, -1), axis=-1).astype(jnp.uint8)


def _unpack(p: FrodoParams, b: jax.Array) -> jax.Array:
    """(..., m*d/8) uint8 -> (..., m) int32."""
    bits = (b[..., :, None].astype(jnp.int32) >> np.arange(7, -1, -1)) & 1
    bits = bits.reshape(b.shape[:-1] + (-1, p.d))
    return jnp.sum(bits << np.arange(p.d - 1, -1, -1), axis=-1)


def _encode(p: FrodoParams, mu: jax.Array) -> jax.Array:
    """(..., len_sec) uint8 -> (..., 64) int32 (nbar x nbar row-major)."""
    bits = (mu[..., :, None].astype(jnp.int32) >> np.arange(8)) & 1
    bits = bits.reshape(mu.shape[:-1] + (64, p.b))
    vals = jnp.sum(bits << np.arange(p.b), axis=-1)
    return vals << (p.d - p.b)


def _decode(p: FrodoParams, m: jax.Array) -> jax.Array:
    """(..., 64) int32 -> (..., len_sec) uint8."""
    val = (((m & (p.q - 1)) << p.b) + (p.q >> 1)) >> p.d
    val = val & ((1 << p.b) - 1)
    bits = (val[..., :, None] >> np.arange(p.b)) & 1
    bits = bits.reshape(m.shape[:-1] + (-1, 8))
    return jnp.sum(bits << np.arange(8), axis=-1).astype(jnp.uint8)


# -- A-matrix row-chunk generation -------------------------------------------


def _gen_a_chunk(p: FrodoParams, ctx, row_start: int, nrows: int) -> jax.Array:
    """-> (batch, nrows, n) int32; ctx = round_keys (AES) or seed_a (SHAKE)."""
    mask = p.q - 1
    if p.aes:
        rk = ctx
        pt = np.zeros((nrows, p.n // 8, 16), dtype=np.uint8)
        for r in range(nrows):
            i = row_start + r
            pt[r, :, 0] = i & 0xFF
            pt[r, :, 1] = i >> 8
            cols = np.arange(0, p.n, 8)
            pt[r, :, 2] = cols & 0xFF
            pt[r, :, 3] = cols >> 8
        blocks = jnp.asarray(pt.reshape(-1, 16))
        blocks = jnp.broadcast_to(blocks, rk.shape[:-2] + blocks.shape)
        aes_impl = jaes_bs if _use_bitsliced_aes() else jaes
        ct = aes_impl.encrypt_blocks(rk, blocks)
        vals = _le16(ct.reshape(ct.shape[:-2] + (-1,)))
        return vals.reshape(vals.shape[:-1] + (nrows, p.n)) & mask
    seed_a = ctx
    idx = np.zeros((nrows, 2), dtype=np.uint8)
    rows = np.arange(row_start, row_start + nrows)
    idx[:, 0] = rows & 0xFF
    idx[:, 1] = rows >> 8
    lead = seed_a.shape[:-1] + (nrows,)
    seeds = jnp.concatenate(
        [
            jnp.broadcast_to(jnp.asarray(idx), lead + (2,)),
            jnp.broadcast_to(seed_a[..., None, :], lead + (16,)),
        ],
        axis=-1,
    )
    buf = keccak.shake128(seeds, 2 * p.n)  # Gen uses SHAKE128 for every set
    return _le16(buf) & mask


def _a_ctx(p: FrodoParams, seed_a: jax.Array):
    return jaes.key_schedule(seed_a) if p.aes else seed_a


def _a_times_s(p: FrodoParams, ctx, s: jax.Array) -> jax.Array:
    """A @ S: s (batch, n, nbar) -> (batch, n, nbar), without materialising A.

    SHAKE sets route to the fused Pallas matmul (kem/frodo_pallas.py: sponge
    fused into the matmul consumer, A never touches HBM) on real TPU and to
    its bit-identical scanned-jnp twin elsewhere; the AES sets keep the
    bitsliced-AES chunk loop (their matrix stream is not a sponge)."""
    if not p.aes:
        if keccak._use_pallas():
            return frodo_pallas.a_times_s(p, s, ctx)
        return frodo_pallas.a_times_s_jnp(p, s, ctx)
    rows = p.n // N_CHUNKS
    outs = []
    for c in range(N_CHUNKS):
        a_chunk = _gen_a_chunk(p, ctx, c * rows, rows)
        outs.append(jnp.einsum("...rn,...nj->...rj", a_chunk, s) & (p.q - 1))
    return jnp.concatenate(outs, axis=-2)


def _s_times_a(p: FrodoParams, sp: jax.Array, ctx) -> jax.Array:
    """S' @ A: sp (batch, nbar, n) -> (batch, nbar, n).

    Routing mirrors :func:`_a_times_s` (fused Pallas / scanned twin for the
    SHAKE sets, AES chunk loop otherwise)."""
    if not p.aes:
        if keccak._use_pallas():
            return frodo_pallas.s_times_a(p, sp, ctx)
        return frodo_pallas.s_times_a_jnp(p, sp, ctx)
    rows = p.n // N_CHUNKS
    acc = jnp.zeros(sp.shape[:-1] + (p.n,), jnp.int32)
    for c in range(N_CHUNKS):
        a_chunk = _gen_a_chunk(p, ctx, c * rows, rows)
        sp_chunk = sp[..., :, c * rows : (c + 1) * rows]
        acc = (acc + jnp.einsum("...ir,...rn->...in", sp_chunk, a_chunk)) & (p.q - 1)
    return acc


# -- KEM -----------------------------------------------------------------------


def keygen(p: FrodoParams, s: jax.Array, seed_se: jax.Array, z: jax.Array):
    """(..., len_sec) x3 uint8 -> (pk (..., pk_len), sk (..., sk_len))."""
    s = jnp.asarray(s, jnp.uint8)
    seed_se = jnp.asarray(seed_se, jnp.uint8)
    z = jnp.asarray(z, jnp.uint8)
    batch = z.shape[:-1]
    seed_a = _shake(p, z, 16)
    ctx = _a_ctx(p, seed_a)
    pfx = jnp.broadcast_to(jnp.uint8(0x5F), batch + (1,))
    r = _le16(_shake(p, jnp.concatenate([pfx, seed_se], axis=-1), 4 * p.n * NBAR))
    st = _sample(p, r[..., : p.n * NBAR]).reshape(batch + (NBAR, p.n))
    e = _sample(p, r[..., p.n * NBAR :]).reshape(batch + (p.n, NBAR))
    s_mat = jnp.swapaxes(st, -1, -2)
    b_mat = (_a_times_s(p, ctx, s_mat) + e) & (p.q - 1)
    b_packed = _pack(p, b_mat.reshape(batch + (-1,)))
    pk = jnp.concatenate([seed_a, b_packed], axis=-1)
    pkh = _shake(p, pk, p.len_sec)
    # stored as centered signed int16 (v - q when v >= q/2), like the spec
    st_c = st.reshape(batch + (-1,))
    st_bytes = _to_le16((st_c - jnp.where(st_c >= p.q // 2, p.q, 0)) & 0xFFFF)
    sk = jnp.concatenate([s, pk, st_bytes, pkh], axis=-1)
    return pk, sk


def _encaps_noise(p: FrodoParams, mu: jax.Array, pkh: jax.Array):
    """Deterministic encaps randomness: -> (sp, ep, epp, k)."""
    batch = mu.shape[:-1]
    se_k = _shake(p, jnp.concatenate([pkh, mu], axis=-1), 2 * p.len_sec)
    seed_se, k = se_k[..., : p.len_sec], se_k[..., p.len_sec :]
    pfx = jnp.broadcast_to(jnp.uint8(0x96), batch + (1,))
    r = _le16(
        _shake(p, jnp.concatenate([pfx, seed_se], axis=-1),
               (2 * NBAR * p.n + NBAR * NBAR) * 2)
    )
    sp = _sample(p, r[..., : NBAR * p.n]).reshape(batch + (NBAR, p.n))
    ep = _sample(p, r[..., NBAR * p.n : 2 * NBAR * p.n]).reshape(batch + (NBAR, p.n))
    epp = _sample(p, r[..., 2 * NBAR * p.n :]).reshape(batch + (NBAR, NBAR))
    return sp, ep, epp, k


def _assemble_ct(p: FrodoParams, sp: jax.Array, bp: jax.Array,
                 b_mat: jax.Array, epp: jax.Array, mu: jax.Array):
    """Shared encaps tail: B' and the unpacked B matrix -> packed ct."""
    batch = mu.shape[:-1]
    v = (jnp.einsum("...in,...nj->...ij", sp, b_mat) + epp) & (p.q - 1)
    c = (v.reshape(batch + (-1,)) + _encode(p, mu)) & (p.q - 1)
    return jnp.concatenate(
        [_pack(p, bp.reshape(batch + (-1,))), _pack(p, c)], axis=-1
    )


def _reencrypt(p: FrodoParams, pk: jax.Array, mu: jax.Array, pkh: jax.Array):
    """Shared encaps core: -> (ct, k)."""
    batch = mu.shape[:-1]
    seed_a, b_packed = pk[..., :16], pk[..., 16:]
    sp, ep, epp, k = _encaps_noise(p, mu, pkh)
    ctx = _a_ctx(p, seed_a)
    bp = (_s_times_a(p, sp, ctx) + ep) & (p.q - 1)
    b_mat = _unpack(p, b_packed).reshape(batch + (p.n, NBAR))
    return _assemble_ct(p, sp, bp, b_mat, epp, mu), k


def encaps(p: FrodoParams, pk: jax.Array, mu: jax.Array):
    """pk (..., pk_len), mu (..., len_sec) -> (ct (..., ct_len), ss (..., len_sec))."""
    pk = jnp.asarray(pk, jnp.uint8)
    mu = jnp.asarray(mu, jnp.uint8)
    pkh = _shake(p, pk, p.len_sec)
    ct, k = _reencrypt(p, pk, mu, pkh)
    ss = _shake(p, jnp.concatenate([ct, k], axis=-1), p.len_sec)
    return ct, ss


def decaps(p: FrodoParams, sk: jax.Array, ct: jax.Array):
    """sk (..., sk_len), ct (..., ct_len) -> ss (..., len_sec)."""
    sk = jnp.asarray(sk, jnp.uint8)
    ct = jnp.asarray(ct, jnp.uint8)
    batch = ct.shape[:-1]
    s = sk[..., : p.len_sec]
    pk = sk[..., p.len_sec : p.len_sec + p.pk_len]
    st_off = p.len_sec + p.pk_len
    st_bytes = sk[..., st_off : st_off + 2 * NBAR * p.n]
    pkh = sk[..., st_off + 2 * NBAR * p.n :]
    # signed-LE16 mod q == raw 16-bit value masked, since q | 2^16
    st = (_le16(st_bytes) & (p.q - 1)).reshape(batch + (NBAR, p.n))
    c1_len = NBAR * p.n * p.d // 8
    bp = _unpack(p, ct[..., :c1_len]).reshape(batch + (NBAR, p.n))
    c = _unpack(p, ct[..., c1_len:])
    bps = jnp.einsum("...in,...jn->...ij", bp, st) & (p.q - 1)
    m = (c - bps.reshape(batch + (-1,))) & (p.q - 1)
    mu_p = _decode(p, m)
    ct2, kp = _reencrypt(p, pk, mu_p, pkh)
    ok = jnp.all(ct == ct2, axis=-1, keepdims=True)
    tail = jnp.where(ok, kp, s)
    return _shake(p, jnp.concatenate([ct, tail], axis=-1), p.len_sec)


@functools.cache
def get(name: str):
    """Jitted (keygen, encaps, decaps) triple for a parameter-set name."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(keygen, p)),
        jax.jit(functools.partial(encaps, p)),
        jax.jit(functools.partial(decaps, p)),
    )


# --------------------------------------------------------------------------
# Per-key precompute (device operand cache seam, provider/opcache.py)
# --------------------------------------------------------------------------


def precompute_pk(p: FrodoParams, pk: jax.Array) -> dict[str, jax.Array]:
    """Per-key device state encaps reuses across dispatches: the MATERIALISED
    A matrix (the dominant per-dispatch regen cost — n^2 sponge/AES bytes),
    the unpacked B matrix, and H(pk).  Computed once per key by the operand
    cache; repeat encaps against the same peer key then run a pure dense
    matmul with zero matrix regeneration.  May be unbatched; broadcasts
    against any mu batch.  A is int32 (n=1344: 7.2 MB/key, bounded by the
    cache's entry cap)."""
    pk = jnp.asarray(pk, jnp.uint8)
    seed_a, b_packed = pk[..., :16], pk[..., 16:]
    ctx = _a_ctx(p, seed_a)
    rows = p.n // N_CHUNKS
    a_mat = jnp.concatenate(
        [_gen_a_chunk(p, ctx, c * rows, rows) for c in range(N_CHUNKS)],
        axis=-2,
    )
    b_mat = _unpack(p, b_packed).reshape(pk.shape[:-1] + (p.n, NBAR))
    return {"a": a_mat, "b": b_mat, "pkh": _shake(p, pk, p.len_sec)}


def encaps_pre(p: FrodoParams, pre: dict[str, jax.Array], mu: jax.Array):
    """``encaps`` over a ``precompute_pk`` pytree — bit-identical output
    (the precompute is a pure hoist of the key-dependent prefix; int32
    products wrap mod 2^32 identically in the dense and fused paths, and
    q | 2^32 keeps the masked results equal)."""
    mu = jnp.asarray(mu, jnp.uint8)
    batch = mu.shape[:-1]
    pkh = jnp.broadcast_to(pre["pkh"], batch + (p.len_sec,))
    sp, ep, epp, k = _encaps_noise(p, mu, pkh)
    bp = (jnp.einsum("...ir,...rn->...in", sp, pre["a"]) + ep) & (p.q - 1)
    ct = _assemble_ct(p, sp, bp, pre["b"], epp, mu)
    ss = _shake(p, jnp.concatenate([ct, k], axis=-1), p.len_sec)
    return ct, ss


def encaps_cold(p: FrodoParams, pk: jax.Array, mu: jax.Array):
    """Cache-filling encaps: ONE dispatch returning the per-key device state
    plus the op results (same rationale as kem/mlkem.encaps_cold — a miss
    must not cost an extra round trip over the uncached path)."""
    pre = precompute_pk(p, pk)
    ct, ss = encaps_pre(p, pre, mu)
    return pre, ct, ss


@functools.cache
def get_pre(name: str):
    """Jitted (encaps_cold, encaps_pre) pair for the device operand cache
    (provider/opcache.py): cold fills the cache in one dispatch; pre runs a
    pure dense matmul over the cached A — single-key batches skip the
    matrix regeneration entirely."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(encaps_cold, p)),
        jax.jit(functools.partial(encaps_pre, p)),
    )
