"""Batched ML-KEM (FIPS 203) in JAX — the TPU crypto core's flagship KEM.

TPU-native design
-----------------
Every function operates on arrays with an arbitrary leading batch shape and
fixed trailing shapes, so a single jitted program amortises compilation over
thousands of concurrent handshakes (the reference app performs one serial
liboqs FFI call per handshake: crypto/key_exchange.py:125-186).

* Polynomials are ``(..., 256)`` int32 kept reduced in [0, q); q = 3329, so all
  intermediate products fit comfortably in int32 — no 64-bit emulation needed.
* The NTT is the layered butterfly vectorised across all 128 butterflies of a
  layer at once (7 static layers, no data-dependent control flow).
* SampleNTT's rejection loop becomes a fixed-size squeeze (672 bytes -> 448
  candidates, P[shortfall] < 1e-38) followed by a stable-sort compaction —
  identical output to the spec's sequential scan whenever the spec would have
  consumed <= 672 bytes.
* All hashing (G/H/J/PRF/XOF) is the batched Keccak kernel from
  ``core.keccak``; randomness (d, z, m) is an explicit input, giving the
  deterministic seam FIPS 203 defines for KATs.

Bit-exactness oracle: ``pyref.mlkem_ref`` (clean-room FIPS 203 over hashlib).
Replaces (reference): MLKEMKeyExchange's per-call liboqs objects
(crypto/key_exchange.py:57-186, vendor/oqs.py:310-390).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core import keccak
from ..core.sortnet import bitonic_sort
from ..pyref.mlkem_ref import (  # parameter sets + computed constant tables
    GAMMAS,
    MLKEM512,
    MLKEM768,
    MLKEM1024,
    MLKEMParams,
    PARAMS,
    ZETAS,
)

Q = 3329
N = 256

#: Provider slice size.  The fused Pallas sampler kernels
#: (kem/mlkem_pallas.py) process exactly 1024 sponges per grid step, so
#: smaller dispatches pad and waste tile lanes; an earlier platform showed
#: throughput flat over 1024-2048 rows and falling past 2048, where the
#: remaining jnp pipeline's working set spills.  Not measured on this
#: chip: the cap awaits a sweep there.  Providers slice larger batches
#: (provider/base.py sliced_dispatch).
MAX_DEVICE_BATCH = 1024
_N_INV = 3303  # 128^-1 mod q

_ZETAS = np.asarray(ZETAS, dtype=np.int32)
_GAMMAS = np.asarray(GAMMAS, dtype=np.int32)

# --------------------------------------------------------------------------
# Byte codecs (FIPS 203 ByteEncode_d / ByteDecode_d), batched
# --------------------------------------------------------------------------


def byte_decode(b: jax.Array, d: int) -> jax.Array:
    """(..., 32*d) uint8 -> (..., 256) int32 (mod q when d == 12).

    d == 12 (t_hat/s_hat, on every op's path) uses an arithmetic split —
    3 bytes onto 2 coefficients with fixed shifts, ~6x fewer ops than the
    generic bit expansion and no (..., 256, 12) intermediate.  The other
    widths keep the bit path: measured on chip, arithmetic forms of the
    narrow widths (group shapes like (64, 5) for d = 10) misalign TPU
    lanes and run SLOWER than the wide bit-expansion arrays (headline
    1.073M with this split vs 919k all-arithmetic encaps/s).
    """
    if d != 12:
        bits = (b[..., :, None].astype(jnp.int32) >> jnp.arange(8)) & 1
        bits = bits.reshape(b.shape[:-1] + (N, d))
        return jnp.sum(bits << jnp.arange(d), axis=-1)
    t = b.astype(jnp.int32).reshape(b.shape[:-1] + (N // 2, 3))
    lo = t[..., 0] | ((t[..., 1] & 0xF) << 8)
    hi = (t[..., 1] >> 4) | (t[..., 2] << 4)
    return jnp.stack([lo, hi], axis=-1).reshape(b.shape[:-1] + (N,)) % Q


def byte_encode(vals: jax.Array, d: int) -> jax.Array:
    """(..., 256) int32 -> (..., 32*d) uint8 (inverse of byte_decode;
    same d == 12 arithmetic-vs-bit split, see byte_decode)."""
    if d != 12:
        bits = (vals[..., :, None] >> jnp.arange(d)) & 1
        bits = bits.reshape(vals.shape[:-1] + (32 * d, 8))
        return jnp.sum(bits << jnp.arange(8), axis=-1).astype(jnp.uint8)
    v = vals.reshape(vals.shape[:-1] + (N // 2, 2))
    # The arithmetic split would spill bits >= 12 into adjacent bytes (the
    # old bit path truncated them); mask so non-canonical inputs can't.
    lo, hi = v[..., 0] & 0xFFF, v[..., 1] & 0xFFF
    out = jnp.stack([lo & 0xFF, (lo >> 8) | ((hi & 0xF) << 4), hi >> 4], axis=-1)
    return out.reshape(vals.shape[:-1] + (384,)).astype(jnp.uint8)


def compress(x: jax.Array, d: int) -> jax.Array:
    return ((x << (d + 1)) + Q) // (2 * Q) % (1 << d)


def decompress(y: jax.Array, d: int) -> jax.Array:
    return (y * Q + (1 << (d - 1))) >> d


# --------------------------------------------------------------------------
# NTT over Z_q[X]/(X^256+1), q = 3329 (FIPS 203 §4.3), batched & layer-vectorised
# --------------------------------------------------------------------------


def ntt(f: jax.Array) -> jax.Array:
    """(..., 256) int32 in [0,q) -> NTT domain, same shape.

    On TPU the 7 butterfly layers run register-resident in one Pallas
    kernel (kem/mlkem_pallas.py:ntt_words) — the jnp formulation below
    materialises the full batched array between layers, 14 HBM round-trips
    per transform."""
    if keccak._use_pallas():
        from . import mlkem_pallas  # deferred: pallas import

        flat = f.reshape((-1, N))
        return mlkem_pallas.ntt_words(flat.T).T.reshape(f.shape)
    zetas = jnp.asarray(_ZETAS)
    k = 1
    length = 128
    while length >= 2:
        groups = N // (2 * length)
        z = zetas[k : k + groups]
        fr = f.reshape(f.shape[:-1] + (groups, 2, length))
        f0, f1 = fr[..., 0, :], fr[..., 1, :]
        t = (z[:, None] * f1) % Q
        f = jnp.stack([(f0 + t) % Q, (f0 - t) % Q], axis=-2).reshape(f.shape)
        k += groups
        length //= 2
    return f


def ntt_inv(f: jax.Array) -> jax.Array:
    if keccak._use_pallas():
        from . import mlkem_pallas  # deferred: pallas import

        flat = f.reshape((-1, N))
        return mlkem_pallas.ntt_words(flat.T, inverse=True).T.reshape(f.shape)
    zetas = jnp.asarray(_ZETAS)
    k = 127
    length = 2
    while length <= 128:
        groups = N // (2 * length)
        z = zetas[k - groups + 1 : k + 1][::-1]
        fr = f.reshape(f.shape[:-1] + (groups, 2, length))
        f0, f1 = fr[..., 0, :], fr[..., 1, :]
        s = (f0 + f1) % Q
        t = (z[:, None] * ((f1 - f0) % Q)) % Q
        f = jnp.stack([s, t], axis=-2).reshape(f.shape)
        k -= groups
        length *= 2
    return (f * _N_INV) % Q


def multiply_ntts(f: jax.Array, g: jax.Array) -> jax.Array:
    """Pairwise base-case products; broadcasts over leading dims."""
    gam = jnp.asarray(_GAMMAS)
    a0, a1 = f[..., 0::2], f[..., 1::2]
    b0, b1 = g[..., 0::2], g[..., 1::2]
    c0 = (a0 * b0 + (a1 * b1 % Q) * gam) % Q
    c1 = (a0 * b1 + a1 * b0) % Q
    return jnp.stack([c0, c1], axis=-1).reshape(jnp.broadcast_shapes(f.shape, g.shape))


# --------------------------------------------------------------------------
# Samplers (FIPS 203 §4.2.2), batched with fixed shapes
# --------------------------------------------------------------------------

_SAMPLE_NTT_BYTES = 672  # 4 SHAKE-128 rate blocks -> 448 candidates for 256 slots


def sample_ntt(seeds: jax.Array) -> jax.Array:
    """(..., 34) uint8 XOF seeds -> (..., 256) int32 NTT-domain polynomials.

    Fixed-shape replacement for the spec's squeeze-until-256-accepted loop:
    squeeze 672 bytes up front, mark candidates < q, and compact accepted
    candidates to the front in spec order.  The compaction is a gather-free
    bitonic network over packed int32 keys (reject | index | value) — XLA's
    argsort/take_along_axis serialise on TPU and measured 200+ ms per batch,
    the entire encaps budget (core/sortnet.py).

    On TPU the whole pipeline (SHAKE squeeze -> extraction -> compaction)
    runs as one fused Pallas kernel with every intermediate in VMEM
    (kem/mlkem_pallas.py) — it is ~85% of encaps' HBM traffic otherwise.
    """
    if keccak._use_pallas():
        from . import mlkem_pallas  # deferred: pallas import

        ph, plo, batch = keccak.seed_block_words(seeds, 168, 0x1F)
        return mlkem_pallas.sample_ntt_words(ph, plo).T.reshape(batch + (N,))

    buf = keccak.shake128(seeds, _SAMPLE_NTT_BYTES).astype(jnp.int32)
    t = buf.reshape(buf.shape[:-1] + (-1, 3))
    d1 = t[..., 0] + 256 * (t[..., 1] % 16)
    d2 = (t[..., 1] // 16) + 16 * t[..., 2]
    cand = jnp.stack([d1, d2], axis=-1).reshape(buf.shape[:-1] + (-1,))
    nc = cand.shape[-1]
    idx = jnp.arange(nc, dtype=jnp.int32)
    # key: accepted (bit 21 clear) before rejected, index order within each,
    # 12-bit candidate value in the low bits.  Unique keys => stable partition.
    key = jnp.where(cand < Q, 0, 1 << 21) | (idx << 12) | cand
    np2 = 1 << (nc - 1).bit_length()
    key = jnp.pad(
        key,
        [(0, 0)] * (key.ndim - 1) + [(0, np2 - nc)],
        constant_values=1 << 22,
    )
    return bitonic_sort(key)[..., :N] & 0xFFF


def sample_poly_cbd(b: jax.Array, eta: int) -> jax.Array:
    """(..., 64*eta) uint8 PRF output -> (..., 256) int32 CBD_eta polynomial."""
    bits = (b[..., :, None].astype(jnp.int32) >> jnp.arange(8)) & 1
    bits = bits.reshape(b.shape[:-1] + (N, 2, eta))
    x = bits.sum(axis=-1)
    return (x[..., 0] - x[..., 1]) % Q


def _prf_seeds(s: jax.Array, n_consts: np.ndarray) -> jax.Array:
    """PRF seed blocks for a vector of counter bytes: (..., 32) -> (..., len(n_consts), 33) s || n."""
    reps = len(n_consts)
    s_rep = jnp.broadcast_to(s[..., None, :], s.shape[:-1] + (reps, 32))
    n_col = jnp.broadcast_to(
        jnp.asarray(n_consts, dtype=jnp.uint8)[:, None], s.shape[:-1] + (reps, 1)
    )
    return jnp.concatenate([s_rep, n_col], axis=-1)


def _prf_cbd(s: jax.Array, n_consts: np.ndarray, eta: int) -> jax.Array:
    """PRF_eta + SamplePolyCBD: s (..., 32) -> (..., len(n_consts), 256).

    On TPU the SHAKE-256 squeeze and the CBD bit-sums run as one fused
    Pallas kernel (kem/mlkem_pallas.py:cbd_words); elsewhere the jnp
    sponge + sample_poly_cbd path.
    """
    seeds = _prf_seeds(s, n_consts)
    if keccak._use_pallas():
        from . import mlkem_pallas  # deferred: pallas import

        ph, plo, batch = keccak.seed_block_words(seeds, 136, 0x1F)
        return mlkem_pallas.cbd_words(ph, plo, eta=eta).T.reshape(batch + (N,))
    return sample_poly_cbd(keccak.shake256(seeds, 64 * eta), eta)


def _prf_cbd_ntt(s: jax.Array, n_consts: np.ndarray, eta: int) -> jax.Array:
    """``ntt(_prf_cbd(...))`` — fused into ONE Pallas kernel on TPU.

    The noise polynomials that feed matrix products are consumed only in
    the NTT domain, so squeezing, CBD-summing, and all 7 butterfly layers
    run on the same VMEM-resident register tiles; the intermediate CBD
    polynomial never touches HBM (kem/mlkem_pallas.py:cbd_ntt_words).
    Bit-identical to the two-step form on every path."""
    seeds = _prf_seeds(s, n_consts)
    if keccak._use_pallas():
        from . import mlkem_pallas  # deferred: pallas import

        ph, plo, batch = keccak.seed_block_words(seeds, 136, 0x1F)
        return mlkem_pallas.cbd_ntt_words(ph, plo, eta=eta).T.reshape(batch + (N,))
    return ntt(sample_poly_cbd(keccak.shake256(seeds, 64 * eta), eta))


def _expand_matrix(rho: jax.Array, k: int) -> jax.Array:
    """rho (..., 32) -> A_hat (..., k, k, 256) with A[i,j] = SampleNTT(rho||j||i)."""
    ji = np.array([[j, i] for i in range(k) for j in range(k)], dtype=np.uint8)
    rho_rep = jnp.broadcast_to(rho[..., None, :], rho.shape[:-1] + (k * k, 32))
    ji_rep = jnp.broadcast_to(jnp.asarray(ji), rho.shape[:-1] + (k * k, 2))
    seeds = jnp.concatenate([rho_rep, ji_rep], axis=-1)
    a = sample_ntt(seeds)
    return a.reshape(rho.shape[:-1] + (k, k, N))


# --------------------------------------------------------------------------
# K-PKE + ML-KEM (FIPS 203 §5-7), batched
# --------------------------------------------------------------------------


def _kpke_keygen(p: MLKEMParams, d: jax.Array):
    k = p.k
    kin = jnp.concatenate(
        [d, jnp.broadcast_to(jnp.uint8(k), d.shape[:-1] + (1,))], axis=-1
    )
    g = keccak.sha3_512(kin)
    rho, sigma = g[..., :32], g[..., 32:]
    a_hat = _expand_matrix(rho, k)
    noise_hat = _prf_cbd_ntt(sigma, np.arange(2 * k), p.eta1)
    s_hat = noise_hat[..., :k, :]
    e_hat = noise_hat[..., k:, :]
    t_hat = (
        jnp.sum(multiply_ntts(a_hat, s_hat[..., None, :, :]), axis=-2) + e_hat
    ) % Q
    ek = jnp.concatenate(
        [byte_encode(t_hat, 12).reshape(d.shape[:-1] + (384 * k,)), rho], axis=-1
    )
    dk_pke = byte_encode(s_hat, 12).reshape(d.shape[:-1] + (384 * k,))
    return ek, dk_pke


def _kpke_encrypt(p: MLKEMParams, ek: jax.Array, m: jax.Array, r: jax.Array):
    k = p.k
    t_hat = byte_decode(ek[..., : 384 * k].reshape(ek.shape[:-1] + (k, 384)), 12)
    rho = ek[..., 384 * k :]
    a_hat = _expand_matrix(rho, k)
    return _kpke_encrypt_pre(p, t_hat, a_hat, m, r)


def _kpke_encrypt_pre(p: MLKEMParams, t_hat: jax.Array, a_hat: jax.Array,
                      m: jax.Array, r: jax.Array):
    """K-PKE.Encrypt over pre-decoded key material (t_hat, ExpandA output).

    ``t_hat``/``a_hat`` may be unbatched (one key) and broadcast against a
    batched (m, r) — the seam the device operand cache uses to reuse one
    key's ExpandA across every encaps against that key.
    """
    k = p.k
    e1 = _prf_cbd(r, np.arange(k, 2 * k), p.eta2)
    e2 = _prf_cbd(r, np.array([2 * k]), p.eta2)[..., 0, :]
    y_hat = _prf_cbd_ntt(r, np.arange(k), p.eta1)
    # u = invNTT(A^T ∘ y_hat) + e1 : contract over row index i of A[i,j]
    u = (
        ntt_inv(jnp.sum(multiply_ntts(a_hat, y_hat[..., :, None, :]), axis=-3) % Q)
        + e1
    ) % Q
    mu = decompress(byte_decode(m, 1), 1)
    v = (
        ntt_inv(jnp.sum(multiply_ntts(t_hat, y_hat), axis=-2) % Q) + e2 + mu
    ) % Q
    c1e = byte_encode(compress(u, p.du), p.du)  # (..., k, 32*du)
    c1 = c1e.reshape(c1e.shape[:-2] + (32 * p.du * k,))
    c2 = byte_encode(compress(v, p.dv), p.dv)
    return jnp.concatenate([c1, c2], axis=-1)


def _kpke_decrypt(p: MLKEMParams, dk_pke: jax.Array, c: jax.Array):
    k, du, dv = p.k, p.du, p.dv
    c1 = c[..., : 32 * du * k].reshape(c.shape[:-1] + (k, 32 * du))
    u = decompress(byte_decode(c1, du), du)
    v = decompress(byte_decode(c[..., 32 * du * k :], dv), dv)
    s_hat = byte_decode(dk_pke.reshape(dk_pke.shape[:-1] + (k, 384)), 12)
    w = (v - ntt_inv(jnp.sum(multiply_ntts(s_hat, ntt(u)), axis=-2) % Q)) % Q
    return byte_encode(compress(w, 1), 1)


def keygen(p: MLKEMParams, d: jax.Array, z: jax.Array):
    """ML-KEM.KeyGen_internal: seeds d, z (..., 32) -> ek (..., ek_len), dk (..., dk_len)."""
    d = jnp.asarray(d, jnp.uint8)
    z = jnp.asarray(z, jnp.uint8)
    ek, dk_pke = _kpke_keygen(p, d)
    dk = jnp.concatenate([dk_pke, ek, keccak.sha3_256(ek), z], axis=-1)
    return ek, dk


def encaps(p: MLKEMParams, ek: jax.Array, m: jax.Array):
    """ML-KEM.Encaps_internal: ek, m (..., 32) -> K (..., 32), c (..., ct_len)."""
    ek = jnp.asarray(ek, jnp.uint8)
    m = jnp.asarray(m, jnp.uint8)
    g = keccak.sha3_512(jnp.concatenate([m, keccak.sha3_256(ek)], axis=-1))
    key, r = g[..., :32], g[..., 32:]
    c = _kpke_encrypt(p, ek, m, r)
    return key, c


def precompute_ek(p: MLKEMParams, ek: jax.Array) -> dict[str, jax.Array]:
    """Per-key device state encaps reuses across dispatches: the decoded
    t_hat, ExpandA(rho) — ~85% of encaps' sampling work — and H(ek).
    Computed once per key by the operand cache (provider/opcache.py) so
    repeat encaps against the same peer key skip the re-upload and the
    matrix expansion.  May be unbatched; broadcasts against any m batch."""
    ek = jnp.asarray(ek, jnp.uint8)
    k = p.k
    return {
        "t_hat": byte_decode(ek[..., : 384 * k].reshape(ek.shape[:-1] + (k, 384)), 12),
        "a_hat": _expand_matrix(ek[..., 384 * k :], k),
        "h_ek": keccak.sha3_256(ek),
    }


def encaps_pre(p: MLKEMParams, pre: dict[str, jax.Array], m: jax.Array):
    """``encaps`` over a ``precompute_ek`` pytree — bit-identical output
    (the precompute is a pure hoist of the key-dependent prefix)."""
    m = jnp.asarray(m, jnp.uint8)
    h_ek = jnp.broadcast_to(pre["h_ek"], m.shape[:-1] + (32,))
    g = keccak.sha3_512(jnp.concatenate([m, h_ek], axis=-1))
    key, r = g[..., :32], g[..., 32:]
    c = _kpke_encrypt_pre(p, pre["t_hat"], pre["a_hat"], m, r)
    return key, c


def decaps(p: MLKEMParams, dk: jax.Array, c: jax.Array):
    """ML-KEM.Decaps_internal with implicit rejection (branch-free select)."""
    dk = jnp.asarray(dk, jnp.uint8)
    c = jnp.asarray(c, jnp.uint8)
    k = p.k
    dk_pke = dk[..., : 384 * k]
    ek = dk[..., 384 * k : 768 * k + 32]
    h = dk[..., 768 * k + 32 : 768 * k + 64]
    z = dk[..., 768 * k + 64 :]
    m2 = _kpke_decrypt(p, dk_pke, c)
    g = keccak.sha3_512(jnp.concatenate([m2, h], axis=-1))
    key2, r2 = g[..., :32], g[..., 32:]
    key_bar = keccak.shake256(jnp.concatenate([z, c], axis=-1), 32)
    c2 = _kpke_encrypt(p, ek, m2, r2)
    ok = jnp.all(c == c2, axis=-1, keepdims=True)
    return jnp.where(ok, key2, key_bar)


# --------------------------------------------------------------------------
# Jitted per-parameter-set entry points
# --------------------------------------------------------------------------


@functools.cache
def get(name: str):
    """Jitted (keygen, encaps, decaps) triple for a parameter-set name."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(keygen, p)),
        jax.jit(functools.partial(encaps, p)),
        jax.jit(functools.partial(decaps, p)),
    )


def encaps_cold(p: MLKEMParams, ek: jax.Array, m: jax.Array):
    """Cache-filling encaps: ONE dispatch returning both the per-key device
    state and the op results.  A cache miss must not cost an extra round
    trip over the uncached path (a separate precompute dispatch would), so
    the precompute rides along as extra outputs — its arrays stay
    device-resident (jit outputs) and go straight into the operand cache."""
    pre = precompute_ek(p, ek)
    key, c = encaps_pre(p, pre, m)
    return pre, key, c


@functools.cache
def get_pre(name: str):
    """Jitted (encaps_cold, encaps_pre) pair for the device operand cache
    (provider/opcache.py): cold fills the cache in one dispatch; pre runs
    over a cached pytree, skipping the ek upload and ExpandA."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(encaps_cold, p)),
        jax.jit(functools.partial(encaps_pre, p)),
    )
