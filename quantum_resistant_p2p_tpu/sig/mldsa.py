"""Batched ML-DSA (FIPS 204) in JAX — lattice signatures on the TPU VPU.

TPU-native design
-----------------
* q = 8380417 < 2**23, so residues fit int32 but products do not; TPUs have no
  64-bit lanes.  ``_mm`` performs modular multiplication via a Horner split of
  one operand into 8-bit limbs: every intermediate stays below 2**31, all in
  int32 — no 64-bit emulation, fully vectorised.
* The signing rejection loop (reference behavior: liboqs ML-DSA via
  crypto/signatures.py:157; spec loop in pyref.mldsa_ref.sign_internal) is a
  ``lax.while_loop`` over whole *batches* with per-lane done masks and
  per-lane kappa counters: lanes that already produced a valid signature keep
  their result via ``jnp.where`` while stragglers retry, reproducing each
  lane's serial kappa sequence exactly (bit-exact vs the oracle).
* SampleInBall's data-dependent Fisher–Yates is a fixed 1024-step ``lax.scan``
  over the SHAKE buffer bytes, maintaining (c, i, sign-bit index) state — same
  fixed-buffer convention as the pyref oracle.
* ExpandA / ExpandS rejection sampling uses the same fixed-squeeze +
  gather-free bitonic compaction as kem.mlkem.sample_ntt (XLA argsort /
  take_along_axis serialise per-lane on TPU; see core/sortnet.py).
* Variable-length messages are hashed to ``mu = SHAKE256(tr||M', 64)``
  host-side (cheap, public data); the device kernels take fixed-shape mu
  batches.  Key-dependent NTTs (A_hat, s1_hat, s2_hat, t0_hat) are hoisted out
  of the per-message batch and computed once per key.

Bit-exactness oracle: ``pyref.mldsa_ref`` (tests/test_mldsa.py).
Replaces (reference): MLDSASignature's per-call liboqs objects
(crypto/signatures.py:58-188, vendor/oqs.py:506-583).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import keccak
from ..utils import next_pow2 as _next_pow2_i
from ..core.sortnet import bitonic_sort, bitonic_sort_pairs
from ..pyref.mldsa_ref import (
    D,
    MLDSA44,
    MLDSA65,
    MLDSA87,
    MLDSAParams,
    PARAMS,
    ZETAS,
)

Q = 8380417
N = 256
_N_INV = pow(256, -1, Q)
_ZETAS = np.asarray(ZETAS, dtype=np.int32)

MAX_SIGN_ITERS = 128  # P[a lane needs >128 attempts] < 1e-12 (avg ~4-6 attempts)

# Test/debug guard: fail loudly if the truncated 1024-candidate sampler
# buffers would diverge from the oracle's full-buffer convention (advisor
# round-2 finding; P < 1e-94 per poly, but silent divergence is worse than
# a crash).  Enabled by tests; off in production (adds a host callback).
# NOTE: read at TRACE time — jitted entry points (get()) bake the setting
# into their cached trace, so set it before the first call of a fresh
# process/jit wrapper (same caveat as QRP2P_PALLAS).
STRICT_SAMPLERS = False


def _check_sampler_fill(ok, name: str) -> None:
    if not np.all(np.asarray(ok)):
        raise AssertionError(
            f"{name}: fewer than {N} accepted candidates in the truncated "
            "sort buffer — output diverges from the pyref oracle convention"
        )

# --------------------------------------------------------------------------
# int32 modular arithmetic without 64-bit lanes
# --------------------------------------------------------------------------


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """(a * b) mod q for a, b int32 in [0, q); all intermediates < 2**31.

    Horner over 8-bit limbs of b: a*b2 < 2**30, r<<8 < 2**31, a*b_i < 2**31.
    """
    b2 = b >> 16
    b1 = (b >> 8) & 0xFF
    b0 = b & 0xFF
    r = (a * b2) % Q
    r = (((r << 8) % Q) + (a * b1) % Q) % Q
    r = (((r << 8) % Q) + (a * b0) % Q) % Q
    return r


def _center(x: jax.Array, m: int = Q) -> jax.Array:
    """mod± representative in (-m/2, m/2]."""
    x = x % m
    return jnp.where(x > m // 2, x - m, x)


# --------------------------------------------------------------------------
# NTT over Z_q[X]/(X^256+1) (FIPS 204 §7.5) — complete 256-point transform
# --------------------------------------------------------------------------


def _ntt_pallas(f: jax.Array, inverse: bool) -> jax.Array:
    """Route one (inv)NTT through the VMEM-resident kernel: flatten every
    leading axis into the lane dimension (polys transform independently),
    transpose to the words layout, and back."""
    from . import mldsa_pallas  # deferred: pallas import

    sh = f.shape
    x = f.reshape(-1, N).T  # (256, L)
    out = mldsa_pallas.ntt_words(x, inverse=inverse)
    return out.T.reshape(sh)


def ntt(f: jax.Array) -> jax.Array:
    """(..., 256) int32 in [0,q) -> NTT domain.

    On TPU the transform runs as one VMEM-resident Pallas program (1 HBM
    read + 1 write instead of 16 stage round-trips; sig/mldsa_pallas.py) —
    the sign rejection loop runs ~29 poly transforms per attempt."""
    if keccak._use_pallas():
        return _ntt_pallas(f, inverse=False)
    zetas = jnp.asarray(_ZETAS)
    k = 1
    length = 128
    while length >= 1:
        groups = N // (2 * length)
        z = zetas[k : k + groups]
        fr = f.reshape(f.shape[:-1] + (groups, 2, length))
        f0, f1 = fr[..., 0, :], fr[..., 1, :]
        t = _mm(jnp.broadcast_to(z[:, None], f1.shape), f1)
        f = jnp.stack([(f0 + t) % Q, (f0 - t) % Q], axis=-2).reshape(f.shape)
        k += groups
        length //= 2
    return f


def ntt_inv(f: jax.Array) -> jax.Array:
    if keccak._use_pallas():
        return _ntt_pallas(f, inverse=True)
    zetas = jnp.asarray(_ZETAS)
    k = 255
    length = 1
    while length <= 128:
        groups = N // (2 * length)
        z = zetas[k - groups + 1 : k + 1][::-1]
        fr = f.reshape(f.shape[:-1] + (groups, 2, length))
        f0, f1 = fr[..., 0, :], fr[..., 1, :]
        s = (f0 + f1) % Q
        t = _mm(jnp.broadcast_to(z[:, None], f1.shape), (f1 - f0) % Q)
        f = jnp.stack([s, t], axis=-2).reshape(f.shape)
        k -= groups
        length *= 2
    return _mm(f, jnp.asarray(np.int32(_N_INV)))


def pw_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    a, b = jnp.broadcast_arrays(a, b)
    return _mm(a, b)


# --------------------------------------------------------------------------
# Bit packing (FIPS 204 §7.1), batched
# --------------------------------------------------------------------------


def simple_bit_pack(vals: jax.Array, bits: int) -> jax.Array:
    """(..., 256) int32 in [0, 2^bits) -> (..., 32*bits) uint8, LSB-first.

    Byte-assembly formulation: the LSB-first bitstream is periodic with
    period lcm(bits, 8) — ``pc`` coefficients fill ``pb`` bytes — so each
    output byte position is a STATIC shift/or of at most a few
    coefficients.  The naive bit-matrix route (explode to (..., 256, bits)
    then regroup by 8) materialises a bits-x blowup in HBM: for the z
    packing inside the sign rejection loop (bits=20, batch 8192 x l=5)
    that alone measured tens of ms per attempt (r4 prefix probe)."""
    import math

    period = math.lcm(bits, 8)
    pb, pc = period // 8, period // bits
    g = vals.reshape(vals.shape[:-1] + (N // pc, pc))
    outs = []
    for j in range(pb):
        lo = 8 * j
        acc = None
        for c in range(pc):
            s = c * bits
            if s + bits <= lo or s >= lo + 8:
                continue
            sh = lo - s
            contrib = (g[..., c] >> sh) if sh >= 0 else (g[..., c] << (-sh))
            acc = contrib if acc is None else (acc | contrib)
        outs.append(acc & 0xFF)
    b = jnp.stack(outs, axis=-1)  # (..., 256/pc, pb)
    return b.reshape(vals.shape[:-1] + (32 * bits,)).astype(jnp.uint8)


def simple_bit_unpack(b: jax.Array, bits: int) -> jax.Array:
    """(..., 32*bits) uint8 -> (..., 256) int32 (byte-assembly, see pack)."""
    import math

    period = math.lcm(bits, 8)
    pb, pc = period // 8, period // bits
    g = b.reshape(b.shape[:-1] + (N // pc, pb)).astype(jnp.int32)
    outs = []
    for c in range(pc):
        s = c * bits
        acc = None
        for j in range(pb):
            lo = 8 * j
            if lo + 8 <= s or lo >= s + bits:
                continue
            sh = lo - s
            contrib = (g[..., j] << sh) if sh >= 0 else (g[..., j] >> (-sh))
            acc = contrib if acc is None else (acc | contrib)
        outs.append(acc & ((1 << bits) - 1))
    x = jnp.stack(outs, axis=-1)  # (..., 256/pc, pc)
    return x.reshape(b.shape[:-1] + (N,))


def bit_pack(vals: jax.Array, up: int, bits: int) -> jax.Array:
    return simple_bit_pack((up - _center(vals)), bits)


def bit_unpack(b: jax.Array, up: int, bits: int) -> jax.Array:
    return (up - simple_bit_unpack(b, bits)) % Q


# --------------------------------------------------------------------------
# Rounding (FIPS 204 §7.4), batched
# --------------------------------------------------------------------------


def power2round(r: jax.Array) -> tuple[jax.Array, jax.Array]:
    r = r % Q
    r0 = _center(r, 1 << D)
    return (r - r0) >> D, r0


def decompose(p: MLDSAParams, r: jax.Array) -> tuple[jax.Array, jax.Array]:
    alpha = 2 * p.gamma2
    r = r % Q
    r0 = _center(r, alpha)
    wrap = (r - r0) == (Q - 1)
    r1 = jnp.where(wrap, 0, (r - r0) // alpha)
    r0 = jnp.where(wrap, r0 - 1, r0)
    return r1, r0


def use_hint(p: MLDSAParams, h: jax.Array, r: jax.Array) -> jax.Array:
    m = (Q - 1) // (2 * p.gamma2)
    r1, r0 = decompose(p, r)
    up = jnp.where(r0 > 0, (r1 + 1) % m, (r1 - 1) % m)
    return jnp.where(h != 0, up, r1)


# --------------------------------------------------------------------------
# Samplers (FIPS 204 §7.3), batched fixed-shape
# --------------------------------------------------------------------------

_REJ_NTT_BYTES = 168 * 7  # 392 candidates for 256 slots (matches oracle buffer)
_REJ_BOUNDED_BYTES = 136 * 4  # 1088 nibbles for 256 slots
_REJ_BOUNDED_SORT = 1024  # nibbles fed to the compaction (see rej_bounded_poly)


def rej_ntt_poly(seeds: jax.Array) -> jax.Array:
    """(..., 34) uint8 -> (..., 256) int32 NTT-domain uniform polys.

    Compaction is the gather-free bitonic network (core/sortnet.py) — XLA's
    stable argsort + take_along_axis serialise per-lane on TPU (the same
    hazard kem/mlkem.py:sample_ntt documents).  23-bit candidates don't fit
    an int32 key next to the index, so the pairs variant carries them.

    On TPU the whole pipeline (SHAKE squeeze -> extraction -> compaction)
    is one fused Pallas kernel with every intermediate in VMEM
    (sig/mldsa_pallas.py) — the jnp pairs-network alone moves ~11 GB of
    HBM per 1024-batch of ExpandA otherwise.
    """
    if keccak._use_pallas():
        from . import mldsa_pallas  # deferred: pallas import

        ph, plo, batch = keccak.seed_block_words(seeds, 168, 0x1F)
        return mldsa_pallas.rej_ntt_words(ph, plo).T.reshape(batch + (N,))

    buf = keccak.shake128(seeds, _REJ_NTT_BYTES).astype(jnp.int32)
    t = buf.reshape(buf.shape[:-1] + (-1, 3))
    cand = t[..., 0] | (t[..., 1] << 8) | ((t[..., 2] & 0x7F) << 16)
    nc = cand.shape[-1]
    idx = jnp.arange(nc, dtype=jnp.int32)
    key = jnp.where(cand < Q, 0, 1 << 10) | idx  # accepted first, spec order
    np2 = 1 << (nc - 1).bit_length()
    pad = [(0, 0)] * (key.ndim - 1) + [(0, np2 - nc)]
    key = jnp.pad(key, pad, constant_values=1 << 11)
    cand = jnp.pad(cand, pad)
    _, cand = bitonic_sort_pairs(key, cand)
    return cand[..., :N]


def rej_bounded_poly(eta: int, seeds: jax.Array) -> jax.Array:
    """(..., 66) uint8 -> (..., 256) int32 coefficients in {q-eta..q+eta mod q}.

    The raw nibble rides in the low bits of the (unique) sort key, so one
    int32 bitonic network replaces the serialised argsort; the eta-map is
    applied after compaction.  Only the first 1024 of the 1088 squeezed
    nibbles feed the network (1024 is the power of two the sort wants):
    output differs from the full-buffer formulation only if fewer than 256
    of the first 1024 candidates are accepted — P < 1e-164 for eta=2
    (accept 15/16), < 1e-94 for eta=4 (accept 9/16).

    On TPU the whole pipeline is one fused Pallas kernel
    (sig/mldsa_pallas.py), same recipe as rej_ntt_poly.
    """
    if keccak._use_pallas():
        from . import mldsa_pallas  # deferred: pallas import

        ph, plo, batch = keccak.seed_block_words(seeds, 136, 0x1F)
        z = mldsa_pallas.rej_bounded_words(ph, plo, eta=eta).T.reshape(batch + (N,))
    else:
        buf = keccak.shake256(seeds, _REJ_BOUNDED_BYTES).astype(jnp.int32)
        z = jnp.stack([buf & 0xF, buf >> 4], axis=-1).reshape(buf.shape[:-1] + (-1,))
        z = z[..., :_REJ_BOUNDED_SORT]
        ok = z < (15 if eta == 2 else 9)
        idx = jnp.arange(_REJ_BOUNDED_SORT, dtype=jnp.int32)
        key = jnp.where(ok, 0, 1 << 16) | (idx << 4) | z
        skey = bitonic_sort(key)
        if STRICT_SAMPLERS:
            # slot N-1 must still be an accepted candidate (reject bit clear)
            jax.debug.callback(
                _check_sampler_fill, skey[..., N - 1] < (1 << 16), "rej_bounded_poly"
            )
        z = skey[..., :N] & 0xF
    if eta == 2:
        return (2 - z % 5) % Q
    return (4 - z) % Q


def expand_a(p: MLDSAParams, rho: jax.Array) -> jax.Array:
    """rho (..., 32) -> A_hat (..., k, l, 256); A[r,s] = RejNTTPoly(rho||s||r)."""
    sr = np.array([[s, r] for r in range(p.k) for s in range(p.l)], dtype=np.uint8)
    rho_rep = jnp.broadcast_to(rho[..., None, :], rho.shape[:-1] + (p.k * p.l, 32))
    sr_rep = jnp.broadcast_to(jnp.asarray(sr), rho.shape[:-1] + (p.k * p.l, 2))
    a = rej_ntt_poly(jnp.concatenate([rho_rep, sr_rep], axis=-1))
    return a.reshape(rho.shape[:-1] + (p.k, p.l, N))


def expand_s(p: MLDSAParams, rhop: jax.Array) -> tuple[jax.Array, jax.Array]:
    """rhop (..., 64) -> s1 (..., l, 256), s2 (..., k, 256)."""
    total = p.l + p.k
    n16 = np.zeros((total, 2), dtype=np.uint8)
    n16[:, 0] = np.arange(total) & 0xFF
    rep = jnp.broadcast_to(rhop[..., None, :], rhop.shape[:-1] + (total, 64))
    seeds = jnp.concatenate(
        [rep, jnp.broadcast_to(jnp.asarray(n16), rhop.shape[:-1] + (total, 2))], axis=-1
    )
    s = rej_bounded_poly(p.eta, seeds)
    return s[..., : p.l, :], s[..., p.l :, :]


def expand_mask(p: MLDSAParams, rhopp: jax.Array, kappa: jax.Array) -> jax.Array:
    """rhopp (..., 64), kappa (...,) int32 -> y (..., l, 256).

    kappa is traced data (per-lane counters differ), so the 2-byte LE suffix is
    built from arithmetic on the traced value.
    """
    kr = kappa[..., None] + jnp.arange(p.l)  # (..., l)
    suffix = jnp.stack([kr & 0xFF, (kr >> 8) & 0xFF], axis=-1).astype(jnp.uint8)
    rep = jnp.broadcast_to(rhopp[..., None, :], rhopp.shape[:-1] + (p.l, 64))
    buf = keccak.shake256(jnp.concatenate([rep, suffix], axis=-1), 32 * p.z_bits)
    return bit_unpack(buf, p.gamma1, p.z_bits)


_BALL_BYTES = 8 + 1024  # fixed SHAKE squeeze, same convention as the oracle


def sample_in_ball(p: MLDSAParams, ctilde: jax.Array) -> jax.Array:
    """(..., lambda/4) uint8 -> (..., 256) int32 with tau ±1 coefficients.

    Gather-free reformulation of the spec's Fisher-Yates (fixed 1024-byte
    buffer, same convention as the oracle).  The naive per-byte scan needs a
    dynamic gather + two dynamic scatters per step x 1024 steps, which
    serialise per-lane on TPU (measured 24 us/op — 73% of a whole verify).
    Three phases instead:

    1. a scalar scan over the 1024 bytes carrying only the insertion index
       ``i`` per lane — which bytes are *accepted* depends on nothing else;
    2. a bitonic compaction of the accepted bytes to the front (spec order);
    3. ``tau`` static swap steps: at the s-th accepted swap the insertion
       position is ALWAYS ``N - tau + s`` (a static index) and the sign bit
       index is ``s``, so only the ``j`` side needs a one-hot mask.  The
       sign write lands after the ``c[i] = c[j]`` copy, preserving the
       ``j == i`` overwrite order of the sequential formulation.
    """
    buf = keccak.shake256(ctilde, _BALL_BYTES)
    signs = buf[..., :8]
    # 64 sign bits as two uint32 words
    s_lo = jnp.sum(
        signs[..., :4].astype(jnp.uint32) << (8 * jnp.arange(4, dtype=jnp.uint32)), axis=-1
    )
    s_hi = jnp.sum(
        signs[..., 4:8].astype(jnp.uint32) << (8 * jnp.arange(4, dtype=jnp.uint32)), axis=-1
    )
    rejb = buf[..., 8:].astype(jnp.int32)
    batch = ctilde.shape[:-1]
    tau = p.tau
    nb = rejb.shape[-1]

    def step(i, j):
        take = (i < N) & (j <= i)
        return i + take, take

    i0 = jnp.full(batch, N - tau, dtype=jnp.int32)
    _, takes = lax.scan(step, i0, jnp.moveaxis(rejb, -1, 0))
    takes = jnp.moveaxis(takes, 0, -1)  # (..., 1024) bool
    ntakes = jnp.sum(takes, axis=-1)

    # accepted bytes to the front, spec order (nb is a power of two)
    idx = jnp.arange(nb, dtype=jnp.int32)
    key = jnp.where(takes, 0, 1 << 18) | (idx << 8) | rejb
    j_acc = bitonic_sort(key)[..., :tau] & 0xFF

    c = jnp.zeros(batch + (N,), dtype=jnp.int32)
    pos = jnp.arange(N, dtype=jnp.int32)
    for s in range(tau):
        valid = s < ntakes
        mask = (pos == j_acc[..., s, None]) & valid[..., None]
        cj = jnp.sum(c * mask, axis=-1)
        bit = ((s_lo >> s) if s < 32 else (s_hi >> (s - 32))) & 1
        sign_val = jnp.where(bit == 0, 1, Q - 1).astype(jnp.int32)
        tgt = N - tau + s
        c = c.at[..., tgt].set(jnp.where(valid, cj, c[..., tgt]))
        c = jnp.where(mask, sign_val[..., None], c)
    return c


# --------------------------------------------------------------------------
# Hint packing (FIPS 204 §7.1 HintBitPack / HintBitUnpack), batched
# --------------------------------------------------------------------------


def hint_bit_pack(p: MLDSAParams, h: jax.Array) -> jax.Array:
    """h (..., k, 256) in {0,1} -> (..., omega + k) uint8.

    Gather/scatter/sort-free: the destination byte of each set hint bit is
    its prefix rank (cumsum) plus the preceding rows' total, and the output
    is a one-hot contraction out[w] = sum_n pos_n * [dest_n == w] over the
    k*256 candidate bits — (omega+k) x 1536 compares per lane, pure VPU.
    The previous stable-argsort + put_along_axis formulation serialised
    per-lane on TPU and dominated the sign attempt (r4 prefix probe: the
    pack stage was ~68%% of the whole attempt at batch 8192)."""
    batch = h.shape[:-2]
    h = h.astype(jnp.int32)
    counts = jnp.sum(h, axis=-1)  # (..., k)
    ends = jnp.cumsum(counts, axis=-1)
    starts = ends - counts
    # rank of each set bit within its row (0-based among ones, index order)
    rank = jnp.cumsum(h, axis=-1) - h
    dest = jnp.where(h == 1, starts[..., None] + rank, -1)  # (..., k, 256)
    npos = jnp.arange(N, dtype=jnp.int32)
    flat_dest = dest.reshape(batch + (1, -1))  # (..., 1, k*256)
    flat_pos = jnp.broadcast_to(
        jnp.tile(npos, h.shape[-2]), flat_dest.shape[:-2] + (flat_dest.shape[-1],)
    )[..., None, :]
    w = jnp.arange(p.omega, dtype=jnp.int32)[..., :, None]  # (omega, 1)
    packed = jnp.sum(
        jnp.where(flat_dest == w, flat_pos, 0), axis=-1
    )  # (..., omega)
    out = jnp.concatenate([packed, ends], axis=-1)
    return out.astype(jnp.uint8)


def hint_bit_unpack(p: MLDSAParams, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., omega + k) uint8 -> (h (..., k, 256), ok (...,) bool)."""
    pos = b[..., : p.omega].astype(jnp.int32)  # (..., omega)
    ends = b[..., p.omega :].astype(jnp.int32)  # (..., k)
    starts = jnp.concatenate([jnp.zeros_like(ends[..., :1]), ends[..., :-1]], axis=-1)
    ok = jnp.all(ends >= starts, axis=-1) & jnp.all(ends <= p.omega, axis=-1)
    widx = jnp.arange(p.omega)
    in_row = (widx >= starts[..., None]) & (widx < ends[..., None])  # (..., k, omega)
    # strictly increasing within each row
    prev_same_row = in_row & (widx > starts[..., None])
    inc_ok = jnp.where(
        prev_same_row,
        pos[..., None, :] > jnp.roll(pos, 1, axis=-1)[..., None, :],
        True,
    )
    ok = ok & jnp.all(inc_ok, axis=(-1, -2))
    total = ends[..., -1]
    ok = ok & jnp.all(jnp.where(widx >= total[..., None], pos == 0, True), axis=-1)
    # scatter ones: h[r, pos[w]] = 1 for w in [starts[r], ends[r])
    h = jnp.zeros(b.shape[:-1] + (p.k, N + 1), dtype=jnp.int32)
    dest = jnp.where(in_row, pos[..., None, :], N)  # sentinel column dropped
    h = jnp.put_along_axis(h, dest, jnp.where(in_row, 1, 0), axis=-1, inplace=False)
    return h[..., :N], ok


# --------------------------------------------------------------------------
# KeyGen (FIPS 204 Algorithm 6), batched
# --------------------------------------------------------------------------


def _matvec(a_hat: jax.Array, v_hat: jax.Array) -> jax.Array:
    """(..., k, l, 256) ∘ (..., l, 256) -> (..., k, 256) pointwise-NTT matvec."""
    return jnp.sum(pw_mul(a_hat, v_hat[..., None, :, :]), axis=-2) % Q


def keygen(p: MLDSAParams, xi: jax.Array) -> tuple[jax.Array, jax.Array]:
    """xi (..., 32) uint8 -> (pk (..., pk_len), sk (..., sk_len)) uint8."""
    xi = jnp.asarray(xi, jnp.uint8)
    batch = xi.shape[:-1]
    kl = jnp.broadcast_to(jnp.asarray([p.k, p.l], jnp.uint8), batch + (2,))
    seed = keccak.shake256(jnp.concatenate([xi, kl], axis=-1), 128)
    rho, rhop, cap_k = seed[..., :32], seed[..., 32:96], seed[..., 96:]
    a_hat = expand_a(p, rho)
    s1, s2 = expand_s(p, rhop)
    s1_hat = ntt(s1)
    t = (ntt_inv(_matvec(a_hat, s1_hat)) + s2) % Q
    t1, t0 = power2round(t)
    pk = jnp.concatenate(
        [rho, simple_bit_pack(t1, 23 - D).reshape(batch + (-1,))], axis=-1
    )
    tr = keccak.shake256(pk, 64)
    sk = jnp.concatenate(
        [
            rho,
            cap_k,
            tr,
            bit_pack(s1, p.eta, p.s_bits).reshape(batch + (-1,)),
            bit_pack(s2, p.eta, p.s_bits).reshape(batch + (-1,)),
            bit_pack(t0, 1 << (D - 1), D).reshape(batch + (-1,)),
        ],
        axis=-1,
    )
    return pk, sk


# --------------------------------------------------------------------------
# Sign (FIPS 204 Algorithm 7), batched with masked retry loop
# --------------------------------------------------------------------------


def _unpack_sk(p: MLDSAParams, sk: jax.Array):
    batch = sk.shape[:-1]
    rho, cap_k, tr = sk[..., :32], sk[..., 32:64], sk[..., 64:128]
    off = 128
    sb = 32 * p.s_bits
    s1 = bit_unpack(sk[..., off : off + p.l * sb].reshape(batch + (p.l, sb)), p.eta, p.s_bits)
    off += p.l * sb
    s2 = bit_unpack(sk[..., off : off + p.k * sb].reshape(batch + (p.k, sb)), p.eta, p.s_bits)
    off += p.k * sb
    tb = 32 * D
    t0 = bit_unpack(
        sk[..., off : off + p.k * tb].reshape(batch + (p.k, tb)), 1 << (D - 1), D
    )
    return rho, cap_k, tr, s1, s2, t0


def _inf_norm(x: jax.Array, axes) -> jax.Array:
    return jnp.max(jnp.abs(_center(x)), axis=axes)


def precompute_sk(p: MLDSAParams, sk: jax.Array) -> dict[str, jax.Array]:
    """Per-key device state the sign loop reuses across every dispatch.

    ExpandA and the key-dependent NTTs (s1, s2, t0) depend only on the
    secret key — hoisting them out of ``sign_mu`` lets the operand cache
    (provider/opcache.py) compute them ONCE per key and keep them
    device-resident, so repeat sign dispatches against the same key skip
    both the sk re-upload and the ExpandA work.  The returned pytree may be
    unbatched (one key) and broadcasts against any mu/rnd batch.
    """
    rho, cap_k, tr, s1, s2, t0 = _unpack_sk(p, jnp.asarray(sk, jnp.uint8))
    del tr
    return {
        "cap_k": cap_k,
        "a_hat": expand_a(p, rho),
        "s1_hat": ntt(s1),
        "s2_hat": ntt(s2),
        "t0_hat": ntt(t0),
    }


def sign_mu_rounds(p: MLDSAParams, sk: jax.Array, mu: jax.Array, rnd: jax.Array,
                   kappa0: jax.Array, n_iters: int, unroll: int = 1):
    """At most ``n_iters`` rejection-loop iterations from per-lane ``kappa0``.

    Returns (sigma, done, kappa): each lane's kappa sequence depends only on
    its own rhopp and counter, so a caller may stop, compact the unfinished
    lanes into a smaller batch, and resume from the returned kappa — the
    produced signatures are bit-identical to the run-to-completion loop
    (the compact-and-refill driver below, ``sign_mu_compact``).

    ``unroll`` runs that many attempts per ``while_loop`` body (masked
    selection keeps each lane's FIRST accept, so results are bit-identical;
    ``n_iters`` must be a multiple of ``unroll`` so the attempt budget —
    and thus the returned (done, kappa) resumption state — is exactly the
    unroll=1 contract).  A negative result from an earlier platform, not
    measured on this chip: an in-loop attempt took ~155 ms at batch 8192
    while its standalone stages summed to ~55 ms, but unroll=5 changed
    nothing (784.7 vs 794.6 ms for 5 attempts) — the gap was not the
    iteration boundary; standalone stage timings are flattered by
    cross-dispatch overlap, and the serial in-context chain is the true
    cost.  Default 1.
    """
    return _sign_mu_core(p, precompute_sk(p, sk), mu, rnd, kappa0, n_iters,
                         unroll)


def _sign_mu_core(p: MLDSAParams, pre: dict[str, jax.Array], mu: jax.Array,
                  rnd: jax.Array, kappa0: jax.Array, n_iters: int,
                  unroll: int = 1):
    """Rejection loop over precomputed key state (see ``precompute_sk``)."""
    if unroll < 1 or n_iters % unroll:
        raise ValueError(f"n_iters ({n_iters}) must be a positive multiple "
                         f"of unroll ({unroll})")
    mu = jnp.asarray(mu, jnp.uint8)
    rnd = jnp.asarray(rnd, jnp.uint8)
    batch = mu.shape[:-1]
    a_hat = pre["a_hat"]
    s1_hat, s2_hat, t0_hat = pre["s1_hat"], pre["s2_hat"], pre["t0_hat"]
    cap_k = jnp.broadcast_to(pre["cap_k"], batch + (32,))
    rhopp = keccak.shake256(jnp.concatenate([cap_k, rnd, mu], axis=-1), 64)

    zb = 32 * p.z_bits
    sig_len = p.sig_len
    done0 = jnp.zeros(batch, dtype=bool)
    kappa_init = jnp.broadcast_to(jnp.asarray(kappa0, jnp.int32), batch)
    sig0 = jnp.zeros(batch + (sig_len,), dtype=jnp.uint8)

    def attempt(kappa):
        """One rejection-loop iteration for every lane; returns (ok, sigma)."""
        y = expand_mask(p, rhopp, kappa)
        w = ntt_inv(_matvec(a_hat, ntt(y)))
        w1, _ = decompose(p, w)
        w1_enc = simple_bit_pack(w1, p.w1_bits).reshape(batch + (-1,))
        ctilde = keccak.shake256(
            jnp.concatenate([mu, w1_enc], axis=-1), p.ctilde_len
        )
        c_hat = ntt(sample_in_ball(p, ctilde))
        cs1 = ntt_inv(pw_mul(c_hat[..., None, :], s1_hat))
        z = (y + cs1) % Q
        ok = _inf_norm(z, (-1, -2)) < p.gamma1 - p.beta
        cs2 = ntt_inv(pw_mul(c_hat[..., None, :], s2_hat))
        r_minus = (w - cs2) % Q
        _, r0 = decompose(p, r_minus)
        ok &= jnp.max(jnp.abs(r0), axis=(-1, -2)) < p.gamma2 - p.beta
        ct0 = ntt_inv(pw_mul(c_hat[..., None, :], t0_hat))
        ok &= _inf_norm(ct0, (-1, -2)) < p.gamma2
        h_arg = (_center(r_minus) + _center(ct0)) % Q
        hi_with = decompose(p, h_arg)[0]
        hi_base = decompose(p, r_minus)[0]
        h = (hi_with != hi_base).astype(jnp.int32)
        ok &= jnp.sum(h, axis=(-1, -2)) <= p.omega
        sigma = jnp.concatenate(
            [
                ctilde,
                bit_pack(z, p.gamma1, p.z_bits).reshape(batch + (-1,)),
                hint_bit_pack(p, h),
            ],
            axis=-1,
        )
        return ok, sigma

    def cond(state):
        done, _, _, it = state
        return (~jnp.all(done)) & (it < n_iters)

    def body(state):
        done, kappa, sig, it = state
        for _ in range(unroll):
            ok, sigma = attempt(kappa)
            newly = (~done) & ok
            sig = jnp.where(newly[..., None], sigma, sig)
            kappa = jnp.where(done | ok, kappa, kappa + p.l)
            done = done | ok
        return done, kappa, sig, it + unroll

    done, kappa, sig, _ = lax.while_loop(
        cond, body, (done0, kappa_init, sig0, jnp.int32(0))
    )
    return sig, done, kappa


def sign_mu(p: MLDSAParams, sk: jax.Array, mu: jax.Array, rnd: jax.Array):
    """Core of Algorithm 7 given mu = SHAKE256(tr||M', 64).

    sk (..., sk_len), mu (..., 64), rnd (..., 32) ->
    (sigma (..., sig_len), done (...,) bool).

    ``done`` is False for any lane whose rejection loop exhausted
    MAX_SIGN_ITERS attempts (P < 1e-12 per lane); such a lane's sigma is
    all-zero and must not be emitted — callers check host-side and raise.
    """
    sig, done, _ = sign_mu_rounds(p, sk, mu, rnd, jnp.int32(0), MAX_SIGN_ITERS)
    return sig, done


def sign_mu_pre(p: MLDSAParams, pre: dict[str, jax.Array], mu: jax.Array,
                rnd: jax.Array):
    """``sign_mu`` over a ``precompute_sk`` pytree — bit-identical output
    (the precompute is a pure hoist of the key-dependent prefix)."""
    sig, done, _ = _sign_mu_core(p, pre, mu, rnd, jnp.int32(0), MAX_SIGN_ITERS)
    return sig, done


#: compact-and-refill schedule: iterations for the first dispatches; after
#: the schedule is exhausted the surviving (small) bucket runs to
#: completion in ONE dispatch.  Three total dispatches — each round trip
#: costs real time, so the tail must not become a string of tiny rounds
#: (an earlier platform measured a 3-iter/round greedy schedule 2x SLOWER
#: than the plain loop from ~11 rounds of dispatch overhead).
COMPACT_SCHEDULE = (6, 6)


@functools.cache
def _rounds_jit(name: str, n_iters: int):
    p = PARAMS[name]
    return jax.jit(functools.partial(sign_mu_rounds, p, n_iters=n_iters))


def sign_mu_compact(name: str, sk, mu, rnd, *,
                    schedule: tuple[int, ...] = COMPACT_SCHEDULE,
                    min_bucket: int = 64):
    """Compact-and-refill signing driver (host-orchestrated, device-resident).

    The all-lanes loop in ``sign_mu`` iterates until the SLOWEST lane
    accepts — E[max of B geometrics] ≈ 30 attempts at B = 8192 where the
    mean is ~4, so ~7x the necessary work.  This driver runs ``schedule[0]``
    iterations on the full batch, gathers the unfinished lanes into the
    next power-of-two bucket ON DEVICE (the host only downloads the done
    mask and uploads a small index list — operand rows never cross the
    host link), repeats for ``schedule[1:]`` from each lane's saved kappa,
    then runs the last survivors to completion in one final dispatch.
    Results are bit-identical to ``sign_mu`` (same per-lane kappa
    sequences); attempted work drops ~3x at batch 8192.

    Returns (sigma, done) as numpy arrays.
    """
    p = PARAMS[name]
    sk_d = jnp.asarray(sk, jnp.uint8)
    mu_d = jnp.asarray(mu, jnp.uint8)
    rnd_d = jnp.asarray(rnd, jnp.uint8)
    b = mu_d.shape[0]
    sig_out = jnp.zeros((b, p.sig_len), jnp.uint8)
    done_out = np.zeros(b, dtype=bool)
    idx = np.arange(b)
    kappa_d = jnp.zeros(b, jnp.int32)
    iters_used = 0
    round_no = 0
    while idx.size and iters_used < MAX_SIGN_ITERS:
        bucket = max(min(_next_pow2_i(idx.size), b), min(min_bucket, b))
        pad_idx = np.concatenate([idx, np.full(bucket - idx.size, idx[-1])]) \
            if idx.size < bucket else idx
        idx_d = jnp.asarray(pad_idx)
        if round_no < len(schedule):
            n_it = min(schedule[round_no], MAX_SIGN_ITERS - iters_used)
        else:
            # Completion round: a CONSTANT iteration bound so every bucket
            # size shares one compiled variant regardless of the schedule
            # (the while_loop exits as soon as all lanes accept; lanes may
            # thus exceed MAX_SIGN_ITERS total by the schedule's length —
            # strictly more attempts than the plain loop, never fewer).
            n_it = MAX_SIGN_ITERS
        round_no += 1
        sig_r, done_r, kappa_r = _rounds_jit(name, n_it)(
            jnp.take(sk_d, idx_d, axis=0),
            jnp.take(mu_d, idx_d, axis=0),
            jnp.take(rnd_d, idx_d, axis=0),
            jnp.take(kappa_d, idx_d, axis=0),
        )
        iters_used += n_it
        live = idx.size
        # scatter finished rows back (device-side); dedupe pad rows first
        sig_out = sig_out.at[idx_d[:live]].set(sig_r[:live])
        kappa_d = kappa_d.at[idx_d[:live]].set(kappa_r[:live])
        done_host = np.asarray(done_r)[:live]  # tiny d2h transfer
        done_out[idx[done_host]] = True
        idx = idx[~done_host]  # qrlint: disable=flow-secret-branch — ML-DSA rejection-sampling bookkeeping: which rows finished per round is public by FIPS 204 design (iteration counts leak, coefficients don't)
    return np.asarray(sig_out), done_out





# --------------------------------------------------------------------------
# Verify (FIPS 204 Algorithm 8), batched
# --------------------------------------------------------------------------


def precompute_pk(p: MLDSAParams, pk: jax.Array) -> dict[str, jax.Array]:
    """Per-key device state the verify path reuses across dispatches:
    ExpandA(rho) and NTT(t1 << D) depend only on the public key (same
    rationale as ``precompute_sk``; consumed by the operand cache).  May be
    unbatched and broadcasts against any mu/sigma batch."""
    pk = jnp.asarray(pk, jnp.uint8)
    rho = pk[..., :32]
    t1 = simple_bit_unpack(
        pk[..., 32:].reshape(pk.shape[:-1] + (p.k, 32 * (23 - D))), 23 - D
    )
    t1_shift = (t1.astype(jnp.int32) << D) % Q
    return {"a_hat": expand_a(p, rho), "t1_hat": ntt(t1_shift)}


def verify_mu(p: MLDSAParams, pk: jax.Array, mu: jax.Array, sigma: jax.Array) -> jax.Array:
    """Core of Algorithm 8 given mu. pk (..., pk_len), mu (..., 64),
    sigma (..., sig_len) -> bool (...,)."""
    return verify_mu_pre(p, precompute_pk(p, pk), mu, sigma)


def verify_mu_pre(p: MLDSAParams, pre: dict[str, jax.Array], mu: jax.Array,
                  sigma: jax.Array) -> jax.Array:
    """``verify_mu`` over a ``precompute_pk`` pytree (pure hoist)."""
    mu = jnp.asarray(mu, jnp.uint8)
    sigma = jnp.asarray(sigma, jnp.uint8)
    batch = mu.shape[:-1]
    ctilde = sigma[..., : p.ctilde_len]
    zb = 32 * p.z_bits
    off = p.ctilde_len
    z = bit_unpack(
        sigma[..., off : off + p.l * zb].reshape(batch + (p.l, zb)), p.gamma1, p.z_bits
    )
    h, ok = hint_bit_unpack(p, sigma[..., off + p.l * zb :])
    ok &= _inf_norm(z, (-1, -2)) < p.gamma1 - p.beta
    c_hat = ntt(sample_in_ball(p, ctilde))
    az = _matvec(pre["a_hat"], ntt(z))
    ct1 = pw_mul(c_hat[..., None, :], pre["t1_hat"])
    w_approx = ntt_inv((az - ct1) % Q)
    w1 = use_hint(p, h, w_approx)
    w1_enc = simple_bit_pack(w1, p.w1_bits).reshape(batch + (-1,))
    ctilde2 = keccak.shake256(jnp.concatenate([mu, w1_enc], axis=-1), p.ctilde_len)
    ok &= jnp.all(ctilde == ctilde2, axis=-1)
    return ok


# --------------------------------------------------------------------------
# Jitted per-parameter-set entry points
# --------------------------------------------------------------------------


@functools.cache
def get(name: str):
    """Jitted (keygen, sign_mu, verify_mu) triple for a parameter-set name."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(keygen, p)),
        jax.jit(functools.partial(sign_mu, p)),
        jax.jit(functools.partial(verify_mu, p)),
    )


def sign_mu_cold(p: MLDSAParams, sk: jax.Array, mu: jax.Array, rnd: jax.Array):
    """Cache-filling sign: ONE dispatch returning the per-key device state
    (ExpandA + key NTTs) alongside the signatures, so a cache miss costs no
    extra round trip over the uncached path (see kem.mlkem.encaps_cold)."""
    pre = precompute_sk(p, sk)
    sig, done = sign_mu_pre(p, pre, mu, rnd)
    return pre, sig, done


def verify_mu_cold(p: MLDSAParams, pk: jax.Array, mu: jax.Array, sigma: jax.Array):
    """Cache-filling verify (see ``sign_mu_cold``)."""
    pre = precompute_pk(p, pk)
    return pre, verify_mu_pre(p, pre, mu, sigma)


@functools.cache
def get_pre(name: str):
    """Jitted (sign_mu_cold, sign_mu_pre, verify_mu_cold, verify_mu_pre)
    for the device operand cache (provider/opcache.py): the cold variants
    fill the cache in one dispatch; the pre variants run over a cached
    pytree, skipping the key upload and ExpandA."""
    p = PARAMS[name]
    return (
        jax.jit(functools.partial(sign_mu_cold, p)),
        jax.jit(functools.partial(sign_mu_pre, p)),
        jax.jit(functools.partial(verify_mu_cold, p)),
        jax.jit(functools.partial(verify_mu_pre, p)),
    )
