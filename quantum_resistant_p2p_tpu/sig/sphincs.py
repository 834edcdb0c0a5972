"""Batched SPHINCS+ / SLH-DSA-SHA2 (FIPS 205, 'simple') in JAX.

TPU-native design
-----------------
SPHINCS+ is hash trees all the way down — embarrassingly parallel across WOTS+
chains, tree leaves, FORS trees, and independent signatures.  This
implementation vectorises every one of those axes:

* All F / PRF calls share the constant first SHA-256 block
  ``pk_seed || zero-pad`` (FIPS 205 §11.2.1): its midstate is computed once
  per batch and every hash resumes from it (halves compression count).
  H / T_l for the 192/256-bit sets resume a SHA-512 midstate
  (``core.sha512``, 64-bit words as uint32 pairs).
* WOTS+ chains run as W-1 = 15 lock-step rounds over a ``(batch, leaves,
  wots_len, n)`` array with per-chain masks (``t < d`` when signing, ``t >= d``
  when verifying) — no data-dependent control flow.
* An XMSS tree hashes all 2^h' leaves at once, then h' halving rounds; FORS
  hashes all k * 2^a leaves at once.  Auth paths are `take_along_axis`
  gathers with traced indices.
* The hypertree's 64-bit tree index is kept as an LSB-first bit array (TPUs
  have no 64-bit lanes); per-layer leaf indices and the 8-byte big-endian
  ADRS tree field are static bit-slices of it.
* Variable-length message hashing (H_msg, PRF_msg) happens host-side in the
  provider (public data, negligible cost); the device kernels take the fixed
  m-byte digest.  Signing is fully deterministic given (sk, digest) — no
  rejection loops anywhere.

Bit-exactness oracle: ``pyref.slhdsa_ref`` (tests/test_sphincs.py).
Replaces (reference): SPHINCSSignature's per-call liboqs objects
(crypto/signatures.py:191-315, vendor/oqs.py:506-583).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ..core import sha256 as jsha256
from ..core import sha512 as jsha512
from ..pyref.slhdsa_ref import (
    FORS_PRF,
    FORS_ROOTS,
    FORS_TREE,
    PARAMS,
    SLHDSAParams,
    TREE,
    W,
    WOTS_HASH,
    WOTS_PK,
    WOTS_PRF,
)

# --------------------------------------------------------------------------
# ADRS construction (compressed 22-byte SHA2 form, FIPS 205 §11.2)
# --------------------------------------------------------------------------


def _be4(x, lead: tuple[int, ...]) -> jax.Array:
    """int or int32 array -> (..., 4) uint8 big-endian, broadcast to lead."""
    x = jnp.asarray(x, jnp.int32)
    x = jnp.broadcast_to(x, lead)
    return jnp.stack(
        [(x >> 24) & 0xFF, (x >> 16) & 0xFF, (x >> 8) & 0xFF, x & 0xFF], axis=-1
    ).astype(jnp.uint8)


def _adrs(lead: tuple[int, ...], layer, tree8, typ: int, w1, w2, w3) -> jax.Array:
    """Build (..., 22) uint8 compressed ADRS broadcast over lead dims.

    ``layer`` may be a static int OR a traced int32 scalar (the layered
    sign path compiles one XMSS-layer program and feeds the layer index
    as an operand).
    """
    lb = jnp.broadcast_to(jnp.asarray(layer, jnp.uint8), lead + (1,))
    if tree8 is None:
        tb = jnp.zeros(lead + (8,), jnp.uint8)
    else:
        tb = jnp.broadcast_to(_fit(tree8, len(lead)), lead + (8,))
    ty = jnp.broadcast_to(jnp.uint8(typ), lead + (1,))
    return jnp.concatenate([lb, tb, ty, _be4(w1, lead), _be4(w2, lead), _be4(w3, lead)], axis=-1)


def _fit(a: jax.Array, lead_ndim: int) -> jax.Array:
    """Insert singleton dims so a (B..., k) array broadcasts over lead dims."""
    extra = lead_ndim - (a.ndim - 1)
    if extra < 0:
        raise ValueError("array has more batch dims than target")
    return a.reshape(a.shape[:-1] + (1,) * extra + (a.shape[-1],)) if extra else a


# --------------------------------------------------------------------------
# Hash engines with precomputed pk_seed midstates
# --------------------------------------------------------------------------


class _Ctx:
    """Per-call context: params + pk_seed midstates (batch shape B)."""

    def __init__(self, p: SLHDSAParams, pk_seed: jax.Array):
        self.p = p
        self.batch = pk_seed.shape[:-1]
        pad256 = jnp.zeros(self.batch + (64 - p.n,), jnp.uint8)
        self.mid_f = jsha256.midstate(jnp.concatenate([pk_seed, pad256], axis=-1))
        if p.big_hash:
            pad512 = jnp.zeros(self.batch + (128 - p.n,), jnp.uint8)
            self.mid_t = jsha512.midstate(jnp.concatenate([pk_seed, pad512], axis=-1))

    def f(self, adrs: jax.Array, m: jax.Array) -> jax.Array:
        """F / PRF (always SHA-256): adrs (..., 22), m (..., n) -> (..., n)."""
        data = jnp.concatenate([adrs, m], axis=-1)
        lead = data.shape[:-1]
        mid = jnp.broadcast_to(_fit(self.mid_f, len(lead)), lead + (8,))
        return jsha256.sha256_from_midstate(mid, data, 1)[..., : self.p.n]

    def t(self, adrs: jax.Array, m: jax.Array) -> jax.Array:
        """H / T_l: SHA-256 (n=16) or SHA-512 (n=24/32)."""
        data = jnp.concatenate([adrs, m], axis=-1)
        lead = data.shape[:-1]
        if not self.p.big_hash:
            mid = jnp.broadcast_to(_fit(self.mid_f, len(lead)), lead + (8,))
            return jsha256.sha256_from_midstate(mid, data, 1)[..., : self.p.n]
        mid = (
            jnp.broadcast_to(_fit(self.mid_t[0], len(lead)), lead + (8,)),
            jnp.broadcast_to(_fit(self.mid_t[1], len(lead)), lead + (8,)),
        )
        return jsha512.sha512_from_midstate(mid, data, 1)[..., : self.p.n]


# --------------------------------------------------------------------------
# WOTS+ (FIPS 205 §5), all chains in lock-step
# --------------------------------------------------------------------------


def _wots_digits(p: SLHDSAParams, m: jax.Array) -> jax.Array:
    """(..., n) uint8 -> (..., wots_len) int32 base-16 digits + checksum."""
    m = m.astype(jnp.int32)
    nib = jnp.stack([m >> 4, m & 0xF], axis=-1).reshape(m.shape[:-1] + (p.len1,))
    csum = jnp.sum(W - 1 - nib, axis=-1) << 4
    cs = jnp.stack([(csum >> 12) & 0xF, (csum >> 8) & 0xF, (csum >> 4) & 0xF], axis=-1)
    return jnp.concatenate([nib, cs], axis=-1)


def _chain(ctx: _Ctx, x: jax.Array, d: jax.Array, from_start: bool,
           layer: int, tree8, kp) -> jax.Array:
    """Lock-step chains: x (..., wots_len, n), d (..., wots_len) digits.

    from_start=True  -> apply F at steps t < d   (sign: 0 -> d)
    from_start=False -> apply F at steps t >= d  (verify: d -> W-1)
    """
    p = ctx.p
    lead = x.shape[:-1]
    chains = jnp.arange(p.wots_len)
    for t in range(W - 1):
        adrs = _adrs(lead, layer, tree8, WOTS_HASH, kp, chains, t)
        fx = ctx.f(adrs, x)
        active = (t < d) if from_start else (t >= d)
        x = jnp.where(active[..., None], fx, x)
    return x


def _wots_sk(ctx: _Ctx, sk_seed: jax.Array, layer: int, tree8, kp, lead) -> jax.Array:
    """Secret chain heads: (..., wots_len, n)."""
    p = ctx.p
    chains = jnp.arange(p.wots_len)
    adrs = _adrs(lead, layer, tree8, WOTS_PRF, kp, chains, 0)
    seed = jnp.broadcast_to(_fit(sk_seed, len(lead)), lead + (p.n,))
    return ctx.f(adrs, seed)


def _wots_pkgen(ctx: _Ctx, sk_seed: jax.Array, layer: int, tree8, kp, lead) -> jax.Array:
    """kp (..., leaves) -> compressed WOTS pk (..., leaves, n)."""
    p = ctx.p
    chain_lead = lead + (p.wots_len,)
    sk = _wots_sk(ctx, sk_seed, layer, tree8, kp[..., None], chain_lead)
    full = jnp.full(chain_lead, W - 1, jnp.int32)
    tips = _chain(ctx, sk, full, True, layer, tree8, kp[..., None])
    tmp = tips.reshape(lead + (p.wots_len * p.n,))
    pk_adrs = _adrs(lead, layer, tree8, WOTS_PK, kp, 0, 0)
    return ctx.t(pk_adrs, tmp)


# --------------------------------------------------------------------------
# XMSS (FIPS 205 §6)
# --------------------------------------------------------------------------


def _xmss_levels(ctx: _Ctx, sk_seed: jax.Array, layer: int, tree8) -> list[jax.Array]:
    """All tree levels: levels[z] has shape (B, 2^(hp-z), n)."""
    p = ctx.p
    nl = 1 << p.hp
    lead = ctx.batch + (nl,)
    leaves = _wots_pkgen(ctx, sk_seed, layer, tree8, jnp.arange(nl), lead)
    levels = [leaves]
    node = leaves
    for z in range(1, p.hp + 1):
        pairs = node.reshape(ctx.batch + (node.shape[-2] // 2, 2 * p.n))
        idx = jnp.arange(pairs.shape[-2])
        adrs = _adrs(ctx.batch + (pairs.shape[-2],), layer, tree8, TREE, 0, z, idx)
        node = ctx.t(adrs, pairs)
        levels.append(node)
    return levels


def _xmss_sign(ctx: _Ctx, m: jax.Array, sk_seed: jax.Array, idx: jax.Array,
               layer: int, tree8) -> tuple[jax.Array, jax.Array]:
    """-> (sig_xmss (B, (wots_len+hp)*n), root (B, n)); idx (B,) int32."""
    p = ctx.p
    levels = _xmss_levels(ctx, sk_seed, layer, tree8)
    digits = _wots_digits(p, m)
    chain_lead = ctx.batch + (p.wots_len,)
    sk = _wots_sk(ctx, sk_seed, layer, tree8, idx[..., None], chain_lead)
    sig_w = _chain(ctx, sk, digits, True, layer, tree8, idx[..., None])
    auth = []
    for j in range(p.hp):
        sib = ((idx >> j) ^ 1)[..., None, None]
        auth.append(jnp.take_along_axis(levels[j], sib, axis=-2)[..., 0, :])
    sig = jnp.concatenate(
        [sig_w.reshape(ctx.batch + (p.wots_len * p.n,))] + auth, axis=-1
    )
    return sig, levels[p.hp][..., 0, :]


def _xmss_pk_from_sig(ctx: _Ctx, idx: jax.Array, sig_xmss: jax.Array, m: jax.Array,
                      layer: int, tree8) -> jax.Array:
    p = ctx.p
    wlen = p.wots_len * p.n
    sig_w = sig_xmss[..., :wlen].reshape(ctx.batch + (p.wots_len, p.n))
    digits = _wots_digits(p, m)
    tips = _chain(ctx, sig_w, digits, False, layer, tree8, idx[..., None])
    pk_adrs = _adrs(ctx.batch, layer, tree8, WOTS_PK, idx, 0, 0)
    node = ctx.t(pk_adrs, tips.reshape(ctx.batch + (wlen,)))
    for k in range(p.hp):
        sib = sig_xmss[..., wlen + k * p.n : wlen + (k + 1) * p.n]
        bit = (idx >> k) & 1
        node_idx = idx >> (k + 1)
        adrs = _adrs(ctx.batch, layer, tree8, TREE, 0, k + 1, node_idx)
        pair = jnp.where(
            bit[..., None],
            jnp.concatenate([sib, node], axis=-1),
            jnp.concatenate([node, sib], axis=-1),
        )
        node = ctx.t(adrs, pair)
    return node


# --------------------------------------------------------------------------
# Hypertree index plumbing: 64-bit tree index as an LSB-first bit array
# --------------------------------------------------------------------------


def _digest_split(p: SLHDSAParams, digest: jax.Array):
    """digest (B, m) -> (md (B, ka), tree_bits (B, h-hp) lsb-first, leaf (B,))."""
    ka = (p.k * p.a + 7) // 8
    t = (p.h - p.hp + 7) // 8
    u = (p.hp + 7) // 8
    md = digest[..., :ka]
    tb = digest[..., ka : ka + t].astype(jnp.int32)
    bits = ((tb[..., :, None] >> np.arange(7, -1, -1)) & 1).reshape(tb.shape[:-1] + (8 * t,))
    tree_bits = bits[..., ::-1][..., : p.h - p.hp]
    lb = digest[..., ka + t : ka + t + u].astype(jnp.int32)
    lbits = ((lb[..., :, None] >> np.arange(7, -1, -1)) & 1).reshape(lb.shape[:-1] + (8 * u,))
    lbits = lbits[..., ::-1][..., : p.hp]
    leaf = jnp.sum(lbits << np.arange(p.hp), axis=-1)
    return md, tree_bits, leaf


def _tree8_at(p: SLHDSAParams, tree_bits: jax.Array, j: int) -> jax.Array:
    """8-byte BE ADRS tree field for hypertree layer j (idx_tree >> j*hp)."""
    nbits = p.h - p.hp
    shift = j * p.hp
    bytes_out = []
    for bb in range(7, -1, -1):  # bb = little-endian byte index; emit MSB first
        acc = jnp.zeros(tree_bits.shape[:-1], jnp.int32)
        for t in range(8):
            e = shift + 8 * bb + t
            if e < nbits:
                acc = acc | (tree_bits[..., e] << t)
        bytes_out.append(acc)
    return jnp.stack(bytes_out, axis=-1).astype(jnp.uint8)


def _leaf_at(p: SLHDSAParams, tree_bits: jax.Array, j: int) -> jax.Array:
    """Layer-j (>=1) leaf index: bits [(j-1)*hp, j*hp) of idx_tree."""
    lo = (j - 1) * p.hp
    acc = jnp.zeros(tree_bits.shape[:-1], jnp.int32)
    for t in range(p.hp):
        if lo + t < p.h - p.hp:
            acc = acc | (tree_bits[..., lo + t] << t)
    return acc


# --------------------------------------------------------------------------
# FORS (FIPS 205 §8)
# --------------------------------------------------------------------------


def _fors_indices(p: SLHDSAParams, md: jax.Array) -> jax.Array:
    """(B, ka) -> (B, k) int32 base-2^a digits, MSB-first per digit."""
    bits = ((md[..., :, None].astype(jnp.int32) >> np.arange(7, -1, -1)) & 1).reshape(
        md.shape[:-1] + (-1,)
    )[..., : p.k * p.a]
    grp = bits.reshape(md.shape[:-1] + (p.k, p.a))
    return jnp.sum(grp << np.arange(p.a - 1, -1, -1), axis=-1)


def _fors_levels(ctx: _Ctx, sk_seed: jax.Array, tree8, idx_leaf) -> list[jax.Array]:
    """levels[z]: (B, k, 2^(a-z), n) — all k FORS trees in parallel."""
    p = ctx.p
    npos = 1 << p.a
    ti = jnp.arange(p.k)[:, None]
    pos = jnp.arange(npos)[None, :]
    gidx = (ti << p.a) + pos  # (k, 2^a) global node indices
    lead = ctx.batch + (p.k, npos)
    prf_adrs = _adrs(lead, 0, tree8, FORS_PRF, idx_leaf[..., None, None], 0, gidx)
    seed = jnp.broadcast_to(_fit(sk_seed, len(lead)), lead + (p.n,))
    sk = ctx.f(prf_adrs, seed)
    leaf_adrs = _adrs(lead, 0, tree8, FORS_TREE, idx_leaf[..., None, None], 0, gidx)
    node = ctx.f(leaf_adrs, sk)
    levels = [node]
    for z in range(1, p.a + 1):
        width = node.shape[-2] // 2
        pairs = node.reshape(ctx.batch + (p.k, width, 2 * p.n))
        g = (ti << (p.a - z)) + jnp.arange(width)[None, :]
        adrs = _adrs(ctx.batch + (p.k, width), 0, tree8, FORS_TREE,
                     idx_leaf[..., None, None], z, g)
        node = ctx.t(adrs, pairs)
        levels.append(node)
    return levels, sk


def _fors_sign(ctx: _Ctx, md: jax.Array, sk_seed: jax.Array, tree8, idx_leaf):
    """-> (sig_fors (B, k*(1+a)*n), indices (B, k))."""
    p = ctx.p
    indices = _fors_indices(p, md)
    levels, sk = _fors_levels(ctx, sk_seed, tree8, idx_leaf)
    parts = []
    sk_sel = jnp.take_along_axis(sk, indices[..., :, None, None], axis=-2)[..., 0, :]
    for i in range(p.k):
        parts.append(sk_sel[..., i, :])
        for j in range(p.a):
            sib = ((indices[..., i] >> j) ^ 1)[..., None, None]
            node = jnp.take_along_axis(levels[j][..., i, :, :], sib, axis=-2)[..., 0, :]
            parts.append(node)
    sig = jnp.concatenate(parts, axis=-1)
    return sig, indices, levels


def _fors_pk_from_sig(ctx: _Ctx, sig_fors: jax.Array, md: jax.Array, tree8, idx_leaf):
    p = ctx.p
    indices = _fors_indices(p, md)
    per = (1 + p.a) * p.n
    roots = []
    for i in range(p.k):
        chunk = sig_fors[..., i * per : (i + 1) * per]
        sk = chunk[..., : p.n]
        idx = indices[..., i]
        gidx = (i << p.a) + idx
        leaf_adrs = _adrs(ctx.batch, 0, tree8, FORS_TREE, idx_leaf, 0, gidx)
        node = ctx.f(leaf_adrs, sk)
        for j in range(p.a):
            sib = chunk[..., (1 + j) * p.n : (2 + j) * p.n]
            bit = (gidx >> j) & 1
            adrs = _adrs(ctx.batch, 0, tree8, FORS_TREE, idx_leaf, j + 1, gidx >> (j + 1))
            pair = jnp.where(
                bit[..., None],
                jnp.concatenate([sib, node], axis=-1),
                jnp.concatenate([node, sib], axis=-1),
            )
            node = ctx.t(adrs, pair)
        roots.append(node)
    pk_adrs = _adrs(ctx.batch, 0, tree8, FORS_ROOTS, idx_leaf, 0, 0)
    return ctx.t(pk_adrs, jnp.concatenate(roots, axis=-1))


# --------------------------------------------------------------------------
# SLH-DSA top level (device cores take the fixed-size H_msg digest)
# --------------------------------------------------------------------------


def keygen(p: SLHDSAParams, sk_seed: jax.Array, sk_prf: jax.Array, pk_seed: jax.Array):
    """Three (..., n) seeds -> (pk (..., 2n), sk (..., 4n))."""
    sk_seed = jnp.asarray(sk_seed, jnp.uint8)
    sk_prf = jnp.asarray(sk_prf, jnp.uint8)
    pk_seed = jnp.asarray(pk_seed, jnp.uint8)
    ctx = _Ctx(p, pk_seed)
    tree8 = jnp.zeros(ctx.batch + (8,), jnp.uint8)
    levels = _xmss_levels(ctx, sk_seed, p.d - 1, tree8)
    pk_root = levels[p.hp][..., 0, :]
    pk = jnp.concatenate([pk_seed, pk_root], axis=-1)
    return pk, jnp.concatenate([sk_seed, sk_prf, pk], axis=-1)


def sign_digest(p: SLHDSAParams, sk: jax.Array, r: jax.Array, digest: jax.Array):
    """sk (B, 4n), r (B, n) randomizer, digest (B, m) = H_msg -> sig (B, sig_len)."""
    sk = jnp.asarray(sk, jnp.uint8)
    r = jnp.asarray(r, jnp.uint8)
    digest = jnp.asarray(digest, jnp.uint8)
    sk_seed, pk_seed = sk[..., : p.n], sk[..., 2 * p.n : 3 * p.n]
    ctx = _Ctx(p, pk_seed)
    md, tree_bits, idx_leaf = _digest_split(p, digest)
    tree8 = _tree8_at(p, tree_bits, 0)
    sig_fors, _, _ = _fors_sign(ctx, md, sk_seed, tree8, idx_leaf)
    pk_fors = _fors_pk_from_sig(ctx, sig_fors, md, tree8, idx_leaf)
    parts = [r, sig_fors]
    msg = pk_fors
    leaf = idx_leaf
    for j in range(p.d):
        t8 = _tree8_at(p, tree_bits, j)
        sig_x, root = _xmss_sign(ctx, msg, sk_seed, leaf, j, t8)
        parts.append(sig_x)
        msg = root
        if j + 1 < p.d:
            leaf = _leaf_at(p, tree_bits, j + 1)
    return jnp.concatenate(parts, axis=-1)


@functools.cache
def _layered_fns(p: SLHDSAParams):
    """Jitted (fors_part, xmss_layer) pair for the layered sign path."""

    @jax.jit
    def fors_part(sk_seed, pk_seed, digest):
        ctx = _Ctx(p, pk_seed)
        md, tree_bits, idx_leaf = _digest_split(p, digest)
        t8_0 = _tree8_at(p, tree_bits, 0)
        sig_fors, _, _ = _fors_sign(ctx, md, sk_seed, t8_0, idx_leaf)
        pk_fors = _fors_pk_from_sig(ctx, sig_fors, md, t8_0, idx_leaf)
        t8s = jnp.stack([t8_0] + [_tree8_at(p, tree_bits, j) for j in range(1, p.d)])
        leaves = jnp.stack(
            [idx_leaf] + [_leaf_at(p, tree_bits, j) for j in range(1, p.d)]
        )
        return sig_fors, pk_fors, t8s, leaves

    @jax.jit
    def xmss_layer(sk_seed, pk_seed, msg, leaf, layer, t8):
        ctx = _Ctx(p, pk_seed)
        return _xmss_sign(ctx, msg, sk_seed, leaf, layer, t8)

    return fors_part, xmss_layer


def sign_digest_layered(p: SLHDSAParams, sk: jax.Array, r: jax.Array,
                        digest: jax.Array):
    """``sign_digest`` as 1 FORS dispatch + d per-layer XMSS dispatches.

    Bit-identical output.  The XMSS-layer program takes the hypertree layer
    index, ADRS tree field, and leaf index as traced operands, so it is
    traced and compiled ONCE and reused for all d layers — the XLA graph is
    ~d× smaller than the monolithic sign.  On an earlier platform 256s
    sign, whose monolithic graph never compiled at any batch, ran at batch
    32, and 128s compiled at 512 vs the monolithic 128; not measured on
    this chip.
    """
    sk = jnp.asarray(sk, jnp.uint8)
    r = jnp.asarray(r, jnp.uint8)
    digest = jnp.asarray(digest, jnp.uint8)
    fors_part, xmss_layer = _layered_fns(p)
    sk_seed, pk_seed = sk[..., : p.n], sk[..., 2 * p.n : 3 * p.n]
    sig_fors, msg, t8s, leaves = fors_part(sk_seed, pk_seed, digest)
    parts = [r, sig_fors]
    for j in range(p.d):
        sig_x, msg = xmss_layer(sk_seed, pk_seed, msg, leaves[j],
                                jnp.int32(j), t8s[j])
        parts.append(sig_x)
    return jnp.concatenate(parts, axis=-1)


def verify_digest(p: SLHDSAParams, pk: jax.Array, digest: jax.Array, sig: jax.Array):
    """pk (B, 2n), digest (B, m), sig (B, sig_len) -> bool (B,)."""
    pk = jnp.asarray(pk, jnp.uint8)
    digest = jnp.asarray(digest, jnp.uint8)
    sig = jnp.asarray(sig, jnp.uint8)
    pk_seed, pk_root = pk[..., : p.n], pk[..., p.n :]
    ctx = _Ctx(p, pk_seed)
    md, tree_bits, idx_leaf = _digest_split(p, digest)
    fors_len = p.k * (1 + p.a) * p.n
    sig_fors = sig[..., p.n : p.n + fors_len]
    sig_ht = sig[..., p.n + fors_len :]
    tree8 = _tree8_at(p, tree_bits, 0)
    node = _fors_pk_from_sig(ctx, sig_fors, md, tree8, idx_leaf)
    per = (p.wots_len + p.hp) * p.n
    leaf = idx_leaf
    for j in range(p.d):
        t8 = _tree8_at(p, tree_bits, j)
        chunk = sig_ht[..., j * per : (j + 1) * per]
        node = _xmss_pk_from_sig(ctx, leaf, chunk, node, j, t8)
        if j + 1 < p.d:
            leaf = _leaf_at(p, tree_bits, j + 1)
    return jnp.all(node == pk_root, axis=-1)


def _use_layered_sign(p: SLHDSAParams) -> bool:
    """Layered sign for the s-sets by default (256s's monolithic graph never
    compiled at any batch in this environment; 128s capped at 128);
    QRP2P_SPHINCS_LAYERED=1/0 forces either path (trace-time flag: fresh
    process per setting, same caveat as QRP2P_PALLAS)."""
    flag = os.environ.get("QRP2P_SPHINCS_LAYERED", "auto")
    if flag in ("0", "1"):
        return flag == "1"
    return p.hp >= 8


@functools.cache
def get(name: str):
    """(keygen, sign_digest, verify_digest) callables for a parameter set.

    keygen/verify are jitted; sign is jitted for the f-sets but is the
    layered multi-dispatch driver (``sign_digest_layered``, not a jit
    object) for the s-sets — see ``_use_layered_sign``.
    """
    p = PARAMS[name]
    sign = (
        functools.partial(sign_digest_layered, p)
        if _use_layered_sign(p)
        else jax.jit(functools.partial(sign_digest, p))
    )
    return (
        jax.jit(functools.partial(keygen, p)),
        sign,
        jax.jit(functools.partial(verify_digest, p)),
    )
