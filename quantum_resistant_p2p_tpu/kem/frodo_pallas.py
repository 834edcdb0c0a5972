"""Fused Pallas kernels for FrodoKEM's A-matrix products — tiled LWE matmul.

Why a kernel at all: FrodoKEM's cost is the two big products against the
pseudorandom n x n matrix A (A.S in keygen, S'.A in encaps/decaps).  The
chunked jnp path (kem/frodo.py) generates A in 16 row blocks with
``keccak.shake128`` and contracts each against S — so every generated row
round-trips HBM twice (sponge squeeze out, matmul operand in): ~3.4 MB of
A traffic per 640-row key, ~430 GB per 512-batch encaps dispatch, wholly
memory-bound (the same lesson as the FrodoKEM crypto-processor and OpenACC
LWE-KEM papers: tile the matrix product and keep sampling on device).

This kernel fuses the SHAKE-128 row sponge INTO the matmul consumer: each
grid step absorbs the per-row seed block, squeezes a full 2n-byte A row,
and multiply-accumulates it against the resident S tile — A never exists
in HBM at all.  HBM traffic drops to the seed words in and the (nbar x n)
product out.

Layout: the 8 sublanes of every (8, 128) uint32 state-word tile hold 8
CONSECUTIVE A-ROWS of the same sponge seed family; the 128 lanes hold
batch elements — 1024 row-sponges per grid step, the exact
``core/keccak_pallas.py`` register discipline (one vreg per state word).
The per-row 2-byte LE row index lives in the low half of lane word 0, so
one ``broadcasted_iota`` OR per grid step derives all 8 row headers from a
single host-prepared seed block.

All arithmetic is int32: products and accumulations wrap mod 2^32, which
is EXACT mod q because q = 2^15 or 2^16 divides 2^32 — the final ``& (q-1)``
recovers the spec value (the qrkernel wrap-by-design contract, annotated at
each site).

CPU twin: ``a_times_s_jnp`` / ``s_times_a_jnp`` are bit-identical
``lax.scan`` twins over the same 16 row chunks (the ``chacha_pallas``
pattern) — XLA:CPU compiles the 16-step scan well where the fully-unrolled
kernel body chokes LLVM.  Oracle: ``pyref.frodo_ref`` via tests/test_frodo*.

Replaces (hot path): the unrolled ``_gen_a_chunk`` + einsum loops in
kem/frodo.py for the SHAKE parameter sets (the AES sets keep the
bitsliced-AES chunk path — their matrix stream is not a sponge).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import keccak
from ..core.keccak_pallas import _TL, _TS, BT, _f1600, absorb_block
from ..pyref.frodo_ref import NBAR, FrodoParams

RATE_WORDS = 21  # SHAKE-128 rate: 168 bytes = 21 lanes (Gen for every set)

_N_CHUNKS = 16  # twin row chunks (matches kem/frodo.py N_CHUNKS)


def row_blocks(p: FrodoParams) -> int:
    """Squeeze blocks per A row: ceil(2n / 168) — 8 / 12 / 16."""
    return -(-2 * p.n // 168)


def seed_words(p: FrodoParams, seed_a: jax.Array):
    """seed_a (..., 16) uint8 -> ((21, B), (21, B)) uint32 hi/lo lane words
    of the padded SHAKE-128 row-seed block, row header left ZERO.

    The spec's row message is ``le16(row) || seed_a`` (18 bytes); the two
    row bytes land in the low half of lane word 0, so the kernel derives
    every row's block from this one by OR-ing the row index in.
    """
    zero_row = jnp.zeros(seed_a.shape[:-1] + (2,), jnp.uint8)
    seeds = jnp.concatenate([zero_row, jnp.asarray(seed_a, jnp.uint8)], axis=-1)
    ph, plo, batch = keccak.seed_block_words(seeds, 168, 0x1F)
    return ph, plo, batch


def _le16(b: jax.Array) -> jax.Array:
    """(..., 2k) uint8 -> (..., k) int32 little-endian 16-bit (twin helper)."""
    x = b.astype(jnp.int32).reshape(b.shape[:-1] + (-1, 2))
    return x[..., 0] | (x[..., 1] << 8)


def _squeeze_le16(sh: list, sl: list, ncol: int, q_mask: int) -> list:
    """The 84 LE-16 values of one squeezed rate block, first ``ncol`` only.

    Byte order within a 64-bit lane is little-endian with the low word
    first (core.keccak._words_to_bytes), so the four 16-bit values of lane
    word w are lo&0xFFFF, lo>>16, hi&0xFFFF, hi>>16 in stream order.
    """
    vals = []
    for w in range(RATE_WORDS):
        if len(vals) >= ncol:
            break
        lo, hi = sl[w], sh[w]
        vals += [lo & 0xFFFF, lo >> 16, hi & 0xFFFF, hi >> 16]
    # qrkernel: assume q_mask in [0, 65536) — q = 2^15 or 2^16 for every FrodoKEM set, so masked values fit int32 exactly
    return [(v & q_mask).astype(jnp.int32) for v in vals[:ncol]]


# --------------------------------------------------------------------------
# Kernel bodies — pure tile functions (eagerly testable on CPU arrays)
# --------------------------------------------------------------------------


def _absorb_row_seeds(in_hi: list, in_lo: list, row: jax.Array):
    """Absorb the row-seed block for a tile of absolute row indices.

    in_hi/in_lo: 21 uint32 word tiles broadcastable against ``row`` (the
    host-prepared block with a zero row header); row: uint32 tile of A-row
    indices (< n <= 1344 < 2^16, so the two LE header bytes are exactly
    the low half-word of lane 0).
    """
    ih = [jnp.broadcast_to(h, row.shape) for h in in_hi]
    il = [jnp.broadcast_to(lo, row.shape) for lo in in_lo]
    il[0] = il[0] | row
    return absorb_block(ih, il, RATE_WORDS)


def _s_times_a_tiles(in_hi: list, in_lo: list, sp: jax.Array, row: jax.Array,
                     *, n: int, q_mask: int, n_sq: int) -> jax.Array:
    """Partial S'.A for one 8-row tile of A: returns the (NBAR, n, lanes)
    int32 contribution of rows ``row`` (summed over the 8 sublane rows).

    sp: (NBAR,) + row.shape int32 — S' columns for these 8 A rows.
    Output wraps mod 2^32 (exact mod q); callers mask after the full sum.
    """
    sh, sl = _absorb_row_seeds(in_hi, in_lo, row)
    outs = []
    for sb in range(n_sq):
        if sb:
            sh, sl = _f1600(sh, sl)
        ncol = min(84, n - sb * 84)
        a = jnp.stack(_squeeze_le16(sh, sl, ncol, q_mask))  # (ncol, 8, lanes)
        outs.append(jnp.stack([
            jnp.sum(sp[j][None] * a, axis=1)  # qrkernel: wrapping — int32 LWE product/accumulate wraps mod 2^32; q | 2^32 so the masked result is the exact spec value
            for j in range(NBAR)
        ]))
    return jnp.concatenate(outs, axis=1)


def _a_times_s_tiles(in_hi: list, in_lo: list, s_cols: jax.Array,
                     row: jax.Array, *, n: int, q_mask: int,
                     n_sq: int) -> jax.Array:
    """A.S for one 8-row tile of A: returns (8, NBAR, lanes) int32 rows.

    s_cols: (n, NBAR) + lane shape int32 — the full S matrix (resident).
    Each generated A row contracts against all n S rows in-register; the
    output rows are complete (no cross-step accumulation needed).
    """
    sh, sl = _absorb_row_seeds(in_hi, in_lo, row)
    acc = jnp.zeros(row.shape[:1] + (NBAR,) + row.shape[1:], jnp.int32)
    for sb in range(n_sq):
        if sb:
            sh, sl = _f1600(sh, sl)
        ncol = min(84, n - sb * 84)
        for k, a_c in enumerate(_squeeze_le16(sh, sl, ncol, q_mask)):
            acc = acc + a_c[:, None] * s_cols[sb * 84 + k][None]  # qrkernel: wrapping — int32 LWE product/accumulate wraps mod 2^32; q | 2^32 so the masked result is the exact spec value
    return acc


def _cdf_tiles(r: jax.Array, cdf: tuple[int, ...], q_mask: int) -> jax.Array:
    """Inversion sampling on the CDF: (...,) int32 16-bit randoms -> samples
    in [0, q).  Bit-identical to kem/frodo._sample (the jnp twin)."""
    t = r >> 1
    e = jnp.zeros_like(r)
    for c in cdf[:-1]:
        e = e + (t > c).astype(jnp.int32)
    return jnp.where((r & 1) == 1, -e, e) & q_mask


# --------------------------------------------------------------------------
# Pallas launchers
# --------------------------------------------------------------------------


def _s_times_a_kernel(in_hi_ref, in_lo_ref, sp_ref, out_ref, *, n: int,
                      q_mask: int, n_sq: int):
    rc = pl.program_id(1)

    @pl.when(rc == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    row = (lax.broadcasted_iota(jnp.int32, (_TS, _TL), 0)
           + rc * _TS).astype(jnp.uint32)
    contrib = _s_times_a_tiles(
        [in_hi_ref[w] for w in range(RATE_WORDS)],
        [in_lo_ref[w] for w in range(RATE_WORDS)],
        sp_ref[...], row, n=n, q_mask=q_mask, n_sq=n_sq,
    )
    out_ref[...] += contrib  # qrkernel: wrapping — int32 LWE product/accumulate wraps mod 2^32; q | 2^32 so the masked result is the exact spec value


def _a_times_s_kernel(in_hi_ref, in_lo_ref, s_ref, out_ref, *, n: int,
                      q_mask: int, n_sq: int):
    rc = pl.program_id(1)
    row = (lax.broadcasted_iota(jnp.int32, (_TS, _TL), 0)
           + rc * _TS).astype(jnp.uint32)
    out_ref[...] = _a_times_s_tiles(
        [in_hi_ref[w] for w in range(RATE_WORDS)],
        [in_lo_ref[w] for w in range(RATE_WORDS)],
        s_ref[...], row, n=n, q_mask=q_mask, n_sq=n_sq,
    )


def _pad_lanes(x: jax.Array, b: int, bp: int) -> jax.Array:
    if bp == b:
        return x
    pad = [(0, 0)] * (x.ndim - 1) + [(0, bp - b)]
    return jnp.pad(x, pad)


def _seed_tiles(w: jax.Array, b: int, bp: int) -> jax.Array:
    """(21, B) seed words -> (21, B/128, 1, 128): one (1, 128) row per lane
    tile.  Mosaic wants a block's last two dims divisible by (8, 128) or
    equal to the array's; a (21, 1, 128) block of a (21, B/128, 128) array
    is neither, and the v5e compiler refuses it."""
    return _pad_lanes(w, b, bp).reshape(RATE_WORDS, bp // _TL, 1, _TL)


#: S'.A keeps an (NBAR, n, 128) accumulator and same-sized per-tile
#: intermediates in VMEM: 25 MB at n = 640 against the 16 MB default scoped
#: limit, which the v5e compiler refuses.  v5e has 128 MiB of VMEM.
_VMEM_LIMIT = pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20)

#: the seed words' block: the lane tile's (1, 128) row, tile axis squeezed
_SEED_SPEC = pl.BlockSpec((RATE_WORDS, pl.squeezed, 1, _TL),
                          lambda bt, rc: (0, bt, 0, 0))


@functools.partial(jax.jit,
                   static_argnames=("n", "q_mask", "n_sq", "interpret"))
def s_times_a_words(in_hi: jax.Array, in_lo: jax.Array, sp: jax.Array, *,
                    n: int, q_mask: int, n_sq: int,
                    interpret: bool = False) -> jax.Array:
    """S'.A with fused row generation: seed words (21, B), sp (NBAR, n, B)
    int32 -> (NBAR, n, B) int32 (wrapped; callers mask).

    Grid: (B/128 lane tiles) x (n/8 row chunks); the output block stays
    VMEM-resident across the whole row-chunk axis (revisited accumulation,
    init on the first chunk).
    """
    b = in_hi.shape[1]
    bp = -(-b // _TL) * _TL
    in_hi = _seed_tiles(in_hi, b, bp)
    in_lo = _seed_tiles(in_lo, b, bp)
    sp = _pad_lanes(sp, b, bp)
    kern = functools.partial(_s_times_a_kernel, n=n, q_mask=q_mask, n_sq=n_sq)
    out = pl.pallas_call(
        kern,
        grid=(bp // _TL, n // _TS),
        in_specs=[
            _SEED_SPEC,
            _SEED_SPEC,
            pl.BlockSpec((NBAR, _TS, _TL), lambda bt, rc: (0, rc, bt)),
        ],
        out_specs=pl.BlockSpec((NBAR, n, _TL), lambda bt, rc: (0, 0, bt)),
        out_shape=jax.ShapeDtypeStruct((NBAR, n, bp), jnp.int32),
        compiler_params=_VMEM_LIMIT,
        interpret=interpret,
    )(in_hi, in_lo, sp)
    return out[..., :b]


@functools.partial(jax.jit,
                   static_argnames=("n", "q_mask", "n_sq", "interpret"))
def a_times_s_words(in_hi: jax.Array, in_lo: jax.Array, s: jax.Array, *,
                    n: int, q_mask: int, n_sq: int,
                    interpret: bool = False) -> jax.Array:
    """A.S with fused row generation: seed words (21, B), s (n, NBAR, B)
    int32 -> (n, NBAR, B) int32 (wrapped; callers mask).

    The full S block is VMEM-resident per lane tile; each grid step emits
    8 finished output rows (no revisiting).
    """
    b = in_hi.shape[1]
    bp = -(-b // _TL) * _TL
    in_hi = _seed_tiles(in_hi, b, bp)
    in_lo = _seed_tiles(in_lo, b, bp)
    s = _pad_lanes(s, b, bp)
    kern = functools.partial(_a_times_s_kernel, n=n, q_mask=q_mask, n_sq=n_sq)
    out = pl.pallas_call(
        kern,
        grid=(bp // _TL, n // _TS),
        in_specs=[
            _SEED_SPEC,
            _SEED_SPEC,
            pl.BlockSpec((n, NBAR, _TL), lambda bt, rc: (0, 0, bt)),
        ],
        out_specs=pl.BlockSpec((_TS, NBAR, _TL), lambda bt, rc: (rc, 0, bt)),
        out_shape=jax.ShapeDtypeStruct((n, NBAR, bp), jnp.int32),
        interpret=interpret,
    )(in_hi, in_lo, s)
    return out[..., :b]


def _cdf_kernel(r_ref, out_ref, *, cdf: tuple[int, ...], q_mask: int):
    out_ref[...] = _cdf_tiles(r_ref[...], cdf, q_mask)


@functools.partial(jax.jit, static_argnames=("cdf", "q_mask", "interpret"))
def cdf_sample_words(r: jax.Array, *, cdf: tuple[int, ...], q_mask: int,
                     interpret: bool = False) -> jax.Array:
    """Batched CDF inversion on device: (M,) int32 randoms -> samples.

    One flat pass; the compare-sum never materialises the (M, |cdf|)
    comparison tensor in HBM (the jnp path's main traffic)."""
    m = r.shape[0]
    mp = -(-m // BT) * BT
    r = jnp.pad(r, (0, mp - m)).reshape(mp // _TL, _TL)
    out = pl.pallas_call(
        functools.partial(_cdf_kernel, cdf=cdf, q_mask=q_mask),
        grid=(mp // BT,),
        in_specs=[pl.BlockSpec((_TS, _TL), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((_TS, _TL), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp // _TL, _TL), jnp.int32),
        interpret=interpret,
    )(r)
    return out.reshape(mp)[:m]


# --------------------------------------------------------------------------
# Shape-marshalling wrappers (the kem/frodo.py routing surface)
# --------------------------------------------------------------------------


def s_times_a(p: FrodoParams, sp: jax.Array, seed_a: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """S'.A: sp (..., NBAR, n), seed_a (..., 16) -> (..., NBAR, n) in [0, q)."""
    batch = sp.shape[:-2]
    b = int(np.prod(batch)) if batch else 1
    in_hi, in_lo, _ = seed_words(p, seed_a)
    spw = jnp.moveaxis(sp.reshape((b, NBAR, p.n)), 0, -1).astype(jnp.int32)
    out = s_times_a_words(in_hi, in_lo, spw, n=p.n, q_mask=p.q - 1,
                          n_sq=row_blocks(p), interpret=interpret)
    return jnp.moveaxis(out, -1, 0).reshape(batch + (NBAR, p.n)) & (p.q - 1)


def a_times_s(p: FrodoParams, s: jax.Array, seed_a: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """A.S: s (..., n, NBAR), seed_a (..., 16) -> (..., n, NBAR) in [0, q)."""
    batch = s.shape[:-2]
    b = int(np.prod(batch)) if batch else 1
    in_hi, in_lo, _ = seed_words(p, seed_a)
    sw = jnp.moveaxis(s.reshape((b, p.n, NBAR)), 0, -1).astype(jnp.int32)
    out = a_times_s_words(in_hi, in_lo, sw, n=p.n, q_mask=p.q - 1,
                          n_sq=row_blocks(p), interpret=interpret)
    return jnp.moveaxis(out, -1, 0).reshape(batch + (p.n, NBAR)) & (p.q - 1)


def cdf_sample(p: FrodoParams, r16: jax.Array, *,
               interpret: bool = False) -> jax.Array:
    """CDF samples mod q for (...,) int32 16-bit randoms (kernel path)."""
    shape = r16.shape
    out = cdf_sample_words(r16.reshape(-1), cdf=tuple(p.cdf), q_mask=p.q - 1,
                           interpret=interpret)
    return out.reshape(shape)


# --------------------------------------------------------------------------
# Scanned-jnp CPU twins (bit-identical; the chacha_pallas pattern)
# --------------------------------------------------------------------------


def _gen_rows_jnp(p: FrodoParams, seed_a: jax.Array, row0: jax.Array,
                  nrows: int) -> jax.Array:
    """One chunk of A rows via the sponge scan path: -> (..., nrows, n)."""
    rows = row0 + jnp.arange(nrows)
    idx = jnp.stack([rows & 0xFF, rows >> 8], axis=-1).astype(jnp.uint8)
    lead = seed_a.shape[:-1] + (nrows,)
    seeds = jnp.concatenate(
        [
            jnp.broadcast_to(idx, lead + (2,)),
            jnp.broadcast_to(seed_a[..., None, :], lead + (16,)),
        ],
        axis=-1,
    )
    return _le16(keccak.shake128(seeds, 2 * p.n)) & (p.q - 1)


def s_times_a_jnp(p: FrodoParams, sp: jax.Array, seed_a: jax.Array) -> jax.Array:
    """Scanned twin of :func:`s_times_a` — a 16-step ``lax.scan`` over row
    chunks (XLA:CPU compiles the scan well; the unrolled chunk loop traced
    16x the ops).  Bit-identical: all-integer math, masked mod a power of
    two, so chunk order and masking granularity cannot change the result."""
    rows = p.n // _N_CHUNKS

    def step(acc, c):
        a_chunk = _gen_rows_jnp(p, seed_a, c * rows, rows)
        sp_chunk = lax.dynamic_slice_in_dim(sp, c * rows, rows, axis=-1)
        return (acc + jnp.einsum("...ir,...rn->...in", sp_chunk, a_chunk)) & (p.q - 1), None

    acc0 = jnp.zeros(sp.shape[:-1] + (p.n,), jnp.int32)
    acc, _ = lax.scan(step, acc0, jnp.arange(_N_CHUNKS))
    return acc


def a_times_s_jnp(p: FrodoParams, s: jax.Array, seed_a: jax.Array) -> jax.Array:
    """Scanned twin of :func:`a_times_s` (see :func:`s_times_a_jnp`)."""
    rows = p.n // _N_CHUNKS

    def step(carry, c):
        a_chunk = _gen_rows_jnp(p, seed_a, c * rows, rows)
        return carry, jnp.einsum("...rn,...nj->...rj", a_chunk, s) & (p.q - 1)

    _, ys = lax.scan(step, None, jnp.arange(_N_CHUNKS))
    # ys: (chunks, ..., rows, NBAR) -> (..., chunks * rows, NBAR)
    ys = jnp.moveaxis(ys, 0, -3)
    return ys.reshape(s.shape[:-2] + (p.n, s.shape[-1]))
