"""Batched HQC in JAX — quasi-cyclic GF(2) codes on the VPU.

TPU-native design
-----------------
HQC is the least matmul-shaped algorithm in the suite (SURVEY.md §7.4 ranks
it hardest to map); the decomposition here:

* Code vectors live as dense (batch, n) uint8 bit arrays — no 64-bit packing
  (TPUs have no 64-bit lanes and XLA vectorises byte lanes fine).  The
  sparse-by-dense cyclic product x^p * a mod (x^n - 1) is an exact-f32
  FFT convolution by default (``_cyclic_mul_fft`` — conv values <= w <= 149
  sit far inside float32's exact integer range); the blocked-Toeplitz MXU
  contraction (``QRP2P_HQC_FFT=0``) and the rotated-gather loop
  (``QRP2P_HQC_GATHER=1``) remain for A/B.
* The inner RM(1,7) decoder is a batched fast Hadamard transform (7 static
  butterfly stages) over soft-combined duplicates — exactly the
  structure TPUs like.
* The outer Reed-Solomon decoder runs entirely in-graph and GATHER-FREE:
  GF(256) products against static constants (syndrome grids, Chien/Forney
  evaluation points, generator polynomials) are 8 masked XORs against
  precomputed ``x^k * c`` tables; variable-by-variable products
  (Berlekamp-Massey) are branch-free carry-less-multiply + polynomial
  reduction circuits; inversion is the ``b^254`` addition chain.  BM's
  ``x^m * B(x)`` term — a per-lane dynamic shift in the textbook
  formulation — is maintained incrementally as a shift-by-one of a
  select, so no per-lane indices exist anywhere in the decode path.
  (Round 3 first measurement had log/exp-gather GF ops; this rewrite
  removed the family's last table gathers.)
* Fisher-Yates fixed-weight sampling follows the same downward-scan dedup as
  the oracle (sequential fori_loop over w slots, vectorised compares).

Bit-exactness oracle: ``pyref.hqc_ref`` — see that module's compatibility
note: with liboqs stripped from the reference checkout, the PRNG seam is this
framework's own; cpu and tpu backends are bit-exact against each other.
Replaces (reference): HQCKeyExchange (crypto/key_exchange.py:189-309).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import keccak
from ..pyref.hqc_ref import (
    _GF_EXP,
    _RM_ENC_TABLE,
    RM_N,
    HQCParams,
    PARAMS,
    _rs_gen_poly,
)

#: Single-dispatch batch cap (provider/base.py sliced_dispatch).  The FFT
#: cyclic product keeps HQC's working set small (33 MB spectra instead of
#: the Toeplitz chunk expansion); the cap dates from an earlier platform
#: and awaits a sweep on the chip (ROADMAP queue 1 item 6).
MAX_DEVICE_BATCH = 512

_EXP = np.asarray(_GF_EXP, dtype=np.int32)  # length 512 (host-side table builds)

# RM(1,7) encode table as a (256, 128) bit matrix
_RM_BITS = np.array(
    [[(cw >> j) & 1 for j in range(RM_N)] for cw in _RM_ENC_TABLE], dtype=np.int32
)


# field modulus recovered from the pyref tables: x^8 ≡ exp[8] (mod poly)
# for a degree-8 monic modulus means poly = 0x100 | exp[8]  (= 0x11D for HQC)
_GF_POLY = int(_GF_EXP[8] | 0x100)


def _gf_mul(a: jax.Array, b: jax.Array) -> jax.Array:
    """GF(256) product, gather-free: 8-step carry-less multiply + 7-step
    polynomial reduction, pure AND/XOR/shift on int32 lanes.  Replaces the
    log/exp table lookups (3 per-lane gathers per product — the TPU
    anti-pattern this module eliminated everywhere else)."""
    a = a.astype(jnp.int32) if isinstance(a, jax.Array) else jnp.asarray(a, jnp.int32)
    b = b.astype(jnp.int32) if isinstance(b, jax.Array) else jnp.asarray(b, jnp.int32)
    p = jnp.zeros(jnp.broadcast_shapes(a.shape, b.shape), jnp.int32)
    for k in range(8):
        p = p ^ ((-((b >> k) & 1)) & (a << k))
    for k in range(14, 7, -1):
        p = p ^ ((-((p >> k) & 1)) & (_GF_POLY << (k - 8)))
    return p


def _gf_inv(x: jax.Array) -> jax.Array:
    """x^254 = x^-1 in GF(256) (0 -> 0), 4-multiply/7-square chain."""
    x2 = _gf_mul(x, x)
    x3 = _gf_mul(x2, x)
    x12 = _gf_mul(_gf_mul(x3, x3), _gf_mul(x3, x3))
    x15 = _gf_mul(x12, x3)
    x240 = x15
    for _ in range(4):
        x240 = _gf_mul(x240, x240)
    return _gf_mul(_gf_mul(x240, x12), x2)


def _gf_const_tables(c: np.ndarray) -> np.ndarray:
    """(8,) + c.shape int32 tables t[k] = x^k * c, for masked-XOR products."""
    c = np.asarray(c, np.int64)
    out = np.zeros((8,) + c.shape, np.int32)
    for k in range(8):
        v = c << k
        for j in range(14, 7, -1):
            v = np.where((v >> j) & 1, v ^ (_GF_POLY << (j - 8)), v)
        out[k] = v
    return out


def _gf_mul_const(x: jax.Array, tables: jax.Array) -> jax.Array:
    """GF(256) product of variable x against precomputed constant tables
    (from :func:`_gf_const_tables`): 8 masked XORs, no reduction step."""
    x = x.astype(jnp.int32)
    acc = jnp.zeros(jnp.broadcast_shapes(x.shape, tables.shape[1:]), jnp.int32)
    for k in range(8):
        acc = acc ^ ((-((x >> k) & 1)) & tables[k])
    return acc


def _xor_reduce(x: jax.Array, axis: int) -> jax.Array:
    return lax.reduce(x, np.int32(0), lax.bitwise_xor, (axis % x.ndim,))


# -- bit/byte helpers ---------------------------------------------------------


def _bytes_to_bits(b: jax.Array, nbits: int) -> jax.Array:
    bits = (b[..., :, None].astype(jnp.int32) >> np.arange(8)) & 1
    return bits.reshape(b.shape[:-1] + (-1,))[..., :nbits].astype(jnp.uint8)


def _bits_to_bytes(bits: jax.Array) -> jax.Array:
    nbits = bits.shape[-1]
    pad = (-nbits) % 8
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1
        )
    grp = bits.reshape(bits.shape[:-1] + (-1, 8)).astype(jnp.int32)
    return jnp.sum(grp << np.arange(8), axis=-1).astype(jnp.uint8)


# -- sampling -----------------------------------------------------------------


def _seedexpand(seed: jax.Array, out_len: int) -> jax.Array:
    """HQC seedexpander stream: SHAKE256(seed || 0x02) squeezed to out_len.
    Callers slice consecutive reads off one stream (pyref SeedExpander)."""
    dom = jnp.broadcast_to(jnp.uint8(2), seed.shape[:-1] + (1,))
    return keccak.shake256(jnp.concatenate([seed, dom], axis=-1), out_len)


def _u32s(buf: jax.Array) -> jax.Array:
    """(..., 4k) uint8 -> (..., k) uint32 little-endian."""
    b = buf.astype(jnp.uint32).reshape(buf.shape[:-1] + (-1, 4))
    return b[..., 0] | (b[..., 1] << 8) | (b[..., 2] << 16) | (b[..., 3] << 24)


def _mulhi32(a: jax.Array, m: int) -> jax.Array:
    """floor(a * m / 2**32) for uint32 a and python int m < 2**16, exactly,
    without 64-bit lanes: split a into 16-bit halves."""
    assert 0 < m < (1 << 16), f"16-bit split requires m < 2^16, got {m}"
    a1 = a >> 16
    a0 = a & jnp.uint32(0xFFFF)
    # a*m = a1*m*2^16 + a0*m ; both partial products fit uint32 (m < 2^16)
    return (a1 * jnp.uint32(m) + ((a0 * jnp.uint32(m)) >> 16)) >> 16


def _fixed_weight_support(p: HQCParams, rand: jax.Array, weight: int) -> jax.Array:
    """(batch, weight) uint32 randoms -> (batch, weight) int32 positions.

    HQC vect_set_random_fixed_weight: i + (rand32 * (n-i)) >> 32, duplicates
    replaced by their index in a reverse scan (oracle-identical dedup).
    """
    cols = [
        (jnp.uint32(i) + _mulhi32(rand[..., i], p.n - i)).astype(jnp.int32)
        for i in range(weight)
    ]
    sup = jnp.stack(cols, axis=-1)

    idx = jnp.arange(weight)

    def fix(k, s):
        i = weight - 1 - k
        # contiguous dynamic slice + masked write — no per-lane gather/scatter
        si = lax.dynamic_slice_in_dim(s, i, 1, axis=-1)
        clash = jnp.any((s == si) & (idx > i), axis=-1, keepdims=True)
        si_new = jnp.where(clash, i, si)
        return jnp.where(idx == i, si_new, s)

    return lax.fori_loop(0, weight, fix, sup)


def _support_to_bits(p: HQCParams, sup: jax.Array) -> jax.Array:
    """(batch, w) positions -> (batch, n) uint8 bits."""
    v = jnp.zeros(sup.shape[:-1] + (p.n,), jnp.uint8)
    return jnp.put_along_axis(v, sup, jnp.uint8(1), axis=-1, inplace=False)


def _sample_random_bits(p: HQCParams, seed: jax.Array) -> jax.Array:
    """h: first n_bytes of the seed's expander stream."""
    return _bytes_to_bits(_seedexpand(seed, p.n_bytes), p.n)


# -- cyclic arithmetic --------------------------------------------------------


#: Set by :func:`get` when the FFT environment self-check FAILS: overrides
#: the default formulation for the rest of the process (jit caches the
#: traced path, so this must be decided before the first trace).
_FORCED_IMPL: str | None = None


def _cyclic_impl() -> str:
    """Which cyclic-product formulation to trace: "fft" (default),
    "matmul" (QRP2P_HQC_FFT=0 — the blocked-circulant MXU path), or
    "gather" (QRP2P_HQC_GATHER=1 — the rotated-gather loop).  Read at
    TRACE time (fresh process per setting, same caveat as QRP2P_PALLAS)."""
    import os

    if os.environ.get("QRP2P_HQC_GATHER", "0") == "1":
        return "gather"
    if os.environ.get("QRP2P_HQC_FFT", "1") == "0":
        return "matmul"
    if _FORCED_IMPL is not None:
        return _FORCED_IMPL
    return "fft"


def _cyclic_block(n: int) -> int:
    """Shift-block size: bounds the (batch, K, n) Toeplitz transient."""
    return 256 if n <= 20000 else (128 if n <= 40000 else 64)


def _cyclic_mul_matmul(p: HQCParams, dense: jax.Array, sup: jax.Array) -> jax.Array:
    """Gather-free cyclic product: out = dense ⊛ onehot(sup) via blocked
    Toeplitz contractions under a ``lax.scan``.

    Per-lane dynamic gathers (the rotated-index loop below) serialise on
    TPU — the same hazard that cost ML-DSA 25-100x before its samplers went
    gather-free.  Here the support densifies to a one-hot row (a tiny
    w-element scatter), the dense vector is TRIPLED so every rotation is a
    contiguous window, and each block of K shift amounts takes ONE
    scalar-start dynamic window + K static slices (a Toeplitz expansion —
    no per-lane indices anywhere) contracted on the MXU against the one-hot
    slice.  O(n^2) int8 arithmetic instead of O(w*n) serialised gathers;
    arithmetic is what the chip has.
    """
    n = p.n
    k_blk = _cyclic_block(n)
    nblocks = -(-n // k_blk)
    batch = dense.shape[:-1]
    y = _support_to_bits(p, sup).astype(jnp.int8)
    pad = nblocks * k_blk - n
    if pad:
        y = jnp.pad(y, [(0, 0)] * len(batch) + [(0, pad)])
    d3 = jnp.concatenate([dense, dense, dense], axis=-1).astype(jnp.int8)

    def body(acc, blk):
        p0 = blk * k_blk
        # W[j] = d3[2n - p0 - (K-1) + j]; chunk[dp, i] = W[K-1-dp + i]
        #      = dense[(i - p0 - dp) mod n]  (start always > 0: tripled array)
        w_seg = lax.dynamic_slice_in_dim(d3, 2 * n - p0 - (k_blk - 1),
                                         n + k_blk - 1, axis=-1)
        chunk = jnp.stack(
            [w_seg[..., k_blk - 1 - dp : k_blk - 1 - dp + n]
             for dp in range(k_blk)],
            axis=-2,
        )  # (..., K, n)
        y_blk = lax.dynamic_slice_in_dim(y, p0, k_blk, axis=-1)
        acc = acc + jnp.einsum(
            "...kn,...k->...n", chunk, y_blk,
            preferred_element_type=jnp.int32,
        )
        return acc, None

    acc0 = jnp.zeros(batch + (n,), jnp.int32)
    acc, _ = lax.scan(body, acc0, jnp.arange(nblocks))
    return (acc & 1).astype(jnp.uint8)


def _fft_circ(p: HQCParams, dense: jax.Array, sup: jax.Array) -> jax.Array:
    """Float32 circular-convolution counts (pre-rounding) — shared by the
    production path and the environment self-check probe."""
    n = p.n
    nfft = 1 << (2 * n - 2).bit_length()
    y = _support_to_bits(p, sup)
    fd = jnp.fft.rfft(dense.astype(jnp.float32), nfft, axis=-1)
    fy = jnp.fft.rfft(y.astype(jnp.float32), nfft, axis=-1)
    lin = jnp.fft.irfft(fd * fy, nfft, axis=-1)
    tail = jnp.pad(lin[..., n : 2 * n - 1], [(0, 0)] * (lin.ndim - 1) + [(0, 1)])
    return lin[..., :n] + tail


def _cyclic_mul_fft(p: HQCParams, dense: jax.Array, sup: jax.Array) -> jax.Array:
    """Cyclic product as an exact float32 FFT convolution.

    The integer circular convolution of two 0/1 vectors has values
    <= w <= 149 — far inside float32's exact-integer range — and the
    f32 round-trip error at these sizes measures ~1e-4 (worst case
    all-ones dense, asserted in tests/test_hqc.py), a ~5000x margin
    under the 0.5 rounding threshold.  Because that margin is measured,
    not proven, the first :func:`get` in an environment runs
    :func:`_fft_selfcheck` on-device and falls back to the Toeplitz
    path if it fails.  O(N log N) replaces the Toeplitz path's O(n^2)
    MACs and, more importantly, its ~chunk-materialisation HBM traffic
    (the measured bottleneck of every HQC op).  n is prime (no length-n
    FFT), so a pow2-padded LINEAR convolution is folded back to
    circular: circ[i] = lin[i] + lin[i + n].
    """
    circ = _fft_circ(p, dense, sup)
    return (jnp.rint(circ).astype(jnp.int32) & 1).astype(jnp.uint8)


def _cyclic_mul_sparse(p: HQCParams, dense: jax.Array, sup: jax.Array) -> jax.Array:
    """dense (batch, n) bits x support (batch, w) -> (batch, n) bits.

    out[i] = XOR_k dense[(i - p_k) mod n].  Dispatches to the exact-f32
    FFT convolution by default; the blocked-circulant MXU formulation
    (QRP2P_HQC_FFT=0) and the per-support rotated-gather loop
    (QRP2P_HQC_GATHER=1) remain for A/B.

    PRECONDITION: support positions must be pairwise distinct (guaranteed
    by :func:`_fixed_weight_support`'s dedup).  The three formulations
    disagree on duplicates — FFT/matmul go through ``_support_to_bits``
    where duplicates collapse to ONE hit, while the rotated-gather loop
    counts each, so a doubled position cancels mod 2.  Distinctness is the
    stated common contract; nothing in the KEM can violate it, and it is
    asserted below (under ``__debug__``, on concrete inputs only — traced
    values cannot be inspected) so an A/B harness feeding a duplicated
    support fails HERE, not as a silent cross-implementation divergence.
    """
    if __debug__ and not isinstance(sup, jax.core.Tracer):
        _s = np.sort(np.asarray(sup), axis=-1)
        assert bool((np.diff(_s, axis=-1) != 0).all()), (
            "_cyclic_mul_sparse: support positions must be pairwise distinct "
            "(the FFT/matmul and rotated-gather formulations disagree on "
            "duplicates)"
        )
    impl = _cyclic_impl()
    if impl == "fft":
        return _cyclic_mul_fft(p, dense, sup)
    if impl == "matmul":
        return _cyclic_mul_matmul(p, dense, sup)
    n = p.n
    w = sup.shape[-1]
    base = jnp.arange(n)

    def step(k, acc):
        pk = jnp.take_along_axis(sup, jnp.full(sup.shape[:-1] + (1,), k), axis=-1)
        idx = (base - pk) % n
        return acc + jnp.take_along_axis(dense.astype(jnp.int32), idx, axis=-1)

    acc = lax.fori_loop(0, w, step, jnp.zeros(dense.shape, jnp.int32))
    return (acc & 1).astype(jnp.uint8)


# -- FFT environment self-check ----------------------------------------------
#
# The FFT cyclic product is exact only while device-FFT rounding stays under
# 0.5; the measured margin (~1e-4) is empirical, so a new device / XLA / JAX
# version could silently flip KEM bits.  The first `get()` per environment
# therefore runs an on-device probe and falls back to the Toeplitz-MXU path
# on failure.  The verdict is cached per (jax version, jaxlib version,
# device kind) in ~/.cache/qrp2p_tpu so the cost is once per environment,
# not per process.  QRP2P_HQC_SELFCHECK=0 skips the gate (trust the FFT);
# chip_smoke.py runs the same comparison on the chip.


def _fft_selfcheck(p: HQCParams) -> tuple[bool, float]:
    """On-device exactness probe for the f32 FFT cyclic product.

    Runs the largest transform in the suite with (a) all-ones dense — the
    worst-case convolution magnitude — and (b) random dense, comparing bits
    against a host-exact XOR-of-rotations and requiring the pre-rounding
    residual max|circ - rint(circ)| < 0.25 (2x margin under the rounding
    threshold).  Returns (ok, worst_residual).
    """
    rng = np.random.default_rng(0x48514346)  # "HQCF"
    sup = np.sort(rng.choice(p.n, size=p.wr, replace=False)).astype(np.int32)

    @jax.jit
    def probe(dense, sup):
        circ = _fft_circ(p, dense, sup)
        bits = (jnp.rint(circ).astype(jnp.int32) & 1).astype(jnp.uint8)
        return bits, jnp.max(jnp.abs(circ - jnp.rint(circ)))

    ok, worst = True, 0.0
    for dense in (np.ones(p.n, np.uint8), rng.integers(0, 2, p.n, np.uint8)):
        bits, resid = probe(dense[None], sup[None])
        acc = np.zeros(p.n, np.int64)
        for pos in sup:
            acc += np.roll(dense.astype(np.int64), pos)
        ok &= bool((np.asarray(bits)[0] == (acc & 1).astype(np.uint8)).all())
        worst = max(worst, float(resid))
    return ok and worst < 0.25, worst


def _fft_env_key() -> str:
    import jaxlib

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform)
    return f"jax={jax.__version__}|jaxlib={jaxlib.__version__}|dev={kind}"


#: In-process memo of the environment verdict (None = not yet decided) —
#: without it an unwritable ~/.cache would re-run the on-device probe on
#: every get() call.
_FFT_ENV_OK: bool | None = None


def _fft_env_validated() -> bool:
    """Cached per-environment verdict; runs the probe on first sight."""
    global _FFT_ENV_OK
    import hashlib
    import json
    import logging
    import pathlib

    if _FFT_ENV_OK is not None:
        return _FFT_ENV_OK
    from ..native import _CACHE_DIR  # shared cache dir (QRP_NATIVE_CACHE)

    key = _fft_env_key()
    p = max(PARAMS.values(), key=lambda q: q.n)  # largest transform + weight
    cache = pathlib.Path(_CACHE_DIR)
    marker = cache / f"hqc_fft_ok_{hashlib.sha256(key.encode()).hexdigest()[:16]}.json"
    try:
        rec = json.loads(marker.read_text())
        # probe_n guards against a stale verdict from an older package
        # whose largest parameter set was smaller than today's.  Only a
        # POSITIVE verdict is trusted from the marker: this platform's
        # device faults are documented transient, so a failed probe
        # re-runs every process (self-healing) rather than pinning the
        # slow Toeplitz path forever.
        if isinstance(rec, dict) and rec.get("key") == key and rec.get("probe_n") == p.n:
            if rec.get("ok"):
                _FFT_ENV_OK = True
                return True
    except (OSError, ValueError, KeyError):
        pass
    ok, resid = _fft_selfcheck(p)
    if not ok:
        logging.getLogger(__name__).warning(
            "HQC f32-FFT self-check FAILED on %s (residual %.3g) — "
            "falling back to the Toeplitz-MXU cyclic product for this "
            "process (re-probed at next process start)", key, resid
        )
    if ok:
        try:
            cache.mkdir(parents=True, exist_ok=True)
            marker.write_text(json.dumps(
                {"key": key, "ok": ok, "worst_residual": resid, "probe_n": p.n}
            ))
        except OSError:
            pass
    _FFT_ENV_OK = ok
    return ok


def _maybe_gate_fft() -> None:
    """Decide the FFT-vs-Toeplitz default for this process (called by
    :func:`get` before anything is traced)."""
    global _FORCED_IMPL
    import os

    if _FORCED_IMPL is not None or _cyclic_impl() != "fft":
        return
    if os.environ.get("QRP2P_HQC_SELFCHECK", "1") == "0":
        return
    if not _fft_env_validated():
        _FORCED_IMPL = "matmul"


# -- Reed-Solomon over GF(2^8), in-graph --------------------------------------


@functools.cache
def _rs_gen_tables(p: HQCParams) -> np.ndarray:
    return _gf_const_tables(np.asarray(_rs_gen_poly(p)[: 2 * p.delta], np.int64))


def _rs_encode(p: HQCParams, msg: jax.Array) -> jax.Array:
    """(batch, k) int32 bytes -> (batch, n1) codeword.

    Unrolled LFSR division (k <= 32 static steps) with the generator
    product as masked XORs against constant tables — no gathers."""
    g_tab = jnp.asarray(_rs_gen_tables(p))
    red = 2 * p.delta
    rem = jnp.zeros(msg.shape[:-1] + (red,), jnp.int32)
    for j in range(p.k):
        coef = msg[..., p.k - 1 - j] ^ rem[..., -1]
        rem = jnp.concatenate([jnp.zeros_like(rem[..., :1]), rem[..., :-1]], axis=-1)
        rem = rem ^ _gf_mul_const(coef[..., None], g_tab)
    return jnp.concatenate([rem, msg], axis=-1)


@functools.cache
def _syndrome_tables(p: HQCParams) -> np.ndarray:
    red = 2 * p.delta
    ij = np.outer(np.arange(1, red + 1), np.arange(p.n1)) % 255
    return _gf_const_tables(_EXP[ij].astype(np.int64))  # (8, red, n1)


def _rs_syndromes(p: HQCParams, cw: jax.Array) -> jax.Array:
    terms = _gf_mul_const(cw[..., None, :], jnp.asarray(_syndrome_tables(p)))
    return _xor_reduce(terms, -1)  # (batch, red)


def _rs_bm(p: HQCParams, synd: jax.Array) -> jax.Array:
    """Branch-free, gather-free Berlekamp-Massey -> sigma (batch, red+1).

    Two reformulations keep per-lane indices out of the scan body: the
    syndrome window S[n_it], .., S[n_it-deg+1] is one contiguous
    ``dynamic_slice`` of the zero-padded syndrome array (reversed — a
    static op), and the textbook ``x^m * B(x)`` update term — a per-lane
    dynamic shift, since m is data-dependent — is carried incrementally:
    ``D_next = x * (sigma_old if grow else D)``, a shift-by-one of a
    select, which reproduces x^m * B exactly (m resets to 1 on growth).
    """
    red = 2 * p.delta
    batch = synd.shape[:-1]
    deg = red + 1
    sigma0 = jnp.zeros(batch + (deg,), jnp.int32).at[..., 0].set(1)
    # D = x^m * B(x); initially m=1, B=1 => D = x
    d0 = jnp.zeros(batch + (deg,), jnp.int32).at[..., 1].set(1)
    state = (sigma0, d0, jnp.zeros(batch, jnp.int32), jnp.ones(batch, jnp.int32))

    spad = jnp.concatenate([jnp.zeros(batch + (deg,), jnp.int32), synd], axis=-1)

    def shift1(v):
        return jnp.concatenate([jnp.zeros_like(v[..., :1]), v[..., :-1]], axis=-1)

    def step(n_it, st):
        sigma, D, L, bb = st
        # d = XOR_i sigma[i] * S[n_it - i]: spad[n_it+1 .. n_it+deg] reversed
        window = lax.dynamic_slice_in_dim(spad, n_it + 1, deg, axis=-1)
        s_slice = jnp.flip(window, axis=-1)
        d = _xor_reduce(_gf_mul(sigma, s_slice), -1)
        dz = d == 0
        coef = _gf_mul(d, _gf_inv(bb))
        sigma_new = sigma ^ _gf_mul(coef[..., None], D)
        grow = (~dz) & (2 * L <= n_it)
        sigma_out = jnp.where(dz[..., None], sigma, sigma_new)
        D_out = shift1(jnp.where(grow[..., None], sigma, D))
        L_out = jnp.where(grow, n_it + 1 - L, L)
        bb_out = jnp.where(grow, d, bb)
        return sigma_out, D_out, L_out, bb_out

    sigma, *_ = lax.fori_loop(0, red, step, state)
    return sigma


@functools.cache
def _chien_forney_tables(p: HQCParams) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    red = 2 * p.delta
    deg = red + 1
    inv_j = (255 - np.arange(p.n1)) % 255
    ij = np.outer(np.arange(deg), inv_j) % 255
    ijo = np.outer(np.arange(red), inv_j) % 255
    odd = np.arange(1, deg, 2)
    ijd = np.outer(odd - 1, inv_j) % 255
    return (
        _gf_const_tables(_EXP[ij].astype(np.int64)),   # (8, deg, n1)
        _gf_const_tables(_EXP[ijo].astype(np.int64)),  # (8, red, n1)
        _gf_const_tables(_EXP[ijd].astype(np.int64)),  # (8, len(odd), n1)
    )


def _rs_decode(p: HQCParams, cw: jax.Array) -> jax.Array:
    """(batch, n1) int32 -> (batch, k) message bytes (corrects <= delta errors)."""
    red = 2 * p.delta
    t_chien, t_omega, t_deriv = (jnp.asarray(t) for t in _chien_forney_tables(p))
    synd = _rs_syndromes(p, cw)
    sigma = _rs_bm(p, synd)
    # Chien over all positions: sigma(alpha^{-j})
    ev = _xor_reduce(_gf_mul_const(sigma[..., :, None], t_chien), -2)  # (batch, n1)
    is_err = ev == 0
    # omega = S(x) * sigma(x) mod x^red: one static-slice contraction per
    # degree (sigma[..., i::-1] is a strided slice, not a gather)
    omega = []
    for i in range(red):
        prod = _gf_mul(sigma[..., : i + 1], jnp.flip(synd[..., : i + 1], -1))
        omega.append(_xor_reduce(prod, -1))
    omega = jnp.stack(omega, axis=-1)  # (batch, red)
    # Forney at every position (masked by is_err): num = omega(alpha^{-j})
    num = _xor_reduce(_gf_mul_const(omega[..., :, None], t_omega), -2)
    # den = sigma'(alpha^{-j}) = sum over odd i of sigma[i] (alpha^{-j})^{i-1}
    den = _xor_reduce(_gf_mul_const(sigma[..., 1::2, None], t_deriv), -2)
    mag = _gf_mul(num, _gf_inv(den))
    corrected = cw ^ jnp.where(is_err & (den != 0), mag, 0)
    return corrected[..., red:]


# -- duplicated RM(1,7) -------------------------------------------------------


# RM(1,7) is linear: encode(m) = XOR of generator rows selected by m's bits.
# Verified against the pyref table at import; kills the (256, 128) per-lane
# table gather in _rm_encode.
_RM_ROWS = np.stack([_RM_BITS[1 << k] for k in range(8)])  # (8, 128)
assert all(
    np.array_equal(
        np.bitwise_xor.reduce(
            [_RM_ROWS[k] for k in range(8) if (v >> k) & 1] or [np.zeros(RM_N, np.int32)]
        ),
        _RM_BITS[v],
    )
    for v in range(256)
), "RM(1,7) table is not linear — generator-row encode would be wrong"


def _rm_encode(p: HQCParams, rs_cw: jax.Array) -> jax.Array:
    """(batch, n1) bytes -> (batch, n1*n2) bits (linear masked-XOR encode)."""
    cw = _gf_mul_const(
        rs_cw[..., None], jnp.asarray(_RM_ROWS, jnp.int32)
    ).astype(jnp.uint8)  # (batch, n1, 128)
    dup = jnp.repeat(cw[..., None, :], p.dup, axis=-2)  # (batch, n1, dup, 128)
    return dup.reshape(rs_cw.shape[:-1] + (p.n1 * p.n2,))


def _rm_decode(p: HQCParams, bits: jax.Array) -> jax.Array:
    """(batch, n1*n2) bits -> (batch, n1) decoded bytes (soft FHT)."""
    x = bits.reshape(bits.shape[:-1] + (p.n1, p.dup, RM_N)).astype(jnp.int32)
    f = jnp.sum(1 - 2 * x, axis=-2)  # (batch, n1, 128) soft counts
    h = 1
    while h < RM_N:
        fr = f.reshape(f.shape[:-1] + (RM_N // (2 * h), 2, h))
        a, b = fr[..., 0, :], fr[..., 1, :]
        f = jnp.stack([a + b, a - b], axis=-2).reshape(f.shape)
        h *= 2
    best = jnp.argmax(jnp.abs(f), axis=-1)  # (batch, n1)
    # select f[best] without a per-lane gather: one-hot contraction
    onehot = (jnp.arange(RM_N) == best[..., None]).astype(jnp.int32)
    fbest = jnp.sum(f * onehot, axis=-1)
    b0 = (fbest < 0).astype(jnp.int32)
    return (best << 1) | b0


# -- hashes -------------------------------------------------------------------


def _hash_dom(data: jax.Array, domain: int, out_len: int = 64) -> jax.Array:
    """SHAKE256-512 with TRAILING domain byte (HQC hash.c shake256_512_ds)."""
    sfx = jnp.broadcast_to(jnp.uint8(domain), data.shape[:-1] + (1,))
    return keccak.shake256(jnp.concatenate([data, sfx], axis=-1), out_len)


# -- KEM ----------------------------------------------------------------------


def keygen(p: HQCParams, sk_seed: jax.Array, sigma: jax.Array, pk_seed: jax.Array):
    """sk_seed (..., 40), sigma (..., k), pk_seed (..., 40) -> (pk, sk)."""
    sk_seed = jnp.asarray(sk_seed, jnp.uint8)
    sigma = jnp.asarray(sigma, jnp.uint8)
    pk_seed = jnp.asarray(pk_seed, jnp.uint8)
    h = _sample_random_bits(p, pk_seed)
    # one sk expander stream: y first, then x (pyref keygen order)
    sk_stream = _u32s(_seedexpand(sk_seed, 8 * p.w))
    y_sup = _fixed_weight_support(p, sk_stream[..., : p.w], p.w)
    x_sup = _fixed_weight_support(p, sk_stream[..., p.w :], p.w)
    x = _support_to_bits(p, x_sup)
    s = x ^ _cyclic_mul_sparse(p, h, y_sup)
    pk = jnp.concatenate([pk_seed, _bits_to_bytes(s)], axis=-1)
    sk = jnp.concatenate([sk_seed, sigma, pk], axis=-1)
    return pk, sk


def _encrypt(p: HQCParams, pk: jax.Array, m: jax.Array, theta: jax.Array):
    pk_seed = pk[..., :40]
    s = _bytes_to_bits(pk[..., 40:], p.n)
    h = _sample_random_bits(p, pk_seed)
    # one theta expander stream: r2, e, r1 (pyref _encrypt order)
    stream = _u32s(_seedexpand(theta, 12 * p.wr))
    r2_sup = _fixed_weight_support(p, stream[..., : p.wr], p.wr)
    e_sup = _fixed_weight_support(p, stream[..., p.wr : 2 * p.wr], p.wr)
    r1_sup = _fixed_weight_support(p, stream[..., 2 * p.wr :], p.wr)
    u = _support_to_bits(p, r1_sup) ^ _cyclic_mul_sparse(p, h, r2_sup)
    code = _rm_encode(p, _rs_encode(p, m.astype(jnp.int32)))
    t = _cyclic_mul_sparse(p, s, r2_sup) ^ _support_to_bits(p, e_sup)
    v = code ^ t[..., : p.n1 * p.n2]
    return u, v


def encaps(p: HQCParams, pk: jax.Array, m: jax.Array, salt: jax.Array):
    """pk, m (..., k), salt (..., 16) -> (ct (..., ct_len), ss (..., 64))."""
    pk = jnp.asarray(pk, jnp.uint8)
    m = jnp.asarray(m, jnp.uint8)
    salt = jnp.asarray(salt, jnp.uint8)
    theta = _hash_dom(jnp.concatenate([m, pk[..., :32], salt], axis=-1), 3)
    u, v = _encrypt(p, pk, m, theta)
    u_b = _bits_to_bytes(u)
    v_b = _bits_to_bytes(v)
    ct = jnp.concatenate([u_b, v_b, salt], axis=-1)
    ss = _hash_dom(jnp.concatenate([m, u_b, v_b], axis=-1), 4)
    return ct, ss


def decaps(p: HQCParams, sk: jax.Array, ct: jax.Array):
    sk = jnp.asarray(sk, jnp.uint8)
    ct = jnp.asarray(ct, jnp.uint8)
    sk_seed = sk[..., :40]
    sigma = sk[..., 40 : 40 + p.k]
    pk = sk[..., 40 + p.k :]
    u_b = ct[..., : p.n_bytes]
    v_b = ct[..., p.n_bytes : p.n_bytes + p.n1n2_bytes]
    salt = ct[..., p.n_bytes + p.n1n2_bytes :]
    u = _bytes_to_bits(u_b, p.n)
    v = _bytes_to_bits(v_b, p.n1 * p.n2)
    # y = first fixed-weight draw off the sk expander stream
    sk_stream = _u32s(_seedexpand(sk_seed, 4 * p.w))
    y_sup = _fixed_weight_support(p, sk_stream, p.w)
    uy = _cyclic_mul_sparse(p, u, y_sup)
    m_p = _rs_decode(p, _rm_decode(p, v ^ uy[..., : p.n1 * p.n2])).astype(jnp.uint8)
    theta_p = _hash_dom(jnp.concatenate([m_p, pk[..., :32], salt], axis=-1), 3)
    u2, v2 = _encrypt(p, pk, m_p, theta_p)
    ok = jnp.all(_bits_to_bytes(u2) == u_b, axis=-1) & jnp.all(
        _bits_to_bytes(v2) == v_b, axis=-1
    )
    good = _hash_dom(jnp.concatenate([m_p, u_b, v_b], axis=-1), 4)
    bad = _hash_dom(jnp.concatenate([sigma, u_b, v_b], axis=-1), 4)
    return jnp.where(ok[..., None], good, bad)


@functools.cache
def get(name: str):
    """Jitted (keygen, encaps, decaps) triple for a parameter-set name."""
    p = PARAMS[name]
    _maybe_gate_fft()
    return (
        jax.jit(functools.partial(keygen, p)),
        jax.jit(functools.partial(encaps, p)),
        jax.jit(functools.partial(decaps, p)),
    )
