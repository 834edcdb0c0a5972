"""qrkernel abstract domains: integer interval + known-bits, dtypes, shapes.

The value domain is an interval ``[lo, hi]`` (``None`` = unbounded on that
side) refined with a *maybe-bits* mask — for non-negative values, the set of
bit positions that may be 1.  The mask is what makes byte-assembly proofs
exact: ``b0 | ((b1 & 0xF) << 8)`` has maybe-bits ``0xFFF``, so the OR is
known to stay a 12-bit value instead of the ``hi_a + hi_b`` a plain interval
would give.  Transfer functions compute the MATHEMATICAL result; dtype
wrapping is applied (and observed) separately by :meth:`IVal.fits`, which is
exactly the proof obligation of the value-range rule: the math interval of a
``*``/``<<`` site must fit its vector-register dtype.

Shapes are symbolic tuples of :class:`Dim` — a product normal form
``coeff * sym1 * sym2 …`` over opaque symbols (a parameter's unknown batch
dim, a sum that doesn't normalise).  Two dims are *provably different* only
when their symbolic factors agree and their integer coefficients differ;
everything else is "unknown", so symbolic code can never false-positive.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Iterable

#: integer dtypes with (lo, hi) representable ranges; floats carry no interval
INT_DTYPES: dict[str, tuple[int, int]] = {
    "bool": (0, 1),
    "uint8": (0, 2**8 - 1),
    "uint16": (0, 2**16 - 1),
    "uint32": (0, 2**32 - 1),
    "uint64": (0, 2**64 - 1),
    "int8": (-(2**7), 2**7 - 1),
    "int16": (-(2**15), 2**15 - 1),
    "int32": (-(2**31), 2**31 - 1),
    "int64": (-(2**63), 2**63 - 1),
}

FLOAT_DTYPES = ("bfloat16", "float16", "float32", "float64")

#: promotion order for the accumulator-dtype check (narrower < wider)
DTYPE_WIDTH: dict[str, int] = {
    "bool": 1, "int8": 8, "uint8": 8, "int16": 16, "uint16": 16,
    "bfloat16": 16, "float16": 16, "int32": 32, "uint32": 32, "float32": 32,
    "int64": 64, "uint64": 64, "float64": 64,
}

#: the conservative check width when a tile's dtype is unknown: TPU vector
#: registers are 32-bit and Mosaic's vector min/max are signed, so int32 is
#: the range a wrap-silent product must fit (matches qrlint's rule text)
DEFAULT_CHECK_DTYPE = "int32"

_MASK64 = 2**64 - 1


def _mask_of(hi: int) -> int:
    """Smallest all-ones mask covering ``hi`` (0 for hi <= 0)."""
    return (1 << max(hi, 0).bit_length()) - 1


@dataclasses.dataclass(frozen=True)
class IVal:
    """Abstract integer (scalar or array element): interval + maybe-bits.

    ``lo``/``hi``: inclusive bounds, ``None`` = unbounded.  ``mb``: for
    values proven non-negative, a mask of bits that may be set (``None`` =
    no bit information).  ``dtype``: the array dtype when known (host Python
    ints, which never wrap, have ``dtype=None``).  ``tile``: True when the
    value is (derived from) a kernel tile / traced array — only tile sites
    carry the 32-bit wrap hazard.
    """

    lo: int | None = None
    hi: int | None = None
    mb: int | None = None
    dtype: str | None = None
    tile: bool = False
    #: symbolic array shape (tuple of Dim) when known, None otherwise
    shape: tuple = None  # type: ignore[assignment]

    # -- constructors -------------------------------------------------------

    @staticmethod
    def const(v: int, dtype: str | None = None, tile: bool = False) -> "IVal":
        mb = v if v >= 0 else None
        return IVal(v, v, mb, dtype, tile)

    @staticmethod
    def range(lo: int | None, hi: int | None, dtype: str | None = None,
              tile: bool = False) -> "IVal":
        mb = _mask_of(hi) if (lo is not None and lo >= 0 and hi is not None) else None
        return IVal(lo, hi, mb, dtype, tile)

    @staticmethod
    def top(dtype: str | None = None, tile: bool = False) -> "IVal":
        if dtype in INT_DTYPES:
            lo, hi = INT_DTYPES[dtype]
            return IVal.range(lo, hi, dtype, tile)
        return IVal(None, None, None, dtype, tile)

    # -- queries ------------------------------------------------------------

    @property
    def is_const(self) -> bool:
        return self.lo is not None and self.lo == self.hi

    @property
    def nonneg(self) -> bool:
        return self.lo is not None and self.lo >= 0

    def effective_hi(self) -> int | None:
        """Tightest upper bound: interval hi refined by the maybe-bits mask."""
        if self.mb is not None:
            return self.mb if self.hi is None else min(self.hi, self.mb)
        return self.hi

    def fits(self, dtype: str | None) -> bool | None:
        """Does the MATH value provably fit ``dtype``'s representable range?

        True = proven in range, False = provably out of range, None = unknown.
        ``dtype=None`` checks against :data:`DEFAULT_CHECK_DTYPE` (int32).
        """
        rng = INT_DTYPES.get(dtype or DEFAULT_CHECK_DTYPE)
        if rng is None:
            return None  # float dtype: wrap analysis does not apply
        lo, hi = self.lo, self.effective_hi()
        if lo is None or hi is None:
            return None
        if rng[0] <= lo and hi <= rng[1]:
            return True
        if hi < rng[0] or lo > rng[1]:
            return False
        return None  # straddles the boundary: not provable either way

    def wrapped(self, dtype: str | None) -> "IVal":
        """The value as stored in ``dtype``: unchanged when it provably fits,
        else the full dtype range (the wrap destroyed the bound)."""
        dt = dtype if dtype is not None else self.dtype
        if dt not in INT_DTYPES:
            return dataclasses.replace(self, dtype=dt)
        if self.fits(dt) is True:
            return dataclasses.replace(self, dtype=dt)
        return IVal.top(dt, tile=self.tile)

    # -- lattice ------------------------------------------------------------

    def join(self, other: "IVal") -> "IVal":
        lo = None if self.lo is None or other.lo is None else min(self.lo, other.lo)
        hi = None if self.hi is None or other.hi is None else max(self.hi, other.hi)
        mb = None if self.mb is None or other.mb is None else (self.mb | other.mb)
        dtype = self.dtype if self.dtype == other.dtype else None
        shape = self.shape if self.shape == other.shape else None
        return IVal(lo, hi, mb, dtype, self.tile or other.tile, shape)


TOP = IVal()


def join_all(vals: Iterable[IVal]) -> IVal:
    out: IVal | None = None
    for v in vals:
        out = v if out is None else out.join(v)
    return out if out is not None else TOP


# -- transfer functions -------------------------------------------------------
#
# Each returns the MATHEMATICAL interval of the op (no dtype wrap); the
# interpreter applies .wrapped() afterwards and records the pre-wrap value at
# checked sites.  All handle unbounded operands by degrading to TOP-ish.


def _tile(a: IVal, b: IVal) -> bool:
    return a.tile or b.tile


def add(a: IVal, b: IVal) -> IVal:
    lo = None if a.lo is None or b.lo is None else a.lo + b.lo
    hi = None if a.hi is None or b.hi is None else a.hi + b.hi
    return IVal.range(lo, hi, None, _tile(a, b))


def sub(a: IVal, b: IVal) -> IVal:
    lo = None if a.lo is None or b.hi is None else a.lo - b.hi
    hi = None if a.hi is None or b.lo is None else a.hi - b.lo
    return IVal.range(lo, hi, None, _tile(a, b))


def mul(a: IVal, b: IVal) -> IVal:
    if None in (a.lo, a.hi, b.lo, b.hi):
        return IVal(None, None, None, None, _tile(a, b))
    corners = [x * y for x, y in itertools.product((a.lo, a.hi), (b.lo, b.hi))]
    return IVal.range(min(corners), max(corners), None, _tile(a, b))


def lshift(a: IVal, b: IVal) -> IVal:
    if b.lo is None or b.hi is None or b.lo < 0 or b.hi > 256:
        return IVal(None, None, None, None, _tile(a, b))
    lo = None if a.lo is None else a.lo << (b.lo if a.lo >= 0 else b.hi)
    hi = None if a.hi is None else a.hi << (b.hi if a.hi >= 0 else b.lo)
    out = IVal.range(lo, hi, None, _tile(a, b))
    if a.mb is not None and out.nonneg:
        mb = 0
        for n in range(b.lo, b.hi + 1):
            mb |= a.mb << n
        out = dataclasses.replace(out, mb=mb)
    return out


def rshift(a: IVal, b: IVal) -> IVal:
    tile = _tile(a, b)
    if b.lo is None or b.lo < 0 or not a.nonneg:
        return IVal(None, None, None, None, tile)
    if a.hi is None:  # non-negative >> non-negative stays non-negative
        return IVal(0, None, None, None, tile)
    hi = a.hi >> b.lo
    lo = 0 if b.hi is None else (a.lo >> b.hi)
    return IVal.range(lo, hi, None, tile)


def bitand(a: IVal, b: IVal) -> IVal:
    # x & mask is in [0, mask] for a non-negative mask REGARDLESS of x's sign
    # (the AND with a non-negative value clears the sign bit) — the seed fact
    # `x & 0xFF -> [0, 255]` needs no dtype knowledge.
    tile = _tile(a, b)
    mb: int | None = None
    hi: int | None = None
    for v in (a, b):
        if v.nonneg and v.hi is not None:
            m = v.mb if v.mb is not None else _mask_of(v.hi)
            mb = m if mb is None else (mb & m)
            hi = v.hi if hi is None else min(hi, v.hi)
    if mb is not None:
        return IVal(0, min(hi, mb), mb, None, tile)
    if a.nonneg or b.nonneg:  # one side non-negative, but unbounded
        return IVal(0, None, None, None, tile)
    return IVal(None, None, None, None, tile)


def bitor(a: IVal, b: IVal) -> IVal:
    if a.nonneg and b.nonneg and a.mb is not None and b.mb is not None:
        mb = a.mb | b.mb
        lo = max(a.lo, b.lo)
        return IVal(lo, mb, mb, None, _tile(a, b))
    return IVal(None, None, None, None, _tile(a, b))


def bitxor(a: IVal, b: IVal) -> IVal:
    if a.nonneg and b.nonneg and a.mb is not None and b.mb is not None:
        mb = a.mb | b.mb
        return IVal(0, mb, mb, None, _tile(a, b))
    return IVal(None, None, None, None, _tile(a, b))


def mod(a: IVal, b: IVal) -> IVal:
    # Python/jnp mod takes the divisor's sign: positive q -> [0, q-1]
    if b.lo is not None and b.lo > 0 and b.hi is not None:
        return IVal.range(0, b.hi - 1, None, _tile(a, b))
    return IVal(None, None, None, None, _tile(a, b))


def rem(a: IVal, b: IVal) -> IVal:
    # lax.rem takes the dividend's sign: a >= 0, q > 0 -> [0, q-1]
    if b.lo is not None and b.lo > 0 and b.hi is not None:
        lo = 0 if a.lo is not None and a.lo >= 0 else -(b.hi - 1)
        return IVal.range(lo, b.hi - 1, None, _tile(a, b))
    return IVal(None, None, None, None, _tile(a, b))


def floordiv(a: IVal, b: IVal) -> IVal:
    if None in (a.lo, a.hi, b.lo, b.hi) or b.lo <= 0 <= b.hi:
        return IVal(None, None, None, None, _tile(a, b))
    corners = [x // y for x, y in itertools.product((a.lo, a.hi), (b.lo, b.hi))]
    return IVal.range(min(corners), max(corners), None, _tile(a, b))


def invert(a: IVal) -> IVal:
    lo = None if a.hi is None else -a.hi - 1
    hi = None if a.lo is None else -a.lo - 1
    return IVal.range(lo, hi, None, a.tile)


def neg(a: IVal) -> IVal:
    lo = None if a.hi is None else -a.hi
    hi = None if a.lo is None else -a.lo
    return IVal.range(lo, hi, None, a.tile)


def compare(a: IVal, b: IVal, op: str) -> IVal:
    """Abstract comparison: a bool value, concrete when decidable."""
    tile = _tile(a, b)
    if None not in (a.lo, a.hi, b.lo, b.hi):
        lt_always = a.hi < b.lo
        gt_always = a.lo > b.hi
        le_always = a.hi <= b.lo
        ge_always = a.lo >= b.hi
        table = {
            "<": (lt_always, ge_always), ">": (gt_always, le_always),
            "<=": (le_always, gt_always), ">=": (ge_always, lt_always),
            "==": (a.is_const and b.is_const and a.lo == b.lo, lt_always or gt_always),
            "!=": (lt_always or gt_always, a.is_const and b.is_const and a.lo == b.lo),
        }
        if op in table:
            true_always, false_always = table[op]
            if true_always:
                return IVal.const(1, "bool", tile)
            if false_always:
                return IVal.const(0, "bool", tile)
    return IVal.range(0, 1, "bool", tile)


# -- symbolic dims ------------------------------------------------------------

_opaque_counter = itertools.count()


@dataclasses.dataclass(frozen=True)
class Dim:
    """One symbolic array dim in product normal form: coeff * factors.

    ``factors`` is a sorted tuple of opaque symbol tokens.  A fresh opaque
    symbol is minted for anything that doesn't normalise (sums, unknown
    values), so structurally-unequal dims are merely *unknown*, never
    provably different.
    """

    coeff: int = 1
    factors: tuple[str, ...] = ()

    @staticmethod
    def const(n: int) -> "Dim":
        return Dim(n, ())

    @staticmethod
    def sym(token: str) -> "Dim":
        return Dim(1, (token,))

    @staticmethod
    def fresh(hint: str = "d") -> "Dim":
        return Dim(1, (f"{hint}?{next(_opaque_counter)}",))

    @property
    def is_const(self) -> bool:
        return not self.factors

    def __mul__(self, other: "Dim") -> "Dim":
        return Dim(self.coeff * other.coeff,
                   tuple(sorted(self.factors + other.factors)))

    def floordiv(self, n: int) -> "Dim":
        if n > 0 and self.coeff % n == 0:
            return Dim(self.coeff // n, self.factors)
        return Dim.fresh("div")

    def provably_ne(self, other: "Dim") -> bool:
        """True only when both dims share symbolic factors but differ in the
        concrete coefficient (covers fully-concrete mismatches too)."""
        return self.factors == other.factors and self.coeff != other.coeff

    def __str__(self) -> str:
        if not self.factors:
            return str(self.coeff)
        body = "*".join(f.split("?")[0] for f in self.factors)
        return body if self.coeff == 1 else f"{self.coeff}*{body}"


def shape_product(dims: Iterable[Dim]) -> Dim:
    out = Dim.const(1)
    for d in dims:
        out = out * d
    return out


def format_shape(shape: tuple[Dim, ...]) -> str:
    return "(" + ", ".join(str(d) for d in shape) + ")"


def dim_of(value: Any) -> Dim:
    """Best-effort Dim from an abstract value (IVal, SymVal, or int)."""
    if isinstance(value, Dim):
        return value
    if isinstance(value, int):
        return Dim.const(value)
    if isinstance(value, IVal) and value.is_const:
        return Dim.const(value.lo)
    inner = getattr(value, "dim", None)  # interp.SymVal (no circular import)
    if isinstance(inner, Dim):
        return inner
    return Dim.fresh()
