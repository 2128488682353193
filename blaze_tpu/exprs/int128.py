"""Vectorized two-limb int128 arithmetic over JAX int64 lanes.

Backs Spark-exact decimal semantics where the unscaled math exceeds
int64 (≙ the reference computing on Arrow decimal128 with
``check_overflow``, datafusion-ext-commons/src/cast.rs): wide decimal
multiply, division rescale, and sum/avg accumulation.

Representation: a signed 128-bit value ``v`` is carried as
``(hi: int64, lo: uint64)`` with ``v = hi * 2^64 + lo`` — the standard
two's-complement split (hi carries the sign).  All ops are elementwise
over arrays and jit-safe (no data-dependent control flow).

The engine stores decimal COLUMNS as int64 unscaled values; int128
lives only inside kernels (multiply/divide/accumulate), and results
are narrowed back with an exact fits-in-int64 check — values beyond
that (possible only for decimal(>18) results above ~9.2e18 at scale 0)
overflow to NULL, which is also what Spark does beyond precision 38.
"""

from __future__ import annotations

import jax.numpy as jnp

# plain ints: jnp scalars at module import would initialize a backend
# (and claim the chip) as a side effect of importing this module
_U32 = 0xFFFFFFFF
_32 = 32


def from_i64(v):
    """Sign-extend an int64 array to (hi, lo)."""
    return (v >> jnp.int64(63), v.view(jnp.uint64) if v.dtype == jnp.int64 else v.astype(jnp.uint64))


def to_i64(hi, lo):
    """(value as int64, fits) — exact narrowing check."""
    v = lo.view(jnp.int64)
    fits = hi == (v >> jnp.int64(63))
    return v, fits


def neg(hi, lo):
    """two's complement negate: (~hi, ~lo) + 1, carry into hi only
    when lo == 0."""
    nlo = (~lo) + jnp.uint64(1)
    nhi = (~hi) + jnp.where(lo == 0, jnp.int64(1), jnp.int64(0))
    return nhi, nlo


def add(ahi, alo, bhi, blo):
    lo = alo + blo
    carry = (lo < alo).astype(jnp.int64)
    return ahi + bhi + carry, lo


def is_negative(hi, lo):
    return hi < 0


def abs128(hi, lo):
    nhi, nlo = neg(hi, lo)
    n = is_negative(hi, lo)
    return jnp.where(n, nhi, hi), jnp.where(n, nlo, lo)


def mul_i64(a, b):
    """Exact signed 64x64 -> 128 multiply via 32-bit limbs."""
    sign = (a < 0) ^ (b < 0)
    ua = jnp.where(a < 0, -a, a).view(jnp.uint64)
    ub = jnp.where(b < 0, -b, b).view(jnp.uint64)
    a0 = ua & _U32
    a1 = ua >> _32
    b0 = ub & _U32
    b1 = ub >> _32
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> _32) + (p01 & _U32) + (p10 & _U32)
    lo = (p00 & _U32) | ((mid & _U32) << _32)
    hi_u = p11 + (p01 >> _32) + (p10 >> _32) + (mid >> _32)
    hi = hi_u.view(jnp.int64)
    nhi, nlo = neg(hi, lo)
    return jnp.where(sign, nhi, hi), jnp.where(sign, nlo, lo)


def mul_small(hi, lo, m: int):
    """(hi, lo) * m for 0 < m < 2^31 (sign carried by hi).  Exact as
    long as the true product fits 128 bits."""
    mu = jnp.uint64(m)
    neg_in = is_negative(hi, lo)
    ah, al = abs128(hi, lo)
    l0 = (al & _U32) * mu
    l1 = (al >> _32) * mu
    lo_out = (l0 & _U32) | ((((l0 >> _32) + (l1 & _U32)) & _U32) << _32)
    carry = ((l0 >> _32) + (l1 & _U32)) >> _32
    hi_u = ah.view(jnp.uint64) * mu + (l1 >> _32) + carry
    hi_out = hi_u.view(jnp.int64)
    nh, nl = neg(hi_out, lo_out)
    return jnp.where(neg_in, nh, hi_out), jnp.where(neg_in, nl, lo_out)


def mul_pow10(hi, lo, k: int):
    """(hi, lo) * 10^k, k >= 0 (chunks of 10^9 keep each factor < 2^31)."""
    while k > 0:
        step = min(k, 9)
        hi, lo = mul_small(hi, lo, 10 ** step)
        k -= step
    return hi, lo


def _to_f64(hi, lo):
    """Approximate signed-128 -> float64.  Uses the exact identity
    v = (hi + carry)*2^64 + lo_signed  (carry = lo >= 2^63,
    lo_signed = lo - carry*2^64): naive hi*2^64 + lo catastrophically
    cancels for small negative values (hi=-1, lo≈2^64)."""
    carry = (lo >> jnp.uint64(63)).view(jnp.int64)
    lo_signed = lo.view(jnp.int64)
    return (hi + carry).astype(jnp.float64) * 18446744073709551616.0 + lo_signed.astype(jnp.float64)


def div_round_half_up(hi, lo, den):
    """round_half_up((hi,lo) / den) -> (q: int64, ok: bool).

    ``den`` int64, elementwise, den != 0 (caller masks zeros).  HALF_UP
    = away from zero, Spark decimal rounding.  Uses a float64 quotient
    estimate + exact int128 residual correction (each pass shrinks the
    error by ~2^52; two passes + a ±2 exact clamp make it exact for all
    |q| < 2^63).  ``ok`` is False where the true quotient overflows
    int64."""
    sign = is_negative(hi, lo) ^ (den < 0)
    nhi, nlo = abs128(hi, lo)
    uden = jnp.where(den < 0, -den, den)
    # HALF_UP = floor((|num| + |den|/2) / |den|) with sign applied
    # after; |den|>>1 is exact for even dens, and odd dens have no
    # exact-half boundary, so the floor truncation is always right
    half = uden.view(jnp.uint64) >> jnp.uint64(1)
    nhi, nlo = add(nhi, nlo, jnp.zeros_like(nhi), half)

    q = jnp.floor_divide(_to_f64(nhi, nlo), uden.astype(jnp.float64))
    q = jnp.clip(q, 0.0, 1.8446744073709552e19).astype(jnp.uint64)

    # two float-correction passes
    for _ in range(2):
        ph, pl = mul_u64(q, uden.view(jnp.uint64))
        rh, rl = sub(nhi, nlo, ph.view(jnp.int64), pl)
        adj = jnp.floor_divide(_to_f64(rh, rl), uden.astype(jnp.float64))
        adj = jnp.clip(adj, -9.2e18, 9.2e18).astype(jnp.int64)
        q = q + adj.view(jnp.uint64)
    # exact ±2 clamp
    for _ in range(2):
        ph, pl = mul_u64(q, uden.view(jnp.uint64))
        rh, rl = sub(nhi, nlo, ph.view(jnp.int64), pl)
        q = q - jnp.where(rh < 0, jnp.uint64(1), jnp.uint64(0))
    ph, pl = mul_u64(q, uden.view(jnp.uint64))
    rh, rl = sub(nhi, nlo, ph.view(jnp.int64), pl)
    too_big = (rh > 0) | ((rh == 0) & (rl >= uden.view(jnp.uint64)))
    q = q + jnp.where(too_big, jnp.uint64(1), jnp.uint64(0))

    # -2^63 is representable: magnitude 2^63 is ok when negative
    # (q.view(int64) is already -2^63 and -(-2^63) wraps back to it)
    ok = (q <= jnp.uint64(0x7FFFFFFFFFFFFFFF)) | (
        sign & (q == jnp.uint64(0x8000000000000000))
    )
    qi = q.view(jnp.int64)
    return jnp.where(sign, -qi, qi), ok


def mul_u64(a, b):
    """Unsigned 64x64 -> 128 (hi: uint64, lo: uint64)."""
    a0 = a & _U32
    a1 = a >> _32
    b0 = b & _U32
    b1 = b >> _32
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = (p00 >> _32) + (p01 & _U32) + (p10 & _U32)
    lo = (p00 & _U32) | ((mid & _U32) << _32)
    hi = p11 + (p01 >> _32) + (p10 >> _32) + (mid >> _32)
    return hi, lo


def sub(ahi, alo, bhi, blo):
    nbh, nbl = neg(bhi, blo)
    return add(ahi, alo, nbh, nbl)


def rescale_down(hi, lo, k: int):
    """(hi, lo) / 10^k with HALF_UP -> (q: int64, ok).  k >= 1."""
    # divide in <= 10^9 chunks? rounding must happen ONCE at full 10^k;
    # 10^k fits int64 for k <= 18 (rescales beyond 18 digits do not
    # occur: Spark result scales are bounded by 38 total digits)
    assert 1 <= k <= 18, k
    den = jnp.full(hi.shape, 10 ** k, jnp.int64)
    return div_round_half_up(hi, lo, den)
