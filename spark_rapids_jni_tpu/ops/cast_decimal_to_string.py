"""Decimal -> string with Java ``BigDecimal.toString`` semantics.

Parity with the reference's decimal_to_non_ansi_string
(cast_decimal_to_string.cu:52-160): plain ``[-]integer.fraction`` when the
(cudf) scale <= 0 and the adjusted exponent >= -6, scientific
``d.dddE±x`` otherwise — including the ``0E-7`` edge for zero at scale -7.

Note on conventions: the reference takes cuDF scales (negative = fraction
digits); this framework's DType carries Spark scales (positive = fraction
digits), so ``spark_scale = -cudf_scale`` throughout.

Vectorization: the single data-dependent division (split at 10^K, where K is
the per-row fraction width) runs through the 256-bit limb divider
(utils.int256) shared with the DECIMAL128 arithmetic ops; each output byte is
then rendered by grid arithmetic as in ops.format_float.  The reference's
zeros+digits fraction assembly collapses to "print the remainder zero-padded
to K digits", which is a pure digit gather.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar.column import (
    Column,
    Decimal128Column,
    StringColumn,
    strings_from_padded,
)
from spark_rapids_jni_tpu.columnar.dtypes import Kind
from spark_rapids_jni_tpu.utils import int256

from spark_rapids_jni_tpu.ops.float_to_string import (
    _decimal_length_u64,
    digit_from_table,
    digit_table_u64,
)

_U64 = jnp.uint64
_I32 = jnp.int32

MAX_LEN = 48  # sign + 39 digits + '.' + 'E' + sign + 3 exp digits

# 10^k for k in [0, 39] as (hi, lo) u64 pairs (10^39 > 2^127, clamp at 39)
_P10_HI = np.array([(10**k >> 64) & ((1 << 64) - 1) for k in range(40)], np.uint64)
_P10_LO = np.array([10**k & ((1 << 64) - 1) for k in range(40)], np.uint64)
_P10_SMALL = np.array([1, 10, 100], np.int32)  # exponent digit divisors


def _digits_1919(h19, l19):
    """decimal digit count of h19 * 10^19 + l19."""
    return jnp.where(
        h19 > 0, 19 + _decimal_length_u64(h19, 20), _decimal_length_u64(l19, 20)
    )


def _digit_table_1919(h19, l19) -> jnp.ndarray:
    """``[n, 39]`` uint8 digits (from the right) of h19 * 10^19 + l19.

    Two constant-divisor digit tables concatenated — replaces per-grid-cell
    u64 division with a variable power-of-10, which compiles slowly on the
    TPU (see float_to_string.digit_table_u64)."""
    return jnp.concatenate(
        [digit_table_u64(l19, 19), digit_table_u64(h19, 20)], axis=-1)


def _split_1919(hi, lo):
    """u128 (hi, lo) -> (h19, l19) with value = h19 * 10^19 + l19."""
    limbs = int256.from_i128(hi.astype(jnp.int64), lo)
    # analyze: ignore[governed-allocation] - decimal->string is not
    # yet wired into a governed pipeline (oracle/parity callers);
    # debt tracked at the site (round 16 baseline burn-down)
    q, r_hi, r_lo = int256.divide_unsigned(
        # analyze: ignore[governed-allocation] - same cast debt
        limbs, jnp.zeros_like(lo), jnp.full(lo.shape, 10**19, jnp.uint64)
    )
    q_lo = int256.to_i128(q)[1]  # quotient < 2^64 for |v| < 2^127
    return q_lo, r_lo


def decimal_to_string(col) -> StringColumn:
    """Convert DECIMAL32/64/128 to strings (decimal_to_non_ansi_string)."""
    if isinstance(col, Decimal128Column):
        hi = col.hi.astype(jnp.int64)
        lo = col.lo.astype(jnp.uint64)
        neg = hi < 0
        # |v| in u128
        nlo = (~lo) + _U64(1)
        nhi = (~hi.astype(_U64)) + (nlo == 0).astype(_U64)
        ahi = jnp.where(neg, nhi, hi.astype(_U64))
        alo = jnp.where(neg, nlo, lo)
        ss = col.dtype.scale
        validity = col.validity
        n = col.size
    elif isinstance(col, Column) and col.dtype.kind in (Kind.DECIMAL32, Kind.DECIMAL64):
        v = col.data.astype(jnp.int64)
        neg = v < 0
        alo = jnp.abs(v).astype(jnp.uint64)
        # analyze: ignore[governed-allocation] - same cast debt
        ahi = jnp.zeros_like(alo)
        ss = col.dtype.scale
        validity = col.validity
        n = col.size
    else:
        raise TypeError("decimal_to_string requires a decimal column")

    # digit count via u128 >= 10^k comparisons (no divider needed)
    p10_hi = jnp.asarray(_P10_HI)
    p10_lo = jnp.asarray(_P10_LO)
    # analyze: ignore[governed-allocation] - same cast debt
    nd = jnp.ones(alo.shape, _I32)
    for k in range(1, 39):
        ge = (ahi > p10_hi[k]) | ((ahi == p10_hi[k]) & (alo >= p10_lo[k]))
        nd = nd + ge.astype(_I32)
    adj = _I32(-ss) + nd - 1  # adjusted exponent (cu:72)
    plain = (ss >= 0) & (adj >= -6)
    K = jnp.where(plain, _I32(ss), nd - 1)  # fraction width

    # split |v| at 10^K: integer part and zero-padded fraction
    # (reuse the tables uploaded above: eager callers pay each jnp.asarray
    # as a fresh host->device constant transfer — round 20 audit)
    limbs = int256.from_i128(ahi.astype(jnp.int64), alo)
    d_hi = p10_hi[jnp.clip(K, 0, 39)]
    d_lo = p10_lo[jnp.clip(K, 0, 39)]
    q, r_hi, r_lo = int256.divide_unsigned(limbs, d_hi, d_lo)
    q_hi, q_lo = int256.to_i128(q)
    ih19, il19 = _split_1919(q_hi.astype(_U64), q_lo)
    fh19, fl19 = _split_1919(r_hi, r_lo)

    il = _digits_1919(ih19, il19)  # integer digit count (>= 1, "0" incl.)
    s = neg.astype(_I32)
    has_dot = K > 0
    eabs = jnp.abs(adj)
    elen = 1 + (eabs >= 10).astype(_I32) + (eabs >= 100).astype(_I32)
    sci = ~plain
    lens = (
        s
        + il
        + has_dot.astype(_I32) * (1 + K)
        + sci.astype(_I32) * (2 + elen)
    )

    # ---- render [n, MAX_LEN] grid ----
    p = jnp.arange(MAX_LEN, dtype=_I32)[None, :]
    sC, ilC, KC = s[:, None], il[:, None], K[:, None]
    in_int = (p >= sC) & (p < sC + ilC)
    int_digit = digit_from_table(
        _digit_table_1919(ih19, il19), ilC - 1 - (p - sC))
    dot_pos = sC + ilC
    frac_t = p - (dot_pos + 1)
    in_frac = has_dot[:, None] & (frac_t >= 0) & (frac_t < KC)
    frac_digit = digit_from_table(
        _digit_table_1919(fh19, fl19), KC - 1 - frac_t)
    pE = dot_pos + jnp.where(has_dot, 1 + K, 0)[:, None]
    exp_t = p - (pE + 2)
    elenC = elen[:, None]
    p10_small = jnp.asarray(_P10_SMALL)
    exp_digit = (
        (eabs[:, None] // p10_small[jnp.clip(elenC - 1 - exp_t, 0, 2)]) % 10
    ).astype(jnp.uint8) + jnp.uint8(ord("0"))

    grid = jnp.where(
        (p == 0) & (sC == 1),
        jnp.uint8(ord("-")),
        jnp.where(
            in_int,
            int_digit,
            jnp.where(
                has_dot[:, None] & (p == dot_pos),
                jnp.uint8(ord(".")),
                jnp.where(
                    in_frac,
                    frac_digit,
                    jnp.where(
                        sci[:, None] & (p == pE),
                        jnp.uint8(ord("E")),
                        jnp.where(
                            sci[:, None] & (p == pE + 1),
                            jnp.where(
                                adj[:, None] < 0,
                                jnp.uint8(ord("-")),
                                jnp.uint8(ord("+")),
                            ),
                            jnp.where(
                                sci[:, None] & (exp_t >= 0) & (exp_t < elenC),
                                exp_digit,
                                jnp.uint8(0),
                            ),
                        ),
                    ),
                ),
            ),
        ),
    )
    return strings_from_padded(grid, lens, validity)
