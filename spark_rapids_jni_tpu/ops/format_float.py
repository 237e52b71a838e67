"""Spark ``format_number`` for float columns (``#,###,###.##`` layout).

Parity with the reference's format_float (format_float.cu:113; layout kernel
to_formatted_chars ftos_converter.cuh:1271-1383, round_half_even :1247,
specials copy_format_special_str :1413-1432): Ryu shortest digits, grouped
with commas, rounded half-even to a fixed number of fraction digits;
NaN -> U+FFFD, +-inf -> U+221E, zero keeps its sign ("-0.00000").

Vectorization: reuses the Ryu cores (_d2d/_f2d) for (mantissa, exponent),
then renders every output byte position with grid arithmetic over
``[rows, width]`` — each position computes its distance-from-the-right ``q``,
decides comma (q % 4 == 3) vs digit (q - q//4), and gathers the digit — so
the reference's per-thread reverse-writing loops become pure lane math with
no data-dependent control flow.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.columnar.column import (
    Column,
    StringColumn,
    strings_from_padded,
)
from spark_rapids_jni_tpu.columnar.dtypes import Kind
from spark_rapids_jni_tpu.ops.float_to_string import (
    _I32,
    _M32,
    _POW10_U64,
    _U64,
    _d2d,
    _decimal_length_u64,
    _f2d,
    digit_from_table,
    digit_table_u64,
)
from spark_rapids_jni_tpu.utils.floatbits import f32_to_bits


def _round_half_even(value, olength, digits):
    """round_half_even (ftos_converter.cuh:1247): keep ``digits`` leading
    decimal digits of ``value`` (which has ``olength`` digits)."""
    k = jnp.clip(olength - digits, 0, 19)
    div = _POW10_U64[k]
    mod = value % div
    num = value // div
    half = div // _U64(2)
    inc = (mod > half) | ((mod == half) & (num % _U64(2) == 1) & (mod != 0))
    return jnp.where(digits >= olength, value, num + inc.astype(jnp.uint64))


# (Per-row digit tables + gathers — digit_table_u64/digit_from_table in the
# import block above — replace the per-grid-cell u64 division with a variable
# power-of-10 divisor, whose emulated u64 division dominated TPU compile time.)


def format_float(col: Column, digits: int, width_hint: int = 0) -> StringColumn:
    """Format FLOAT32/FLOAT64 like Spark's ``format_number(col, digits)``.

    ``width_hint`` (optional) caps the integer-part digit count used to size
    the render grid — callers under ``jit`` (where the host peek below cannot
    run) can pass the largest expected decimal exponent + 2 to keep the
    compiled grid small.
    """
    if digits < 0:
        raise ValueError("digits must be >= 0")
    if col.dtype.kind == Kind.FLOAT64:
        bits = col.data.astype(jnp.int64).astype(jnp.uint64)
        negative = col.data.astype(jnp.int64) < 0
        mant_f = bits & _U64((1 << 52) - 1)
        expo_f = (bits >> _U64(52)) & _U64(0x7FF)
        is_nan = (expo_f == 0x7FF) & (mant_f != 0)
        is_inf = (expo_f == 0x7FF) & (mant_f == 0)
        is_zero = (expo_f == 0) & (mant_f == 0)
        output, e10 = _d2d(bits)
        max_exp = 309
    elif col.dtype.kind == Kind.FLOAT32:
        bits32 = f32_to_bits(col.data)
        bits = bits32.astype(jnp.uint64) & _M32
        negative = bits32 < 0
        mant_f = bits & _U64((1 << 23) - 1)
        expo_f = (bits >> _U64(23)) & _U64(0xFF)
        is_nan = (expo_f == 0xFF) & (mant_f != 0)
        is_inf = (expo_f == 0xFF) & (mant_f == 0)
        is_zero = (expo_f == 0) & (mant_f == 0)
        output, e10 = _f2d(bits)
        max_exp = 39
    else:
        raise TypeError("Values for format_float function must be a float type.")

    n = output.shape[0]
    # Bound the render width by the column's actual largest magnitude when the
    # data is concrete (host peek at the IEEE exponent field); under jit fall
    # back to the type's maximum.  decimal_digits <= floor(e2 * log10(2)) + 2.
    import jax.core as _core

    if width_hint > 0:
        max_exp = min(max_exp, width_hint)
    elif n > 0 and not isinstance(col.data, _core.Tracer):
        e2_max = int(np.max(np.asarray(expo_f).astype(np.int64)))
        bias = 1023 if col.dtype.kind == Kind.FLOAT64 else 127
        max_exp = max(2, min(max_exp, int((max(e2_max - bias, 1)) * 0.30103) + 3))
    width = 1 + max_exp + (max_exp - 1) // 3 + 1 + digits + 1
    olength = _decimal_length_u64(output, 17)
    exp = e10 + olength - 1
    s = negative.astype(_I32)
    D = _I32(digits)

    normal = ~(is_nan | is_inf | is_zero)
    b1 = normal & (exp < 0)
    b23 = normal & (exp >= 0)
    b2 = b23 & (exp + 1 >= olength)
    b3 = b23 & (exp + 1 < olength)

    # ---- branch 1: 0.xxx (ftos_converter.cuh:1280-1314) ----
    nz_full = -exp - 1  # zeros between '.' and the first value digit
    early = b1 & (D < nz_full)  # rounding window ends inside the zeros
    nz = jnp.minimum(nz_full, D)
    actual_round = jnp.maximum(D - nz, 0)
    aol1 = jnp.minimum(olength, actual_round)
    r1 = _round_half_even(output, olength, actual_round)
    # digits == 0 returns the bare '0' before any rounding (cuh:1284)
    carry1 = b1 & ~early & (D > 0) & (r1 >= _POW10_U64[jnp.clip(aol1, 0, 19)])
    r1 = jnp.where(carry1, r1 - _POW10_U64[jnp.clip(aol1, 0, 19)], r1)
    carrier_pos = jnp.where(nz > 0, s + 2 + nz - 1, s)

    # ---- branch 3 rounding (ftos_converter.cuh:1343-1357); the trailing
    # zeros after the temp_d fraction digits fall out of the in_frac grid ----
    temp_d = jnp.minimum(D, olength - exp - 1)
    r3 = _round_half_even(output, olength, exp + temp_d + 1)
    p10_td = _POW10_U64[jnp.clip(temp_d, 0, 19)]
    int3 = r3 // p10_td
    dec3 = r3 % p10_td
    il3 = _decimal_length_u64(int3, 19)

    # integer-section lengths (with commas)
    il2 = exp + 1  # digits in branch 2's integer (before commas)
    fl2 = il2 + exp // 3
    fl3 = il3 + (il3 - 1) // 3
    z2 = exp + 1 - olength  # trailing zeros appended to output in branch 2

    int_fl = jnp.where(b2, fl2, fl3)  # formatted integer length
    # total length per row (format_size :1386-1410 + specials)
    len_norm = jnp.where(
        b1,
        s + 2 + D,
        s + int_fl + 1 + D,
    )
    if digits == 0:
        len_norm = len_norm - 1
    lens = jnp.where(
        is_nan,
        _I32(3),
        jnp.where(
            is_inf,
            s + 3,
            jnp.where(is_zero, jnp.where(D > 0, s + 2 + D, s + 1), len_norm),
        ),
    )

    # ---- render the [n, width] grid ----
    p = jnp.arange(width, dtype=_I32)[None, :]
    sC = s[:, None]
    ZERO, ONE, DOT, COMMA, MINUS = (
        jnp.uint8(ord("0")),
        jnp.uint8(ord("1")),
        jnp.uint8(ord(".")),
        jnp.uint8(ord(",")),
        jnp.uint8(ord("-")),
    )
    # analyze: ignore[governed-allocation] - format_float is not yet
    # wired into a governed pipeline (oracle/test callers); debt tracked
    # at the site (round 16 baseline burn-down)
    out = jnp.zeros((n, width), jnp.uint8)
    tab_r1 = digit_table_u64(r1)
    tab_dec3 = digit_table_u64(dec3)

    # branch 1 grid
    in_zeros = (p >= sC + 2) & (p < sC + 2 + nz[:, None])
    j1 = p - (sC + 2 + nz[:, None])  # index into value digits (from left)
    in_val1 = (j1 >= 0) & (j1 < aol1[:, None])
    ch1 = jnp.where(
        p == sC,
        jnp.where(carry1[:, None] & (nz[:, None] == 0), ONE, ZERO),
        jnp.where(
            p == sC + 1,
            DOT,
            jnp.where(
                in_zeros,
                jnp.where(carry1[:, None] & (p == carrier_pos[:, None]), ONE, ZERO),
                jnp.where(
                    in_val1,
                    digit_from_table(tab_r1, aol1[:, None] - 1 - j1),
                    ZERO,  # trailing zeros
                ),
            ),
        ),
    )

    # branches 2/3 grid: integer section with commas, then '.', fraction
    tab_int = digit_table_u64(jnp.where(b2, output, int3))
    z = jnp.where(b2, z2, 0)[:, None]
    fl = int_fl[:, None]
    q = fl - 1 - (p - sC)  # distance from right within the integer section
    in_int = (p >= sC) & (q >= 0)
    is_comma = in_int & (q % 4 == 3)
    dr = q - q // 4  # digit index from the right
    int_digit = jnp.where(
        dr < z, ZERO, digit_from_table(tab_int, jnp.maximum(dr - z, 0))
    )
    frac_t = p - (sC + fl + 1)  # fraction digit index (0-based)
    in_frac = (frac_t >= 0) & (frac_t < D)
    # branch 2 fraction is all zeros; branch 3: temp_d digits then zeros
    frac_digit = jnp.where(
        b3[:, None] & (frac_t < temp_d[:, None]),
        digit_from_table(tab_dec3, temp_d[:, None] - 1 - frac_t),
        ZERO,
    )
    ch23 = jnp.where(
        is_comma,
        COMMA,
        jnp.where(
            in_int,
            int_digit,
            jnp.where(p == sC + fl, DOT, jnp.where(in_frac, frac_digit, ZERO)),
        ),
    )

    grid = jnp.where(b1[:, None], ch1, ch23)
    # sign for normal/inf/zero rows
    grid = jnp.where((p == 0) & (sC == 1), MINUS, grid)
    # zero rows: "0." + zeros (grid already ZERO beyond; set the dot)
    zero_m = is_zero[:, None]
    grid = jnp.where(zero_m & (p == sC), ZERO, grid)
    grid = jnp.where(zero_m & (p == sC + 1), DOT, grid)
    grid = jnp.where(zero_m & (p > sC + 1), ZERO, grid)
    # specials
    nan_bytes = jnp.asarray(np.frombuffer("�".encode(), np.uint8))
    inf_bytes = jnp.asarray(np.frombuffer("∞".encode(), np.uint8))
    for k in range(3):
        grid = jnp.where(is_nan[:, None] & (p == k), nan_bytes[k], grid)
        grid = jnp.where(is_inf[:, None] & (p == sC + k), inf_bytes[k], grid)

    return strings_from_padded(grid, lens, col.validity)
