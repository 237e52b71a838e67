"""Java ``Double.toString`` / ``Float.toString`` — vectorized Ryu on TPU.

Capability parity with the reference's device Ryu port (ftos_converter.cuh:
d2d :480, f2d :575, to_chars :797/:922, special strings :259; driver
cast_float_to_string.cu:34-128): the shortest decimal representation that
round-trips, formatted per the Java spec — plain notation in [1e-3, 1e7),
scientific ``d.dddE±x`` otherwise, ``NaN`` / ``Infinity`` / ``-0.0`` specials.

The reference runs scalar Ryu per GPU thread.  Here every step is lane
arithmetic over the whole column:

- 128-bit multiplies decompose into 32-bit limb products in uint64 lanes
  (_umul128), with per-lane variable shifts;
- the power-of-5 tables are exact-precomputed host arrays (utils.ryu_tables)
  gathered per element;
- Ryu's shortest-search loop has a bounded trip count (<= 22 digit removals),
  so it unrolls into masked iterations;
- character emission is a batch scatter of (row, position) pairs into a padded
  byte matrix, rebuilt into an Arrow StringColumn.

Round 20 layers the get_json_object playbook on top (the BENCH_r09
0.08 Mrows/s straggler):

- **value-class buckets** (columnar/buckets.class_buckets): specials
  (NaN/Inf/±0) skip Ryu entirely, "simple" doubles — exact integers in
  [1, 1e7), the overwhelming majority of real data — take a 6-step
  trailing-zero strip instead of the 22-iteration shortest-search, and
  only the full-Ryu residue pays the 128-bit limb machinery;
- **strength-reduced emission** (_emit_fast): ONE take_along_axis digit
  gather + two grouped scatters replace the ~85 per-position put()
  scatters of the oracle `_emit`;
- **backend-adaptive dispatch** (`float_device_render="auto"`, the
  json_device_render pattern): XLA:CPU routes to ``# twin:``-pinned
  numpy renderers with branch/active-set compaction the lockstep
  compiled path cannot do.

Every fast path is bit-identical to the monolithic device oracle
(``float_bucketed=False`` + ``float_device_render=True``), which stays
the Spark-parity reference; tests/test_float_to_string.py fuzzes all
three arms against each other and the Java layout oracle.

FLOAT64 input is the int64 bit-pattern convention (columnar.column) — exactly
what Ryu wants: the algorithm never touches float arithmetic.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.columnar.buckets import class_buckets, map_classes
from spark_rapids_jni_tpu.columnar.column import (
    Column,
    StringColumn,
    next_pow2,
    strings_from_padded,
)
from spark_rapids_jni_tpu.columnar.dtypes import Kind
from spark_rapids_jni_tpu.utils.floatbits import f32_to_bits
from spark_rapids_jni_tpu.utils import ryu_tables as rt

_U64 = jnp.uint64
_U32 = jnp.uint32
_I32 = jnp.int32
_M32 = jnp.uint64(0xFFFFFFFF)

MAX_D2S_LEN = 24  # sign + 17 digits + '.' + pad0 + 'E' + '-' + 3 exp digits

_POW10_U64 = jnp.asarray(np.array([10**k for k in range(20)], dtype=np.uint64))

_POW10_NP = np.array([10**k for k in range(20)], dtype=np.uint64)
_POW5_NP = np.array([5**k for k in range(24)], dtype=np.uint64)

# value classes (class_buckets ids): specials render from a 5-row table,
# simple integers take the strip loop, the residue pays full Ryu.
CLS_SPECIAL = 0
CLS_SIMPLE = 1
CLS_RYU = 2


def _u64(x):
    return jnp.asarray(x, dtype=jnp.uint64)


def digit_table_u64(v, maxd: int = 20) -> jnp.ndarray:
    """``[n, maxd]`` uint8 decimal digits of u64 ``v``, index k = digit from
    the RIGHT (ones digit at k=0), zero-padded above the value's length.

    Built by a divide-by-constant-10 chain, rolled into one ``lax.scan``
    step: each step is an emulated-u64 division, and maxd unrolled copies
    cost the TPU compiler over a minute per renderer.  Renderers then
    *gather* from the table per output position — replacing per-grid-cell
    ``v // 10^k`` with a variable k, whose emulated-u64 general division
    dominated TPU compile time on the string-rendering ops."""
    ten = _U64(10)

    def step(v, _):
        return v // ten, (v % ten).astype(jnp.uint8)

    _, cols = jax.lax.scan(step, v, None, length=maxd)  # [maxd, ...]
    return jnp.moveaxis(cols, 0, -1)


def digit_from_table(tab: jnp.ndarray, k) -> jnp.ndarray:
    """ASCII digit chars gathered at (broadcast) right-index ``k``; out-of-
    range k clamps (callers mask those positions anyway)."""
    maxd = tab.shape[-1]
    kc = jnp.clip(k, 0, maxd - 1)
    if kc.ndim == tab.ndim - 1:
        kc = kc[..., None]
        return jnp.take_along_axis(tab, kc, axis=-1)[..., 0] + jnp.uint8(
            ord("0"))
    return jnp.take_along_axis(tab, kc, axis=-1) + jnp.uint8(ord("0"))


def _umul128(a, b):
    """(hi, lo) of the full 128-bit product of two u64 lane arrays."""
    a_lo, a_hi = a & _M32, a >> _U64(32)
    b_lo, b_hi = b & _M32, b >> _U64(32)
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = (ll >> _U64(32)) + (lh & _M32) + (hl & _M32)
    lo = (ll & _M32) | ((mid & _M32) << _U64(32))
    hi = hh + (lh >> _U64(32)) + (hl >> _U64(32)) + (mid >> _U64(32))
    return hi, lo


def _shiftright128(lo, hi, dist):
    """(hi:lo) >> dist for per-lane dist in (0, 64)."""
    dist = dist.astype(jnp.uint64)
    return (hi << (_U64(64) - dist)) | (lo >> dist)


def _mul_shift64(m, mul_lo, mul_hi, j):
    """Ryu mulShift64 (ftos_converter.cuh:375): ((m * mul) >> j) low 64."""
    hi1, lo1 = _umul128(m, mul_hi)
    hi0, _lo0 = _umul128(m, mul_lo)
    s = hi0 + lo1
    hi1 = hi1 + (s < hi0).astype(jnp.uint64)  # carry
    return _shiftright128(s, hi1, j - 64)


def _pow5bits(e):
    return ((e * _I32(1217359)) >> 19) + _I32(1)


def _log10_pow2(e):
    return (e * _I32(78913)) >> 18


def _log10_pow5(e):
    return (e * _I32(732923)) >> 20


def _multiple_of_pow5(value, q):
    """value % 5^q == 0 for q in [0, 23] lanes: q rolled divide-by-5 steps.
    A constant divisor strength-reduces to a multiply-high; the variable
    5^q divisor compiled on the TPU as a general emulated u64 division."""
    q = jnp.clip(q, 0, 23)

    def step(i, carry):
        v, ok = carry
        ok = ok & ((i >= q) | (v % _U64(5) == 0))
        return v // _U64(5), ok

    _, ok = jax.lax.fori_loop(
        0, 23, step, (value, jnp.ones(value.shape, jnp.bool_)))
    return ok


def _multiple_of_pow2(value, q):
    mask = (_U64(1) << jnp.clip(q, 0, 63).astype(jnp.uint64)) - _U64(1)
    return (value & mask) == 0


def _decimal_length_u64(v, max_digits):
    """number of decimal digits of v (>= 1)."""
    n = jnp.ones(v.shape, _I32)
    for k in range(1, max_digits):
        n = n + (v >= _POW10_U64[k]).astype(_I32)
    return n


# twin: f2s_d2d
def _d2d(bits):
    """Vectorized Ryu d2d (ftos_converter.cuh:480): bit patterns ->
    (mantissa u64, exponent i32) of the shortest decimal."""
    u = bits.astype(jnp.uint64)
    ieee_mantissa = u & _U64((1 << 52) - 1)
    ieee_exponent = ((u >> _U64(52)) & _U64(0x7FF)).astype(_I32)

    denormal = ieee_exponent == 0
    e2 = jnp.where(denormal, _I32(1 - 1023 - 52 - 2), ieee_exponent - (1023 + 52 + 2))
    m2 = jnp.where(denormal, ieee_mantissa, ieee_mantissa | _U64(1 << 52))
    even = (m2 & _U64(1)) == 0
    accept_bounds = even

    mv = _U64(4) * m2
    mm_shift = ((ieee_mantissa != 0) | (ieee_exponent <= 1)).astype(jnp.uint64)

    # --- branch A: e2 >= 0 (inverse powers of 5) ---
    qa = jnp.maximum(_log10_pow2(e2) - (e2 > 3).astype(_I32), 0)
    ka = _I32(rt.DOUBLE_POW5_INV_BITCOUNT) + _pow5bits(qa) - 1
    ja = -e2 + qa + ka  # shift argument
    qa_c = jnp.clip(qa, 0, len(rt.DOUBLE_POW5_INV_SPLIT_LO) - 1)
    inv_lo = jnp.asarray(rt.DOUBLE_POW5_INV_SPLIT_LO)[qa_c]
    inv_hi = jnp.asarray(rt.DOUBLE_POW5_INV_SPLIT_HI)[qa_c]
    vr_a = _mul_shift64(mv, inv_lo, inv_hi, ja)
    vp_a = _mul_shift64(mv + _U64(2), inv_lo, inv_hi, ja)
    vm_a = _mul_shift64(mv - _U64(1) - mm_shift, inv_lo, inv_hi, ja)
    e10_a = qa
    # trailing-zero flags (q <= 21 guard)
    guard_a = qa <= 21
    mv_mod5 = mv % _U64(5) == 0
    vr_tz_a = guard_a & mv_mod5 & _multiple_of_pow5(mv, qa)
    vm_tz_a = guard_a & ~mv_mod5 & accept_bounds & _multiple_of_pow5(
        mv - _U64(1) - mm_shift, qa
    )
    vp_a = vp_a - (
        guard_a & ~mv_mod5 & ~accept_bounds & _multiple_of_pow5(mv + _U64(2), qa)
    ).astype(jnp.uint64)

    # --- branch B: e2 < 0 (powers of 5) ---
    neg_e2 = -e2
    qb = jnp.maximum(_log10_pow5(neg_e2) - (neg_e2 > 1).astype(_I32), 0)
    ib = neg_e2 - qb
    kb = _pow5bits(ib) - _I32(rt.DOUBLE_POW5_BITCOUNT)
    jb = qb - kb
    ib_c = jnp.clip(ib, 0, len(rt.DOUBLE_POW5_SPLIT_LO) - 1)
    pw_lo = jnp.asarray(rt.DOUBLE_POW5_SPLIT_LO)[ib_c]
    pw_hi = jnp.asarray(rt.DOUBLE_POW5_SPLIT_HI)[ib_c]
    vr_b = _mul_shift64(mv, pw_lo, pw_hi, jb)
    vp_b = _mul_shift64(mv + _U64(2), pw_lo, pw_hi, jb)
    vm_b = _mul_shift64(mv - _U64(1) - mm_shift, pw_lo, pw_hi, jb)
    e10_b = qb + e2
    q_le1 = qb <= 1
    vr_tz_b = q_le1 | ((qb < 63) & _multiple_of_pow2(mv, qb))
    vm_tz_b = q_le1 & (mm_shift == 1)
    vp_b = vp_b - (q_le1 & ~accept_bounds).astype(jnp.uint64)

    pos = e2 >= 0
    vr = jnp.where(pos, vr_a, vr_b)
    vp = jnp.where(pos, vp_a, vp_b)
    vm = jnp.where(pos, vm_a, vm_b)
    e10 = jnp.where(pos, e10_a, e10_b)
    vm_tz = jnp.where(pos, vm_tz_a, vm_tz_b)
    vr_tz = jnp.where(pos, vr_tz_a, vr_tz_b)

    return _shortest_loop(vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, 22)


# twin: f2s_f2d
def _f2d(bits):
    """Vectorized Ryu f2d (ftos_converter.cuh:575) in u64 lanes."""
    u = bits.astype(jnp.uint64) & _U64(0xFFFFFFFF)
    ieee_mantissa = u & _U64((1 << 23) - 1)
    ieee_exponent = ((u >> _U64(23)) & _U64(0xFF)).astype(_I32)

    denormal = ieee_exponent == 0
    e2 = jnp.where(denormal, _I32(1 - 127 - 23 - 2), ieee_exponent - (127 + 23 + 2))
    m2 = jnp.where(denormal, ieee_mantissa, ieee_mantissa | _U64(1 << 23))
    even = (m2 & _U64(1)) == 0
    accept_bounds = even

    mv = _U64(4) * m2
    mp = mv + _U64(2)
    mm_shift = ((ieee_mantissa != 0) | (ieee_exponent <= 1)).astype(jnp.uint64)
    mm = mv - _U64(1) - mm_shift

    inv_tab = jnp.asarray(rt.FLOAT_POW5_INV_SPLIT)
    pow_tab = jnp.asarray(rt.FLOAT_POW5_SPLIT)

    def mul_pow5_inv_div_pow2(m, q, j):
        factor = inv_tab[jnp.clip(q, 0, len(rt.FLOAT_POW5_INV_SPLIT) - 1)]
        return _mul_shift32(m, factor, j)

    def mul_pow5_div_pow2(m, i, j):
        factor = pow_tab[jnp.clip(i, 0, len(rt.FLOAT_POW5_SPLIT) - 1)]
        return _mul_shift32(m, factor, j)

    # branch A: e2 >= 0
    qa = jnp.maximum(_log10_pow2(e2), 0)
    ka = _I32(rt.FLOAT_POW5_INV_BITCOUNT) + _pow5bits(qa) - 1
    ja = -e2 + qa + ka
    vr_a = mul_pow5_inv_div_pow2(mv, qa, ja)
    vp_a = mul_pow5_inv_div_pow2(mp, qa, ja)
    vm_a = mul_pow5_inv_div_pow2(mm, qa, ja)
    e10_a = qa
    la = _I32(rt.FLOAT_POW5_INV_BITCOUNT) + _pow5bits(jnp.maximum(qa - 1, 0)) - 1
    lrd_a = jnp.where(
        (qa != 0) & ((vp_a - _U64(1)) // _U64(10) <= vm_a // _U64(10)),
        mul_pow5_inv_div_pow2(mv, jnp.maximum(qa - 1, 0), -e2 + qa - 1 + la)
        % _U64(10),
        _U64(0),
    )
    guard_a = qa <= 9
    mv_mod5 = mv % _U64(5) == 0
    vr_tz_a = guard_a & mv_mod5 & _multiple_of_pow5(mv, qa)
    vm_tz_a = guard_a & ~mv_mod5 & accept_bounds & _multiple_of_pow5(mm, qa)
    vp_a = vp_a - (
        guard_a & ~mv_mod5 & ~accept_bounds & _multiple_of_pow5(mp, qa)
    ).astype(jnp.uint64)

    # branch B: e2 < 0
    neg_e2 = -e2
    qb = jnp.maximum(_log10_pow5(neg_e2), 0)
    ib = neg_e2 - qb
    kb = _pow5bits(ib) - _I32(rt.FLOAT_POW5_BITCOUNT)
    jb = qb - kb
    vr_b = mul_pow5_div_pow2(mv, ib, jb)
    vp_b = mul_pow5_div_pow2(mp, ib, jb)
    vm_b = mul_pow5_div_pow2(mm, ib, jb)
    e10_b = qb + e2
    jb2 = qb - 1 - (_pow5bits(ib + 1) - _I32(rt.FLOAT_POW5_BITCOUNT))
    lrd_b = jnp.where(
        (qb != 0) & ((vp_b - _U64(1)) // _U64(10) <= vm_b // _U64(10)),
        mul_pow5_div_pow2(mv, ib + 1, jb2) % _U64(10),
        _U64(0),
    )
    q_le1 = qb <= 1
    vr_tz_b = q_le1 | ((qb < 31) & _multiple_of_pow2(mv, jnp.maximum(qb - 1, 0)))
    vm_tz_b = q_le1 & (mm_shift == 1)
    vp_b = vp_b - (q_le1 & ~accept_bounds).astype(jnp.uint64)

    pos = e2 >= 0
    vr = jnp.where(pos, vr_a, vr_b)
    vp = jnp.where(pos, vp_a, vp_b)
    vm = jnp.where(pos, vm_a, vm_b)
    e10 = jnp.where(pos, e10_a, e10_b)
    vm_tz = jnp.where(pos, vm_tz_a, vm_tz_b)
    vr_tz = jnp.where(pos, vr_tz_a, vr_tz_b)
    lrd = jnp.where(pos, lrd_a, lrd_b)

    return _shortest_loop(
        vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, 11, last_removed=lrd
    )


def _mul_shift32(m, factor, shift):
    """Ryu mulShift32 (ftos_converter.cuh:242) in u64 lanes; shift > 32."""
    factor_lo = factor & _M32
    factor_hi = factor >> _U64(32)
    bits0 = m * factor_lo
    bits1 = m * factor_hi
    s = (bits0 >> _U64(32)) + bits1
    return s >> (shift.astype(jnp.uint64) - _U64(32))


# twin: f2s_shortest
def _shortest_loop(vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, max_iter,
                   last_removed=None):
    """Ryu step 4 (ftos_converter.cuh:570-650): masked unrolled digit removal.

    The reference's common-case div100 fast path is an optimization of the
    same recurrence; the general loop with correctly-initialized flags gives
    identical output for all lanes.
    """
    removed = jnp.zeros(vr.shape, _I32)
    lrd = jnp.zeros(vr.shape, jnp.uint64) if last_removed is None else last_removed

    # Rolled loops, not Python-unrolled ones: each step is several emulated
    # u64 divisions, and max_iter unrolled copies took the TPU compiler
    # ~6 minutes for the d2d kernel alone.
    def remove_common(_, c):
        vr, vp, vm, vm_tz, vr_tz, lrd, removed = c
        act = vp // _U64(10) > vm // _U64(10)
        vm_tz = jnp.where(act, vm_tz & (vm % _U64(10) == 0), vm_tz)
        vr_tz = jnp.where(act, vr_tz & (lrd == 0), vr_tz)
        lrd = jnp.where(act, vr % _U64(10), lrd)
        vr = jnp.where(act, vr // _U64(10), vr)
        vp = jnp.where(act, vp // _U64(10), vp)
        vm = jnp.where(act, vm // _U64(10), vm)
        return vr, vp, vm, vm_tz, vr_tz, lrd, removed + act.astype(_I32)

    def remove_trailing_zeros(_, c):
        vr, vp, vm, vm_tz, vr_tz, lrd, removed = c
        act = vm_tz & (vm % _U64(10) == 0)
        vr_tz = jnp.where(act, vr_tz & (lrd == 0), vr_tz)
        lrd = jnp.where(act, vr % _U64(10), lrd)
        vr = jnp.where(act, vr // _U64(10), vr)
        vp = jnp.where(act, vp // _U64(10), vp)
        vm = jnp.where(act, vm // _U64(10), vm)
        return vr, vp, vm, vm_tz, vr_tz, lrd, removed + act.astype(_I32)

    carry = (vr, vp, vm, vm_tz, vr_tz, lrd, removed)
    carry = jax.lax.fori_loop(0, max_iter, remove_common, carry)
    carry = jax.lax.fori_loop(0, max_iter, remove_trailing_zeros, carry)
    vr, vp, vm, vm_tz, vr_tz, lrd, removed = carry

    lrd = jnp.where(vr_tz & (lrd == 5) & (vr % _U64(2) == 0), _U64(4), lrd)
    round_up = ((vr == vm) & (~accept_bounds | ~vm_tz)) | (lrd >= 5)
    output = vr + round_up.astype(jnp.uint64)
    return output, e10 + removed


def _emit(output, exp10, negative, special_id, is_float):
    """Scatter the decimal into a padded byte matrix per Java formatting
    (to_chars, ftos_converter.cuh:797-893).

    The round-20 fast paths (_emit_fast / _emit_np) replace the ~85
    per-position scatters below with grouped emission; this body stays
    byte-for-byte the parity oracle they are fuzzed against."""
    n = output.shape[0]
    max_digits = 9 if is_float else 17
    olength = _decimal_length_u64(output, max_digits)
    exp = exp10 + olength - 1
    sci = (exp < -3) | (exp >= 7)
    s = negative.astype(_I32)

    out = jnp.full((n, MAX_D2S_LEN), 0, jnp.uint8)
    rows = jnp.arange(n, dtype=_I32)
    OOB = _I32(MAX_D2S_LEN)  # dropped by mode="drop"

    def put(pos, ch, mask):
        p = jnp.where(mask, pos, OOB)
        return lambda o: o.at[rows, p].set(ch, mode="drop")

    writes = []
    normal = special_id < 0

    # sign
    writes.append(put(jnp.zeros(n, _I32), jnp.uint8(ord("-")), normal & negative))

    # digits (MSB-first digit k = (output // 10^(olength-1-k)) % 10)
    plain_neg = normal & ~sci & (exp < 0)
    plain_big = normal & ~sci & (exp >= 0) & (exp + 1 >= olength)
    plain_mid = normal & ~sci & (exp >= 0) & (exp + 1 < olength)
    sci_m = normal & sci
    out_tab = digit_table_u64(output, max_digits)
    for k in range(max_digits):
        have = olength > k
        digit = digit_from_table(out_tab, olength - 1 - k)
        kk = _I32(k)
        writes.append(put(s + kk + (1 if k > 0 else 0), digit, sci_m & have))
        writes.append(put(s + 2 + (-exp - 1) + kk, digit, plain_neg & have))
        writes.append(put(s + kk, digit, plain_big & have))
        writes.append(put(s + kk + (kk > exp).astype(_I32), digit, plain_mid & have))

    dot = jnp.uint8(ord("."))
    zero_c = jnp.uint8(ord("0"))
    # scientific: '.', pad '0' when olength == 1, 'E', exp sign + digits
    writes.append(put(s + 1, dot, sci_m))
    writes.append(put(s + 2, zero_c, sci_m & (olength == 1)))
    p_e = s + olength + 1 + (olength == 1).astype(_I32)
    writes.append(put(p_e, jnp.uint8(ord("E")), sci_m))
    neg_e = exp < 0
    writes.append(put(p_e + 1, jnp.uint8(ord("-")), sci_m & neg_e))
    eabs = jnp.abs(exp)
    elen = 1 + (eabs >= 10).astype(_I32) + (eabs >= 100).astype(_I32)
    pe0 = p_e + 1 + neg_e.astype(_I32)
    # exponent digits MSB-first: digit j of the elen-digit number
    for j in range(3):
        have = elen > j
        p10 = jnp.asarray(np.array([1, 10, 100], np.int32))
        ed = ((eabs // p10[jnp.clip(elen - 1 - j, 0, 2)]) % 10).astype(
            jnp.uint8
        ) + zero_c
        writes.append(put(pe0 + j, ed, sci_m & have))

    # plain, exp < 0: "0." + (-exp-1) zeros + digits
    writes.append(put(s + 0, zero_c, plain_neg))
    writes.append(put(s + 1, dot, plain_neg))
    for t in range(2):  # exp >= -3 -> at most 2 leading zeros
        writes.append(put(s + 2 + t, zero_c, plain_neg & (-exp - 1 > t)))

    # plain, exp+1 >= olength: digits + zeros + ".0"
    for t in range(7):  # exp < 7 -> at most 7 trailing zeros
        writes.append(
            put(s + olength + t, zero_c, plain_big & (exp + 1 - olength > t))
        )
    writes.append(put(s + exp + 1, dot, plain_big))
    writes.append(put(s + exp + 2, zero_c, plain_big))

    # plain, dot between digits
    writes.append(put(s + exp + 1, dot, plain_mid))

    for w in writes:
        out = w(out)

    # lengths (d2s_size, ftos_converter.cuh:877-906)
    len_sci = s + olength + 1 + (olength == 1).astype(_I32) + 1 + neg_e.astype(_I32) + elen
    len_pn = s + 1 - exp + olength
    len_pb = s + exp + 3
    len_pm = s + olength + 1
    lens = jnp.where(
        sci, len_sci, jnp.where(exp < 0, len_pn, jnp.where(exp + 1 >= olength, len_pb, len_pm))
    )

    # specials: 0:"0.0" 1:"-0.0" 2:"Infinity" 3:"-Infinity" 4:"NaN"
    specials = ["0.0", "-0.0", "Infinity", "-Infinity", "NaN"]
    tab = np.zeros((5, MAX_D2S_LEN), np.uint8)
    slen = np.zeros(5, np.int32)
    for i, sp in enumerate(specials):
        b = sp.encode()
        tab[i, : len(b)] = np.frombuffer(b, np.uint8)
        slen[i] = len(b)
    sid = jnp.clip(special_id, 0, 4)
    out = jnp.where(normal[:, None], out, jnp.asarray(tab)[sid])
    lens = jnp.where(normal, lens, jnp.asarray(slen)[sid])
    return out, lens


# --------------------------------------------------------------------------
# round 20: value-class bucketing + strength-reduced emission fast paths
# --------------------------------------------------------------------------


def _special_table():
    """(chars[5, MAX_D2S_LEN] u8, lens[5] i32) of the special strings."""
    specials = ["0.0", "-0.0", "Infinity", "-Infinity", "NaN"]
    tab = np.zeros((5, MAX_D2S_LEN), np.uint8)
    slen = np.zeros(5, np.int32)
    for i, sp in enumerate(specials):
        b = sp.encode()
        tab[i, : len(b)] = np.frombuffer(b, np.uint8)
        slen[i] = len(b)
    return tab, slen


def _classify_np(bits: np.ndarray, special_id: np.ndarray,
                 is_float: bool) -> np.ndarray:
    """[n] int8 value classes from the host bit patterns.

    "simple" = an exact integer v in [1, 1e7): unbiased exponent E in
    [0, mbits] with all fractional mantissa bits zero and the shifted
    value under 10^7 (E <= mbits keeps the shift non-negative; any
    integer < 10^7 satisfies it since 10^7 < 2^24).  The Ryu interval
    around such a v is far narrower than 1 (ulp/2 <= 0.5 even at the
    float32 worst case), so the shortest round-trip decimal is v itself
    with trailing zeros stripped — proven bit-identical to the full-Ryu
    oracle by the fuzz corpora."""
    mbits = 23 if is_float else 52
    bias = 127 if is_float else 1023
    emask = 0xFF if is_float else 0x7FF
    mant = bits & np.uint64((1 << mbits) - 1)
    expo = ((bits >> np.uint64(mbits)) & np.uint64(emask)).astype(np.int32)
    E = expo - bias
    m2 = mant | np.uint64(1 << mbits)
    frac_bits = np.clip(mbits - E, 0, 63).astype(np.uint64)
    frac_mask = (np.uint64(1) << frac_bits) - np.uint64(1)
    v = m2 >> frac_bits
    simple = (
        (expo != 0)
        & (E >= 0)
        & (E <= mbits)
        & ((m2 & frac_mask) == 0)
        & (v < np.uint64(10**7))
    )
    return np.where(
        special_id >= 0, CLS_SPECIAL, np.where(simple, CLS_SIMPLE, CLS_RYU)
    ).astype(np.int8)


# twin: f2s_simple
def _simple_digits(bits, is_float):
    """Shortest digits of a 'simple' value — an exact integer v in
    [1, 1e7): strip trailing zeros (<= 6 for v < 10^7), no shortest-search
    needed (see _classify_np for the interval argument)."""
    mbits = 23 if is_float else 52
    bias = 127 if is_float else 1023
    emask = 0xFF if is_float else 0x7FF
    u = bits.astype(jnp.uint64)
    mant = u & jnp.uint64((1 << mbits) - 1)
    expo = ((u >> jnp.uint64(mbits)) & jnp.uint64(emask)).astype(jnp.int32)
    E = expo - bias
    m2 = mant | jnp.uint64(1 << mbits)
    v = m2 >> jnp.clip(mbits - E, 0, 63).astype(jnp.uint64)
    e10 = jnp.zeros(v.shape, jnp.int32)
    for _ in range(6):
        strip = (v > jnp.uint64(9)) & (v % jnp.uint64(10) == 0)
        v = jnp.where(strip, v // jnp.uint64(10), v)
        e10 = e10 + strip.astype(jnp.int32)
    return v, e10


# twin: f2s_simple
def _simple_digits_np(bits, is_float):
    """numpy twin of _simple_digits."""
    mbits = 23 if is_float else 52
    bias = 127 if is_float else 1023
    emask = 0xFF if is_float else 0x7FF
    u = bits.astype(np.uint64)
    mant = u & np.uint64((1 << mbits) - 1)
    expo = ((u >> np.uint64(mbits)) & np.uint64(emask)).astype(np.int32)
    E = expo - bias
    m2 = mant | np.uint64(1 << mbits)
    v = m2 >> np.clip(mbits - E, 0, 63).astype(np.uint64)
    e10 = np.zeros(v.shape, np.int32)
    for _ in range(6):
        strip = (v > np.uint64(9)) & (v % np.uint64(10) == 0)
        v = np.where(strip, v // np.uint64(10), v)
        e10 = e10 + strip.astype(np.int32)
    return v, e10


# twin: f2s_emit
def _emit_fast(output, exp10, negative, special_id, is_float):
    """Strength-reduced twin of the `_emit` oracle: one take_along_axis
    digit gather + two grouped scatters replace ~85 per-position put()
    scatters.  Layout classes, positions, and length formulas mirror
    d2s_size (ftos_converter.cuh:877-906) byte for byte."""
    n = output.shape[0]
    max_digits = 9 if is_float else 17
    olength = _decimal_length_u64(output, max_digits)
    exp = exp10 + olength - 1
    sci = (exp < -3) | (exp >= 7)
    s = negative.astype(_I32)
    normal = special_id < 0
    neg_e = exp < 0
    eabs = jnp.abs(exp)
    elen = 1 + (eabs >= 10).astype(_I32) + (eabs >= 100).astype(_I32)

    sci_m = normal & sci
    plain_neg = normal & ~sci & (exp < 0)
    plain_big = normal & ~sci & (exp >= 0) & (exp + 1 >= olength)
    plain_mid = normal & ~sci & (exp >= 0) & (exp + 1 < olength)

    # MSB-first digit characters: ONE gather from the div-10 chain table
    # (digit k from the left sits at right-index olength-1-k)
    karr = jnp.arange(max_digits, dtype=jnp.int32)[None, :]
    tab = digit_table_u64(output, max_digits)
    msb = jnp.clip(olength[:, None] - 1 - karr, 0, max_digits - 1)
    digits = jnp.take_along_axis(tab, msb, axis=1) + jnp.uint8(ord("0"))

    # per-layout digit positions, one [n, max_digits] matrix
    dpos = jnp.where(
        sci[:, None],
        s[:, None] + karr + (karr > 0).astype(jnp.int32),
        jnp.where(
            plain_neg[:, None],
            s[:, None] + 2 + (-exp[:, None] - 1) + karr,
            jnp.where(
                plain_big[:, None],
                s[:, None] + karr,
                s[:, None] + karr + (karr > exp[:, None]).astype(jnp.int32),
            ),
        ),
    )
    have = (karr < olength[:, None]) & normal[:, None]

    out = jnp.zeros((n, MAX_D2S_LEN), jnp.uint8)
    rows = jnp.arange(n, dtype=jnp.int32)
    OOB = _I32(MAX_D2S_LEN)
    out = out.at[rows[:, None], jnp.where(have, dpos, OOB)].set(
        digits, mode="drop"
    )

    # the ~19 per-class scalar characters, grouped into one scatter
    dot = jnp.uint8(ord("."))
    zero_c = jnp.uint8(ord("0"))
    p_e = s + olength + 1 + (olength == 1).astype(_I32)
    pe0 = p_e + 1 + neg_e.astype(_I32)
    p10 = jnp.asarray(np.array([1, 10, 100], np.int32))
    ps = []
    cs = []

    def sput(pos, ch, mask):
        ps.append(jnp.where(mask, pos, OOB))
        cs.append(jnp.broadcast_to(jnp.asarray(ch, jnp.uint8), pos.shape))

    sput(s * 0, jnp.uint8(ord("-")), normal & negative)
    sput(s + 1, dot, sci_m)
    sput(s + 2, zero_c, sci_m & (olength == 1))
    sput(p_e, jnp.uint8(ord("E")), sci_m)
    sput(p_e + 1, jnp.uint8(ord("-")), sci_m & neg_e)
    for j in range(3):
        ed = ((eabs // p10[jnp.clip(elen - 1 - j, 0, 2)]) % 10).astype(
            jnp.uint8
        ) + zero_c
        sput(pe0 + j, ed, sci_m & (elen > j))
    sput(s + 0, zero_c, plain_neg)
    sput(s + 1, dot, plain_neg)
    for t in range(2):
        sput(s + 2 + t, zero_c, plain_neg & (-exp - 1 > t))
    for t in range(7):
        sput(s + olength + t, zero_c, plain_big & (exp + 1 - olength > t))
    sput(s + exp + 1, dot, plain_big)
    sput(s + exp + 2, zero_c, plain_big)
    sput(s + exp + 1, dot, plain_mid)

    out = out.at[rows[:, None], jnp.stack(ps, axis=1)].set(
        jnp.stack(cs, axis=1), mode="drop"
    )

    len_sci = s + olength + 1 + (olength == 1).astype(_I32) + 1 + neg_e.astype(_I32) + elen
    len_pn = s + 1 - exp + olength
    len_pb = s + exp + 3
    len_pm = s + olength + 1
    lens = jnp.where(
        sci, len_sci, jnp.where(exp < 0, len_pn, jnp.where(exp + 1 >= olength, len_pb, len_pm))
    )

    tab_sp, slen_sp = _special_table()
    sid = jnp.clip(special_id, 0, 4)
    out = jnp.where(normal[:, None], out, jnp.asarray(tab_sp)[sid])
    lens = jnp.where(normal, lens, jnp.asarray(slen_sp)[sid])
    return out, lens


# twin: f2s_emit
def _emit_np(output, exp10, negative, special_id, is_float):
    """numpy twin of _emit_fast.

    The layout math (classes, exponent split, length formulas) is pinned
    line-for-line against the device twin; the character emission itself
    compacts rows per layout class and writes the digit run as contiguous
    column-slice copies (str(v) is left-aligned, so each layout is a few
    block moves plus a handful of masked scalar stores), where the
    lockstep device twin must scatter through position matrices."""
    n = output.shape[0]
    max_digits = 9 if is_float else 17
    olength = _decimal_length_np(output, max_digits)
    exp = exp10 + olength - 1
    sci = (exp < -3) | (exp >= 7)
    s = negative.astype(np.int32)
    normal = special_id < 0
    neg_e = exp < 0
    eabs = np.abs(exp)
    elen = 1 + (eabs >= 10).astype(np.int32) + (eabs >= 100).astype(np.int32)

    sci_m = normal & sci
    plain_neg = normal & ~sci & (exp < 0)
    plain_big = normal & ~sci & (exp >= 0) & (exp + 1 >= olength)
    plain_mid = normal & ~sci & (exp >= 0) & (exp + 1 < olength)

    # MSB-first digit codepoints, left-aligned: scale by 10^(max_digits -
    # olength) so the value is exactly max_digits wide (no overflow: output
    # has olength digits), then peel digits with divmod-by-10 over u32
    # halves — ~4x cheaper than per-row str() formatting (astype("U17")).
    # Columns past olength hold '0', not '\0'; every emit layout below
    # either overwrites them or leaves them past lens, and
    # _strings_from_padded_np extracts padded[j < lens] only.
    scaled = output.astype(np.uint64) * _POW10_NP[
        np.clip(max_digits - olength, 0, 19)]
    dcols = np.empty((max_digits, n), np.uint8)
    lo10 = (scaled % np.uint64(10**9)).astype(np.uint32)
    hi10 = (scaled // np.uint64(10**9)).astype(np.uint32)
    for j in range(min(9, max_digits)):
        lo10, r = np.divmod(lo10, np.uint32(10))
        dcols[max_digits - 1 - j] = r
    for j in range(max_digits - 9):
        hi10, r = np.divmod(hi10, np.uint32(10))
        dcols[max_digits - 10 - j] = r
    digits32 = dcols.T + np.uint8(ord("0"))

    p_e = s + olength + 1 + (olength == 1).astype(np.int32)
    pe0 = p_e + 1 + neg_e.astype(np.int32)
    p10 = np.array([1, 10, 100], np.int32)

    out = np.zeros((n, MAX_D2S_LEN), np.uint8)
    flat = out.reshape(-1)
    rowoff = np.arange(n, dtype=np.int64) * MAX_D2S_LEN
    DOT = np.uint8(ord("."))
    ZERO = np.uint8(ord("0"))

    ridx = np.nonzero(normal & negative)[0]
    if ridx.size:
        flat[rowoff[ridx]] = np.uint8(ord("-"))

    if sci_m.any():
        # d0 '.' d1..d_{ol-1} 'E' [-] exp -- digit run at fixed columns per
        # sign; trailing '\0's land past the E block and under lens
        for sgn in (0, 1):
            ridx = np.nonzero(sci_m & (s == sgn))[0]
            if not ridx.size:
                continue
            dsub = digits32[ridx]
            out[ridx, sgn] = dsub[:, 0]
            out[ridx, sgn + 1] = DOT
            out[ridx, sgn + 2:sgn + 1 + max_digits] = dsub[:, 1:]
        ridx = np.nonzero(sci_m)[0]
        base = rowoff[ridx]
        pad = ridx[olength[ridx] == 1]
        flat[rowoff[pad] + s[pad] + 2] = ZERO
        flat[base + p_e[ridx]] = np.uint8(ord("E"))
        rneg = ridx[neg_e[ridx]]
        flat[rowoff[rneg] + p_e[rneg] + 1] = np.uint8(ord("-"))
        eb = eabs[ridx]
        el = elen[ridx]
        p0 = pe0[ridx] + base
        for j in range(3):
            rj = np.nonzero(el > j)[0]
            if rj.size:
                edc = (
                    (eb[rj] // p10[np.clip(el[rj] - 1 - j, 0, 2)]) % 10
                ).astype(np.uint8) + ZERO
                flat[p0[rj] + j] = edc

    if plain_big.any():
        # digits, pad zeros to the ones place, then ".0"
        for sgn in (0, 1):
            ridx = np.nonzero(plain_big & (s == sgn))[0]
            if ridx.size:
                out[ridx, sgn:sgn + max_digits] = digits32[ridx]
        ridx = np.nonzero(plain_big)[0]
        base = rowoff[ridx]
        nz = exp[ridx] + 1 - olength[ridx]
        for t in range(7):  # exp < 7 -> at most 7 trailing zeros
            rz = np.nonzero(nz > t)[0]
            if rz.size:
                flat[base[rz] + s[ridx[rz]] + olength[ridx[rz]] + t] = ZERO
        flat[base + s[ridx] + exp[ridx] + 1] = DOT
        flat[base + s[ridx] + exp[ridx] + 2] = ZERO

    if plain_mid.any():
        # dot inside the digit run: exp in [0, 7), so two block moves per
        # (sign, exp) group
        for sgn in (0, 1):
            for e in range(7):
                ridx = np.nonzero(plain_mid & (s == sgn) & (exp == e))[0]
                if not ridx.size:
                    continue
                dsub = digits32[ridx]
                out[ridx, sgn:sgn + e + 1] = dsub[:, : e + 1]
                out[ridx, sgn + e + 1] = DOT
                out[ridx, sgn + e + 2:sgn + max_digits + 1] = dsub[:, e + 1:]

    if plain_neg.any():
        # "0." + up to 2 zeros + digits (exp in [-3, -1))
        for sgn in (0, 1):
            for e in (-1, -2, -3):
                ridx = np.nonzero(plain_neg & (s == sgn) & (exp == e))[0]
                if not ridx.size:
                    continue
                out[ridx, sgn] = ZERO
                out[ridx, sgn + 1] = DOT
                for t in range(-e - 1):
                    out[ridx, sgn + 2 + t] = ZERO
                z0 = sgn + 1 - e
                out[ridx, z0:z0 + max_digits] = digits32[ridx]

    len_sci = s + olength + 1 + (olength == 1).astype(np.int32) + 1 + neg_e.astype(np.int32) + elen
    len_pn = s + 1 - exp + olength
    len_pb = s + exp + 3
    len_pm = s + olength + 1
    lens = np.where(
        sci, len_sci, np.where(exp < 0, len_pn, np.where(exp + 1 >= olength, len_pb, len_pm))
    )

    tab_sp, slen_sp = _special_table()
    sid = np.clip(special_id, 0, 4)
    if not normal.all():
        out = np.where(normal[:, None], out, tab_sp[sid])
    lens = np.where(normal, lens, slen_sp[sid])
    return out, lens


# --------------------------------------------------------------------------
# numpy host Ryu twins (branch + active-set compaction the lockstep
# compiled path cannot do; helpers mirror the device ones 1:1)
# --------------------------------------------------------------------------


def _umul128_np(a, b):
    a_lo, a_hi = a & np.uint64(0xFFFFFFFF), a >> np.uint64(32)
    b_lo, b_hi = b & np.uint64(0xFFFFFFFF), b >> np.uint64(32)
    ll = a_lo * b_lo
    lh = a_lo * b_hi
    hl = a_hi * b_lo
    hh = a_hi * b_hi
    mid = (ll >> np.uint64(32)) + (lh & np.uint64(0xFFFFFFFF)) + (
        hl & np.uint64(0xFFFFFFFF))
    lo = (ll & np.uint64(0xFFFFFFFF)) | (
        (mid & np.uint64(0xFFFFFFFF)) << np.uint64(32))
    hi = hh + (lh >> np.uint64(32)) + (hl >> np.uint64(32)) + (
        mid >> np.uint64(32))
    return hi, lo


def _shiftright128_np(lo, hi, dist):
    dist = dist.astype(np.uint64)
    return (hi << (np.uint64(64) - dist)) | (lo >> dist)


def _shiftright128_safe_np(lo, hi, dist):
    """_shiftright128_np that also tolerates dist == 0 lanes (the halved-
    product shift in _mul_shift_all64_np can hit it)."""
    dist = dist.astype(np.uint64)
    lsh = np.where(dist == 0, np.uint64(1), np.uint64(64) - dist)
    return np.where(dist == 0, lo, (hi << lsh) | (lo >> dist))


def _mul_shift_all64_np(mv, mul_lo, mul_hi, j, mm_shift):
    """Upstream Ryu's mulShiftAll64: two umul128s instead of six.

    One exact 192-bit product of m = 2*m2 (mv/2) with the 128-bit pow5
    factor; the (mv, mv+2, mv-1-mmShift) products differ from it by
    +-factor, so they're derived additively and shifted by j-65 (the
    halving).  mmShift == 0 lanes (whole powers of two, rare) need the
    odd mv-1 multiplier: doubled product minus factor at shift j-64.
    Exact integer arithmetic throughout — bit-identical to three
    independent _mul_shift64_np calls."""
    m = mv >> np.uint64(1)  # 2*m2; mv = 4*m2 is always even
    hi0, lo = _umul128_np(m, mul_lo)
    hi1, lo1 = _umul128_np(m, mul_hi)
    mid = hi0 + lo1
    hi = hi1 + (mid < hi0).astype(np.uint64)  # carry
    d1 = j - 65
    vr = _shiftright128_safe_np(mid, hi, d1)
    lo2 = lo + mul_lo
    mid2 = mid + mul_hi + (lo2 < lo).astype(np.uint64)
    hi2 = hi + (mid2 < mid).astype(np.uint64)
    vp = _shiftright128_safe_np(mid2, hi2, d1)
    lo3 = lo - mul_lo
    mid3 = mid - mul_hi - (lo3 > lo).astype(np.uint64)
    hi3 = hi - (mid3 > mid).astype(np.uint64)
    vm = _shiftright128_safe_np(mid3, hi3, d1)
    z = np.nonzero(mm_shift == 0)[0]
    if z.size:
        lo3b = lo[z] + lo[z]
        mid3b = mid[z] + mid[z] + (lo3b < lo[z]).astype(np.uint64)
        hi3b = hi[z] + hi[z] + (mid3b < mid[z]).astype(np.uint64)
        lo4 = lo3b - mul_lo[z]
        mid4 = mid3b - mul_hi[z] - (lo4 > lo3b).astype(np.uint64)
        hi4 = hi3b - (mid4 > mid3b).astype(np.uint64)
        vm[z] = _shiftright128_np(mid4, hi4, j[z] - 64)
    return vr, vp, vm


def _mul_shift64_np(m, mul_lo, mul_hi, j):
    hi1, lo1 = _umul128_np(m, mul_hi)
    hi0, _lo0 = _umul128_np(m, mul_lo)
    s = hi0 + lo1
    hi1 = hi1 + (s < hi0).astype(np.uint64)  # carry
    return _shiftright128_np(s, hi1, j - 64)


def _mul_shift32_np(m, factor, shift):
    factor_lo = factor & np.uint64(0xFFFFFFFF)
    factor_hi = factor >> np.uint64(32)
    bits0 = m * factor_lo
    bits1 = m * factor_hi
    s = (bits0 >> np.uint64(32)) + bits1
    return s >> (shift.astype(np.uint64) - np.uint64(32))


def _pow5bits_np(e):
    return ((e * np.int32(1217359)) >> 19) + np.int32(1)


def _log10_pow2_np(e):
    return (e * np.int32(78913)) >> 18


def _log10_pow5_np(e):
    return (e * np.int32(732923)) >> 20


def _multiple_of_pow5_np(value, q):
    return value % _POW5_NP[np.clip(q, 0, 23)] == 0


def _multiple_of_pow2_np(value, q):
    mask = (np.uint64(1) << np.clip(q, 0, 63).astype(np.uint64)) - np.uint64(1)
    return (value & mask) == 0


def _decimal_length_np(v, max_digits):
    n = np.ones(v.shape, np.int32)
    for k in range(1, max_digits):
        n = n + (v >= _POW10_NP[k]).astype(np.int32)
    return n


def _d2d_pos_np(e2, mv, mm_shift, accept_bounds):
    """Branch A of _d2d (e2 >= 0, inverse powers of 5), compacted rows."""
    qa = np.maximum(_log10_pow2_np(e2) - (e2 > 3).astype(np.int32), 0)
    ka = np.int32(rt.DOUBLE_POW5_INV_BITCOUNT) + _pow5bits_np(qa) - 1
    ja = -e2 + qa + ka
    qa_c = np.clip(qa, 0, len(rt.DOUBLE_POW5_INV_SPLIT_LO) - 1)
    inv_lo = rt.DOUBLE_POW5_INV_SPLIT_LO[qa_c]
    inv_hi = rt.DOUBLE_POW5_INV_SPLIT_HI[qa_c]
    vr, vp, vm = _mul_shift_all64_np(mv, inv_lo, inv_hi, ja, mm_shift)
    # trailing-zero flags only exist under the q <= 21 guard; the u64
    # pow5 modulos run on those survivor rows alone
    vr_tz = np.zeros(mv.shape, np.bool_)
    vm_tz = np.zeros(mv.shape, np.bool_)
    gi = np.nonzero(qa <= 21)[0]
    if gi.size:
        mv_g = mv[gi]
        q_g = qa[gi]
        mod5_g = mv_g % np.uint64(5) == 0
        ab_g = accept_bounds[gi]
        vr_tz[gi] = mod5_g & _multiple_of_pow5_np(mv_g, q_g)
        vm_tz[gi] = ~mod5_g & ab_g & _multiple_of_pow5_np(
            mv_g - np.uint64(1) - mm_shift[gi], q_g
        )
        vp[gi] -= (
            ~mod5_g & ~ab_g & _multiple_of_pow5_np(mv_g + np.uint64(2), q_g)
        ).astype(np.uint64)
    return vr, vp, vm, qa, vm_tz, vr_tz


def _d2d_neg_np(e2, mv, mm_shift, accept_bounds):
    """Branch B of _d2d (e2 < 0, powers of 5), compacted rows."""
    neg_e2 = -e2
    qb = np.maximum(_log10_pow5_np(neg_e2) - (neg_e2 > 1).astype(np.int32), 0)
    ib = neg_e2 - qb
    kb = _pow5bits_np(ib) - np.int32(rt.DOUBLE_POW5_BITCOUNT)
    jb = qb - kb
    ib_c = np.clip(ib, 0, len(rt.DOUBLE_POW5_SPLIT_LO) - 1)
    pw_lo = rt.DOUBLE_POW5_SPLIT_LO[ib_c]
    pw_hi = rt.DOUBLE_POW5_SPLIT_HI[ib_c]
    vr, vp, vm = _mul_shift_all64_np(mv, pw_lo, pw_hi, jb, mm_shift)
    e10 = qb + e2
    q_le1 = qb <= 1
    vr_tz = q_le1 | ((qb < 63) & _multiple_of_pow2_np(mv, qb))
    vm_tz = q_le1 & (mm_shift == 1)
    vp = vp - (q_le1 & ~accept_bounds).astype(np.uint64)
    return vr, vp, vm, e10, vm_tz, vr_tz


# twin: f2s_d2d
def _d2d_np(bits):
    """numpy twin of _d2d with branch compaction: each power-of-5 branch
    (and its 128-bit limb multiplies) runs only on its survivor rows."""
    u = bits.astype(np.uint64)
    ieee_mantissa = u & np.uint64((1 << 52) - 1)
    ieee_exponent = ((u >> np.uint64(52)) & np.uint64(0x7FF)).astype(np.int32)

    denormal = ieee_exponent == 0
    e2 = np.where(denormal, np.int32(1 - 1023 - 52 - 2), ieee_exponent - (1023 + 52 + 2))
    m2 = np.where(denormal, ieee_mantissa, ieee_mantissa | np.uint64(1 << 52))
    even = (m2 & np.uint64(1)) == 0
    accept_bounds = even

    mv = np.uint64(4) * m2
    mm_shift = ((ieee_mantissa != 0) | (ieee_exponent <= 1)).astype(np.uint64)

    pos = e2 >= 0
    n = u.shape[0]
    vr = np.zeros(n, np.uint64)
    vp = np.zeros(n, np.uint64)
    vm = np.zeros(n, np.uint64)
    e10 = np.zeros(n, np.int32)
    vm_tz = np.zeros(n, np.bool_)
    vr_tz = np.zeros(n, np.bool_)
    for sel, branch in ((pos, _d2d_pos_np), (~pos, _d2d_neg_np)):
        idx = np.nonzero(sel)[0]
        if idx.size:
            (vr[idx], vp[idx], vm[idx], e10[idx], vm_tz[idx],
             vr_tz[idx]) = branch(
                e2[idx], mv[idx], mm_shift[idx], accept_bounds[idx])
    return _shortest_loop_np(vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, 22)


def _f2d_mul_inv_np(m, q, j):
    factor = rt.FLOAT_POW5_INV_SPLIT[
        np.clip(q, 0, len(rt.FLOAT_POW5_INV_SPLIT) - 1)]
    return _mul_shift32_np(m, factor, j)


def _f2d_mul_pow_np(m, i, j):
    factor = rt.FLOAT_POW5_SPLIT[np.clip(i, 0, len(rt.FLOAT_POW5_SPLIT) - 1)]
    return _mul_shift32_np(m, factor, j)


def _f2d_pos_np(e2, mv, mp, mm, mm_shift, accept_bounds):
    """Branch A of _f2d (e2 >= 0), compacted rows."""
    qa = np.maximum(_log10_pow2_np(e2), 0)
    ka = np.int32(rt.FLOAT_POW5_INV_BITCOUNT) + _pow5bits_np(qa) - 1
    ja = -e2 + qa + ka
    vr = _f2d_mul_inv_np(mv, qa, ja)
    vp = _f2d_mul_inv_np(mp, qa, ja)
    vm = _f2d_mul_inv_np(mm, qa, ja)
    la = np.int32(rt.FLOAT_POW5_INV_BITCOUNT) + _pow5bits_np(
        np.maximum(qa - 1, 0)) - 1
    lrd = np.where(
        (qa != 0) & ((vp - np.uint64(1)) // np.uint64(10) <= vm // np.uint64(10)),
        _f2d_mul_inv_np(mv, np.maximum(qa - 1, 0), -e2 + qa - 1 + la)
        % np.uint64(10),
        np.uint64(0),
    )
    guard = qa <= 9
    mv_mod5 = mv % np.uint64(5) == 0
    vr_tz = guard & mv_mod5 & _multiple_of_pow5_np(mv, qa)
    vm_tz = guard & ~mv_mod5 & accept_bounds & _multiple_of_pow5_np(mm, qa)
    vp = vp - (
        guard & ~mv_mod5 & ~accept_bounds & _multiple_of_pow5_np(mp, qa)
    ).astype(np.uint64)
    return vr, vp, vm, qa, vm_tz, vr_tz, lrd


def _f2d_neg_np(e2, mv, mp, mm, mm_shift, accept_bounds):
    """Branch B of _f2d (e2 < 0), compacted rows."""
    neg_e2 = -e2
    qb = np.maximum(_log10_pow5_np(neg_e2), 0)
    ib = neg_e2 - qb
    kb = _pow5bits_np(ib) - np.int32(rt.FLOAT_POW5_BITCOUNT)
    jb = qb - kb
    vr = _f2d_mul_pow_np(mv, ib, jb)
    vp = _f2d_mul_pow_np(mp, ib, jb)
    vm = _f2d_mul_pow_np(mm, ib, jb)
    e10 = qb + e2
    jb2 = qb - 1 - (_pow5bits_np(ib + 1) - np.int32(rt.FLOAT_POW5_BITCOUNT))
    lrd = np.where(
        (qb != 0) & ((vp - np.uint64(1)) // np.uint64(10) <= vm // np.uint64(10)),
        _f2d_mul_pow_np(mv, ib + 1, jb2) % np.uint64(10),
        np.uint64(0),
    )
    q_le1 = qb <= 1
    vr_tz = q_le1 | ((qb < 31) & _multiple_of_pow2_np(mv, np.maximum(qb - 1, 0)))
    vm_tz = q_le1 & (mm_shift == 1)
    vp = vp - (q_le1 & ~accept_bounds).astype(np.uint64)
    return vr, vp, vm, e10, vm_tz, vr_tz, lrd


# twin: f2s_f2d
def _f2d_np(bits):
    """numpy twin of _f2d with branch compaction."""
    u = bits.astype(np.uint64) & np.uint64(0xFFFFFFFF)
    ieee_mantissa = u & np.uint64((1 << 23) - 1)
    ieee_exponent = ((u >> np.uint64(23)) & np.uint64(0xFF)).astype(np.int32)

    denormal = ieee_exponent == 0
    e2 = np.where(denormal, np.int32(1 - 127 - 23 - 2), ieee_exponent - (127 + 23 + 2))
    m2 = np.where(denormal, ieee_mantissa, ieee_mantissa | np.uint64(1 << 23))
    even = (m2 & np.uint64(1)) == 0
    accept_bounds = even

    mv = np.uint64(4) * m2
    mp = mv + np.uint64(2)
    mm_shift = ((ieee_mantissa != 0) | (ieee_exponent <= 1)).astype(np.uint64)
    mm = mv - np.uint64(1) - mm_shift

    pos = e2 >= 0
    n = u.shape[0]
    vr = np.zeros(n, np.uint64)
    vp = np.zeros(n, np.uint64)
    vm = np.zeros(n, np.uint64)
    e10 = np.zeros(n, np.int32)
    vm_tz = np.zeros(n, np.bool_)
    vr_tz = np.zeros(n, np.bool_)
    lrd = np.zeros(n, np.uint64)
    for sel, branch in ((pos, _f2d_pos_np), (~pos, _f2d_neg_np)):
        idx = np.nonzero(sel)[0]
        if idx.size:
            (vr[idx], vp[idx], vm[idx], e10[idx], vm_tz[idx], vr_tz[idx],
             lrd[idx]) = branch(
                e2[idx], mv[idx], mp[idx], mm[idx], mm_shift[idx],
                accept_bounds[idx])
    return _shortest_loop_np(
        vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, 11, last_removed=lrd
    )


# twin: f2s_shortest
def _shortest_loop_np(vr, vp, vm, e10, vm_tz, vr_tz, accept_bounds, max_iter,
                      last_removed=None):
    """numpy twin of _shortest_loop with active-set compaction.

    A lane that fails the removal condition once never re-enters it (the
    divisions only apply to active lanes), so the survivor index set only
    shrinks — the compacted while-loop visits exactly the lanes the
    device's masked unroll would modify, in the same order."""
    vr, vp, vm = vr.copy(), vp.copy(), vm.copy()
    vm_tz, vr_tz = vm_tz.copy(), vr_tz.copy()
    removed = np.zeros(vr.shape, np.int32)
    lrd = np.zeros(vr.shape, np.uint64) if last_removed is None else last_removed.copy()

    ai = np.nonzero(vp // np.uint64(10) > vm // np.uint64(10))[0]
    it = 0
    while ai.size and it < max_iter:
        it += 1
        vm_tz[ai] &= vm[ai] % np.uint64(10) == 0
        vr_tz[ai] &= lrd[ai] == 0
        lrd[ai] = vr[ai] % np.uint64(10)
        vr[ai] //= np.uint64(10)
        vp[ai] //= np.uint64(10)
        vm[ai] //= np.uint64(10)
        removed[ai] += 1
        ai = ai[vp[ai] // np.uint64(10) > vm[ai] // np.uint64(10)]

    ai = np.nonzero(vm_tz & (vm % np.uint64(10) == 0))[0]
    it = 0
    while ai.size and it < max_iter:
        it += 1
        vr_tz[ai] &= lrd[ai] == 0
        lrd[ai] = vr[ai] % np.uint64(10)
        vr[ai] //= np.uint64(10)
        vp[ai] //= np.uint64(10)
        vm[ai] //= np.uint64(10)
        removed[ai] += 1
        ai = ai[vm[ai] % np.uint64(10) == 0]

    lrd = np.where(vr_tz & (lrd == 5) & (vr % np.uint64(2) == 0), np.uint64(4), lrd)
    round_up = ((vr == vm) & (~accept_bounds | ~vm_tz)) | (lrd >= 5)
    output = vr + round_up.astype(np.uint64)
    return output, e10 + removed


# --------------------------------------------------------------------------
# renderers + dispatch
# --------------------------------------------------------------------------

# governed-allocation seeds for the traced fast-path kernels (the
# _scan_padded_jit pattern): allocations inside materialize at launch.
_d2d_jit = jax.jit(_d2d)
_f2d_jit = jax.jit(_f2d)
_simple_digits_jit = jax.jit(_simple_digits, static_argnums=(1,))
_emit_fast_jit = jax.jit(_emit_fast, static_argnums=(4,))


# twin: f2s_render
def _render_device(bits, negative, special_id, cls, is_float):
    """Device value-class renderer: per-class compacted kernels scattered
    back through columnar/buckets.map_classes (pow2-padded row sets keep
    the compiled-shape universe bounded, exactly like length buckets)."""

    def kernel(cid, b_bits, b_neg, b_sid):
        if cid == CLS_SIMPLE:
            output, e10 = _simple_digits_jit(b_bits, is_float)
        elif cid == CLS_RYU:
            output, e10 = (_f2d_jit if is_float else _d2d_jit)(b_bits)
        else:  # specials never reach the digit path; emit masks them
            output, e10 = b_bits, b_sid * 0
        return _emit_fast_jit(output, e10, b_neg, b_sid, is_float)

    padded, lens = map_classes(
        cls, 3, kernel,
        [((MAX_D2S_LEN,), jnp.uint8), ((), jnp.int32)],
        row_args=[bits, negative, special_id],
    )
    return padded, lens


# twin: f2s_render
def _render_host(bits, negative, special_id, cls, is_float):
    """numpy twin of _render_device (no pow2 row padding: host kernels
    compact instead of compile)."""
    n = bits.shape[0]
    padded = np.zeros((n, MAX_D2S_LEN), np.uint8)
    lens = np.zeros(n, np.int32)
    buckets = class_buckets(cls, 3, round_rows=False)
    for cid, rows_np, n_valid in buckets:
        whole = len(buckets) == 1 and n_valid == n
        if whole:
            b_bits, b_neg, b_sid = bits, negative, special_id
        else:
            b_bits = bits[rows_np]
            b_neg = negative[rows_np]
            b_sid = special_id[rows_np]
        if cid == CLS_SIMPLE:
            output, e10 = _simple_digits_np(b_bits, is_float)
        elif cid == CLS_RYU:
            output, e10 = (_f2d_np if is_float else _d2d_np)(b_bits)
        else:
            output, e10 = b_bits, b_sid * 0
        p, l = _emit_np(output, e10, b_neg, b_sid, is_float)
        if whole:
            return p, l
        padded[rows_np] = p
        lens[rows_np] = l
    return padded, lens


def _strings_from_padded_np(padded, lens, validity):
    """Host mirror of columnar.column.strings_from_padded: identical
    offsets / pow2-cap chars layout, assembled in numpy and wrapped once
    (no per-piece device scatters on the host arm)."""
    lens = lens.astype(np.int32)
    offsets = np.concatenate(
        [np.zeros(1, np.int32), np.cumsum(lens, dtype=np.int32)]
    )
    total = int(offsets[-1])
    cap = next_pow2(total)
    chars = np.zeros(cap, np.uint8)
    w = padded.shape[1]
    mask = np.arange(w, dtype=np.int32)[None, :] < lens[:, None]
    # row-major boolean extraction IS the concatenation of each row's
    # first len bytes — no offset index matrix needed
    chars[:total] = padded[mask]
    return StringColumn(jnp.asarray(chars), jnp.asarray(offsets), validity)


def _device_render_enabled() -> bool:
    v = config.get("float_device_render")
    if v == "auto":
        return jax.default_backend() != "cpu"
    return bool(v)


def _special_id_expr(is_nan, is_inf, is_zero, negative):
    """0:"0.0" 1:"-0.0" 2:"Infinity" 3:"-Infinity" 4:"NaN"; -1 normal."""
    return jnp.where(
        is_nan,
        _I32(4),
        jnp.where(
            is_inf,
            jnp.where(negative, _I32(3), _I32(2)),
            jnp.where(is_zero, jnp.where(negative, _I32(1), _I32(0)), _I32(-1)),
        ),
    )


def _float_to_string_device(col: Column) -> StringColumn:
    """Device arm: value-class bucketed fast path, or (float_bucketed off)
    the monolithic whole-column oracle."""
    if col.dtype.kind == Kind.FLOAT64:
        bits = col.data.astype(jnp.int64).astype(jnp.uint64)
        negative = col.data.astype(jnp.int64) < 0
        mant = bits & _U64((1 << 52) - 1)
        expo = (bits >> _U64(52)) & _U64(0x7FF)
        is_nan = (expo == 0x7FF) & (mant != 0)
        is_inf = (expo == 0x7FF) & (mant == 0)
        is_zero = (expo == 0) & (mant == 0)
        is_float = False
    else:
        bits32 = f32_to_bits(col.data)
        bits = bits32.astype(jnp.uint64) & _M32
        negative = bits32 < 0
        mant = bits & _U64((1 << 23) - 1)
        expo = (bits >> _U64(23)) & _U64(0xFF)
        is_nan = (expo == 0xFF) & (mant != 0)
        is_inf = (expo == 0xFF) & (mant == 0)
        is_zero = (expo == 0) & (mant == 0)
        is_float = True

    special_id = _special_id_expr(is_nan, is_inf, is_zero, negative)

    if not config.get("float_bucketed"):
        # monolithic oracle: every row pays full Ryu + per-position emission
        output, e10 = (_f2d if is_float else _d2d)(bits)
        padded, lens = _emit(output, e10, negative, special_id, is_float)
        return strings_from_padded(padded, lens, col.validity)

    cls = _classify_np(
        np.asarray(bits), np.asarray(special_id), is_float
    )
    padded, lens = _render_device(bits, negative, special_id, cls, is_float)
    return strings_from_padded(padded, lens, col.validity)


def _float_to_string_host(col: Column) -> StringColumn:
    """Host-twin arm (XLA:CPU): classify + render entirely in numpy."""
    is_float = col.dtype.kind == Kind.FLOAT32
    if is_float:
        bits32 = np.asarray(col.data).view(np.int32)
        bits = bits32.astype(np.uint64) & np.uint64(0xFFFFFFFF)
        negative = bits32 < 0
        mant = bits & np.uint64((1 << 23) - 1)
        expo = (bits >> np.uint64(23)) & np.uint64(0xFF)
        is_nan = (expo == 0xFF) & (mant != 0)
        is_inf = (expo == 0xFF) & (mant == 0)
        is_zero = (expo == 0) & (mant == 0)
    else:
        data = np.asarray(col.data)  # int64 IEEE bit patterns
        bits = data.view(np.uint64)
        negative = data < 0
        mant = bits & np.uint64((1 << 52) - 1)
        expo = (bits >> np.uint64(52)) & np.uint64(0x7FF)
        is_nan = (expo == 0x7FF) & (mant != 0)
        is_inf = (expo == 0x7FF) & (mant == 0)
        is_zero = (expo == 0) & (mant == 0)
    special_id = np.where(
        is_nan,
        np.int32(4),
        np.where(
            is_inf,
            np.where(negative, np.int32(3), np.int32(2)),
            np.where(
                is_zero,
                np.where(negative, np.int32(1), np.int32(0)),
                np.int32(-1),
            ),
        ),
    )
    cls = _classify_np(bits, special_id, is_float)
    padded, lens = _render_host(bits, negative, special_id, cls, is_float)
    return _strings_from_padded_np(padded, lens, col.validity)


def float_to_string(col: Column) -> StringColumn:
    """Shortest round-trip decimal string of a FLOAT32/FLOAT64 column
    (spark_rapids_jni::float_to_string), backend-adaptive (round 20)."""
    if col.dtype.kind not in (Kind.FLOAT32, Kind.FLOAT64):
        raise TypeError("float_to_string requires FLOAT32 or FLOAT64")
    if _device_render_enabled():
        return _float_to_string_device(col)
    return _float_to_string_host(col)
