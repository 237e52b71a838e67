"""Vectorized 256-bit integer arithmetic as 8x32-bit limb tensors.

The reference's DECIMAL128 math (decimal_utils.cu `chunked256`, multiply at
decimal_utils.cu:126, long division at :148, half-up rounding at :192) runs on
native 64/128-bit scalars per CUDA thread.  TPUs have neither int128 nor a
per-row scalar unit; here a 256-bit value is a little-endian tensor of eight
32-bit limbs, limb-major (``uint32[8, ...]``), so limb products fit exactly in
uint64 lanes and every operation is elementwise over the row axes, safe under
jit.  Limbs lead because a minor dimension of 8 pads to 128 lanes on the TPU:
row-major limbs made DECIMAL128 multiply at 2^22 rows need 18.4 GB of HBM.

Sign convention: two's complement over the full 256 bits (limb 7's top bit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NLIMBS = 8
_M32 = jnp.uint64(0xFFFFFFFF)
_U32 = jnp.uint32
_U64 = jnp.uint64


def const256(v: int) -> np.ndarray:
    """Python int -> (8,) uint32 little-endian two's-complement limbs."""
    v &= (1 << 256) - 1
    return np.array([(v >> (32 * i)) & 0xFFFFFFFF for i in range(NLIMBS)], dtype=np.uint32)


# 10**k for k in 0..76 (product of two decimal-38 values is < 10**76), the
# vectorized analog of the reference's generated pow_ten switch
# (decimal_utils.cu:246+).
POW10 = np.stack([const256(10**k) for k in range(77)])  # (77, 8) uint32


def from_i128(hi, lo):
    """Sign-extend (hi int64, lo uint64) into limbs[8, ...]."""
    hi = hi.astype(jnp.int64)
    lo = lo.astype(jnp.uint64)
    sign = jnp.where(hi < 0, _U32(0xFFFFFFFF), _U32(0))
    limbs = [
        (lo & _M32).astype(_U32),
        ((lo >> _U64(32)) & _M32).astype(_U32),
        (hi.astype(jnp.uint64) & _M32).astype(_U32),
        ((hi.astype(jnp.uint64) >> _U64(32)) & _M32).astype(_U32),
        sign,
        sign,
        sign,
        sign,
    ]
    return jnp.stack(limbs, axis=0)


def from_i64(x):
    """Sign-extend int64 into limbs[8, ...]."""
    x = x.astype(jnp.int64)
    hi = jnp.where(x < 0, jnp.int64(-1), jnp.int64(0))
    return from_i128(hi, x.astype(jnp.uint64))


def to_i128(limbs):
    """Truncate to the low 128 bits as (hi int64, lo uint64)."""
    l = limbs.astype(jnp.uint64)
    lo = l[0] | (l[1] << _U64(32))
    hi = (l[2] | (l[3] << _U64(32))).astype(jnp.int64)
    return hi, lo


def to_i64(limbs):
    """Truncate to the low 64 bits as signed int64 (reference as_64_bits)."""
    l = limbs.astype(jnp.uint64)
    return (l[0] | (l[1] << _U64(32))).astype(jnp.int64)


def is_negative(limbs):
    return (limbs[7] >> _U32(31)) != _U32(0)


def add(a, b):
    """256-bit add, carries rippled through uint64 lanes."""
    out = []
    carry = _U64(0)
    for i in range(NLIMBS):
        s = a[i].astype(_U64) + b[i].astype(_U64) + carry
        out.append((s & _M32).astype(_U32))
        carry = s >> _U64(32)
    return jnp.stack(out, axis=0)


def add_small(a, d):
    """a + d for signed int64/int32 d (sign-extended); d may be an array."""
    return add(a, from_i64(jnp.asarray(d)))


def negate(a):
    out = []
    carry = _U64(1)
    for i in range(NLIMBS):
        s = (~a[i]).astype(_U64) + carry
        out.append((s & _M32).astype(_U32))
        carry = s >> _U64(32)
    return jnp.stack(out, axis=0)


def abs256(a):
    return jnp.where(is_negative(a)[None], negate(a), a)


def multiply(a, b):
    """Schoolbook 8x8 32-bit-limb multiply keeping the low 256 bits
    (reference multiply, decimal_utils.cu:126)."""
    au = [a[i].astype(_U64) for i in range(NLIMBS)]
    bu = [b[i].astype(_U64) for i in range(NLIMBS)]
    r = [jnp.zeros_like(au[0]) for _ in range(NLIMBS)]
    for b_idx in range(NLIMBS):
        carry = _U64(0)
        for a_idx in range(NLIMBS - b_idx):
            r_idx = a_idx + b_idx
            m = au[a_idx] * bu[b_idx] + r[r_idx] + carry
            r[r_idx] = m & _M32
            carry = m >> _U64(32)
    return jnp.stack([x.astype(_U32) for x in r], axis=0)


def lt_unsigned(a, b):
    """Unsigned a < b, lexicographic from the high limb down."""
    lt = jnp.zeros(a.shape[1:], dtype=jnp.bool_)
    eq = jnp.ones(a.shape[1:], dtype=jnp.bool_)
    for i in range(NLIMBS - 1, -1, -1):
        lt = lt | (eq & (a[i] < b[i]))
        eq = eq & (a[i] == b[i])
    return lt


def gte_unsigned(a, b):
    return ~lt_unsigned(a, b)


def eq256(a, b):
    return jnp.all(a == b, axis=0)


def _bcast(table_row, like):
    """Broadcast a host (8,) limb constant against limbs[8, ...]."""
    c = jnp.asarray(table_row).reshape((NLIMBS,) + (1,) * (like.ndim - 1))
    return jnp.broadcast_to(c, like.shape)


def pow_ten(k, like):
    """10**k as limbs broadcast to ``like``'s shape; k is a traced int array
    (clipped to [0, 76]) or a python int."""
    if isinstance(k, int):
        return _bcast(POW10[k], like)
    table = jnp.asarray(POW10.T)  # (8, 77)
    return table[:, jnp.clip(k, 0, 76)]


def precision10(a):
    """Smallest i with 10**i >= |a| (reference precision10,
    decimal_utils.cu:520: NOT digit count — exact powers of ten return their
    exponent).  Equals the number of k in [0, 76] with 10**k < |a|."""
    mag = abs256(a)
    # lt_unsigned(pow10[k], mag) for all k at once, k leading: [77, ...]
    p = jnp.asarray(POW10).reshape((77, NLIMBS) + (1,) * (mag.ndim - 1))
    lt = jnp.zeros((77,) + mag.shape[1:], dtype=jnp.bool_)
    eq = jnp.ones((77,) + mag.shape[1:], dtype=jnp.bool_)
    for i in range(NLIMBS - 1, -1, -1):
        lt = lt | (eq & (p[:, i] < mag[i][None]))
        eq = eq & (p[:, i] == mag[i][None])
    return jnp.sum(lt, axis=0).astype(jnp.int32)


def is_greater_than_decimal_38(a):
    """|a| >= 10**38: Spark's precision-38 overflow test
    (decimal_utils.cu:537)."""
    return gte_unsigned(abs256(a), _bcast(POW10[38], a))


def _u128_lt(ahi, alo, bhi, blo):
    return (ahi < bhi) | ((ahi == bhi) & (alo < blo))


def divide_unsigned(n, d_hi, d_lo):
    """256-bit / 128-bit long division (reference divide_unsigned,
    decimal_utils.cu:148): returns (quotient limbs, remainder (hi, lo) u64).

    n must be non-negative (as unsigned), d positive and < 2**127.  Bitwise
    restoring division: 256 sequential steps of elementwise vector work — the
    per-bit loop is over a *scalar* index, all rows advance in lockstep on the
    VPU.  The inner 32 bits of each limb run in a fori_loop; the 8 limbs are
    unrolled so limb indexing stays static.
    """
    d_hi = d_hi.astype(_U64)
    d_lo = d_lo.astype(_U64)
    r_hi = jnp.zeros_like(d_hi)
    r_lo = jnp.zeros_like(d_lo)
    q_limbs = []

    for block in range(NLIMBS - 1, -1, -1):
        nb = n[block].astype(_U64)

        def body(i, state, nb=nb):
            r_hi, r_lo, q_block = state
            bit_pos = _U64(31) - i.astype(_U64)
            read = (nb >> bit_pos) & _U64(1)
            r_hi = (r_hi << _U64(1)) | (r_lo >> _U64(63))
            r_lo = (r_lo << _U64(1)) | read
            ge = ~_u128_lt(r_hi, r_lo, d_hi, d_lo)
            new_lo = r_lo - d_lo
            borrow = (new_lo > r_lo).astype(_U64)
            new_hi = r_hi - d_hi - borrow
            r_hi = jnp.where(ge, new_hi, r_hi)
            r_lo = jnp.where(ge, new_lo, r_lo)
            q_block = q_block | jnp.where(ge, _U64(1) << bit_pos, _U64(0))
            return r_hi, r_lo, q_block

        r_hi, r_lo, q_block = jax.lax.fori_loop(
            0, 32, body, (r_hi, r_lo, jnp.zeros_like(r_lo))
        )
        q_limbs.append((q_block & _M32).astype(_U32))

    q_limbs.reverse()
    return jnp.stack(q_limbs, axis=0), r_hi, r_lo


def divide(n, d_hi, d_lo):
    """Signed divide: 256-bit n / 128-bit d -> (quotient limbs, remainder
    (hi int64, lo uint64) signed, sign of n).  Truncating (toward zero), like
    the reference divide (decimal_utils.cu:170): quotient negative iff signs
    differ, remainder carries n's sign."""
    n_neg = is_negative(n)
    d_neg = d_hi.astype(jnp.int64) < 0
    abs_n = abs256(n)
    # |d| in unsigned 128
    nd_lo = (~d_lo) + _U64(1)
    nd_hi = (~d_hi.astype(_U64)) + (nd_lo == _U64(0)).astype(_U64)
    ad_hi = jnp.where(d_neg, nd_hi, d_hi.astype(_U64))
    ad_lo = jnp.where(d_neg, nd_lo, d_lo)
    q, r_hi, r_lo = divide_unsigned(abs_n, ad_hi, ad_lo)
    q = jnp.where((d_neg != n_neg)[None], negate(q), q)
    # negate remainder where n negative
    nr_lo = (~r_lo) + _U64(1)
    nr_hi = (~r_hi) + (nr_lo == _U64(0)).astype(_U64)
    r_hi = jnp.where(n_neg, nr_hi, r_hi).astype(jnp.int64)
    r_lo = jnp.where(n_neg, nr_lo, r_lo)
    return q, r_hi, r_lo


def round_from_remainder(q, r_hi, r_lo, n_neg, d_hi, d_lo):
    """Half-up rounding increment from a division remainder (reference
    round_from_remainder, decimal_utils.cu:192): bump |q| by one ulp away from
    zero when |2r| >= |d|, with the doubled-remainder-overflow short circuit."""
    r_hi = r_hi.astype(jnp.int64)
    r_lo = r_lo.astype(_U64)
    dbl_hi = (r_hi << jnp.int64(1)) | (r_lo >> _U64(63)).astype(jnp.int64)
    dbl_lo = r_lo << _U64(1)
    # did (r << 1) >> 1 lose information?
    back_hi = (dbl_hi >> jnp.int64(1))
    back_lo = (dbl_lo >> _U64(1)) | (dbl_hi.astype(_U64) << _U64(63))
    lost = (back_hi != r_hi) | (back_lo != r_lo)
    # |2r| and |d| as unsigned 128
    a2_hi, a2_lo = _abs_i128(dbl_hi, dbl_lo)
    ad_hi, ad_lo = _abs_i128(d_hi.astype(jnp.int64), d_lo)
    ge = ~_u128_lt(a2_hi, a2_lo, ad_hi, ad_lo)
    need_inc = lost | ge
    d_neg = d_hi.astype(jnp.int64) < 0
    round_down = n_neg != d_neg
    inc = jnp.where(
        need_inc, jnp.where(round_down, jnp.int64(-1), jnp.int64(1)), jnp.int64(0)
    )
    return add(q, from_i64(inc))


def _abs_i128(hi, lo):
    neg = hi < 0
    n_lo = (~lo) + _U64(1)
    n_hi = (~hi.astype(_U64)) + (n_lo == _U64(0)).astype(_U64)
    return jnp.where(neg, n_hi, hi.astype(_U64)), jnp.where(neg, n_lo, lo)


def divide_and_round(n, d_hi, d_lo):
    """n / d with Java HALF_UP rounding (decimal_utils.cu:228)."""
    q, r_hi, r_lo = divide(n, d_hi, d_lo)
    return round_from_remainder(q, r_hi, r_lo, is_negative(n), d_hi, d_lo)


def integer_divide(n, d_hi, d_lo):
    """n / d truncated toward zero — Java DOWN rounding (decimal_utils.cu:238)."""
    q, _, _ = divide(n, d_hi, d_lo)
    return q
