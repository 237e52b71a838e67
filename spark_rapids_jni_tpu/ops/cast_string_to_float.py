"""Spark-exact string -> float32/float64 cast.

Behavioral parity with the reference's warp-per-row parser
(cast_string_to_float.cu:598 string_to_float_kernel; parse stages :86-557),
including its quirks:

- 'nan' (any case) is only valid as the exact 3-char string; leading
  whitespace/sign make it null (+ ANSI error) but still parse as nan
  (check_for_nan :243-260);
- 'inf'/'infinity' allow leading whitespace and sign, must end the string,
  and garbage after them is null WITHOUT an ANSI error (check_for_inf :276);
- at most 19 significant digits accumulate into a uint64; beyond that, digits
  truncate with the reference's exact (slightly lossy) exponent accounting
  (parse_digits :327-470, max_holding rule :395-445);
- manual exponents read at most 4 digits (parse_manual_exp :505);
- a single trailing f/F/d/D is allowed — except after a zero value, where only
  whitespace may follow (operator() :134-145);
- the final value is digits x 10^exp in IEEE binary64 (subnormal two-step
  :158-195), cast to float32 at the end for FLOAT32 outputs.

TPU split: the O(n x len) character scan is vectorized lane arithmetic on the
padded byte matrix (cummax prefix masks replace the warp ballot/shuffle
choreography).  The final O(n) digits->double assembly ALSO runs on device —
TPU f64 is float32-pair emulated and would not be bit-exact, so the binary64
multiply/divide/convert steps run as exact integer softfloat lane ops
(utils/softfloat; `_assemble_device`).  The host `_assemble` is kept as the
equivalence oracle.  The only host interaction is the ANSI error decision
(one scalar any() sync; row bytes are pulled only on the throw path).
Digit windows longer than one warp batch (32 chars) follow the single-batch
accounting rather than the reference's batch-boundary-dependent truncation
bookkeeping.

Known <=1-ulp divergence: for negative powers (10^-k) our table is the
correctly-rounded binary64 value, while CUDA's exp10 is occasionally 1 ulp
off (verified at exp10(-291)); this only shows in the extreme-exponent range
where the reference already deviates from Java's correctly-rounded parse.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.columnar.buckets import length_buckets, map_buckets
from spark_rapids_jni_tpu.columnar.column import Column, StringColumn
from spark_rapids_jni_tpu.columnar.dtypes import DType, FLOAT64, Kind
from spark_rapids_jni_tpu.ops.cast_string import CastException
from spark_rapids_jni_tpu.utils.softfloat import (
    f64_bits_to_f32_bits,
    f64_div_bits,
    f64_mul_bits,
    u64_to_f64_bits,
)

MAX_SAFE_DIGITS = 19
MAX_HOLDING = ((1 << 64) - 1 - 9) // 10  # 1844674407370955160

# binary64 values of 10^k for k in [-360, 359].  Non-negative k: float(10**k)
# is correctly rounded (exact integer -> nearest double), overflowing to inf
# past 308, matching exp10 saturation.  Negative k: libm pow (what CUDA's
# exp10 effectively is within an ulp).
_EXP10_OFFSET = 360
_EXP10 = np.array(
    [float(np.power(10.0, k)) for k in range(-_EXP10_OFFSET, 0)]
    + [float(10**k) if k <= 308 else np.inf for k in range(360)],
    dtype=np.float64,
)


def _exp10(k: np.ndarray) -> np.ndarray:
    idx = np.clip(k + _EXP10_OFFSET, 0, len(_EXP10) - 1)
    return _EXP10[idx]


_SCAN_FIELDS = [
    ("lens", jnp.int32), ("all_ws", jnp.bool_), ("negative", jnp.bool_),
    ("is_nan", jnp.bool_), ("inf3", jnp.bool_), ("inf_exact", jnp.bool_),
    ("n_lead_zeros", jnp.int32), ("n_sig", jnp.int32),
    ("n_digit_chars", jnp.int32), ("decimal_pos", jnp.int32),
    ("dot_in_run", jnp.bool_), ("val19", jnp.uint64), ("d20", jnp.uint64),
    ("has_exp", jnp.bool_), ("exp_neg", jnp.bool_), ("exp_val", jnp.int32),
    ("exp_digits", jnp.int32), ("has_suffix", jnp.bool_),
    ("tail_nonws", jnp.bool_), ("tail0_nonws", jnp.bool_),
]


def _scan(col: StringColumn):
    """Per-row parse fields as a dict of device arrays.

    Runs the padded-sweep kernel per length bucket (columnar/buckets.py) so a
    long outlier doesn't pad the whole column, then scatters fields back.
    """
    outs = map_buckets(
        col,
        _scan_padded,
        [((), dt) for _, dt in _SCAN_FIELDS],
    )
    return {k: v for (k, _), v in zip(_SCAN_FIELDS, outs)}


# twin: s2f_scan
def _scan_padded(padded, lens, max_exp_digits: int = 4):
    """Padded-view parse sweep over one [n, L] byte rectangle (jitted alias
    ``_scan_padded_jit`` below for callers composing it with other jits).

    ``max_exp_digits``: the Spark cast reads at most 4 manual-exponent digits
    (parse_manual_exp :505) — a cast-only quirk.  JSON number re-rendering
    passes the full text width instead, with the accumulated value saturated
    (huge exponents must become 0.0/Infinity, not parse errors)."""
    n, L = padded.shape
    lens = lens.astype(jnp.int32)
    pos_mat = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_str = pos_mat < lens[:, None]
    c = padded
    lower = jnp.where((c >= 65) & (c <= 90), c + 32, c)  # ascii tolower

    is_ws = ((c <= 0x1F) | (c == 32)) & in_str
    is_digit = (c >= 48) & (c <= 57) & in_str
    is_dot = (c == 46) & in_str

    def first_true(mask, default):
        """index of first True per row, else default."""
        any_ = jnp.any(mask, axis=1)
        idx = jnp.argmax(mask, axis=1).astype(jnp.int32)
        return jnp.where(any_, idx, jnp.int32(default))

    def char_at(p):
        """lowercased char at position p (0 beyond string)."""
        pc = jnp.clip(p, 0, L - 1)
        v = jnp.take_along_axis(lower, pc[:, None], axis=1)[:, 0]
        return jnp.where((p >= 0) & (p < lens), v, jnp.uint8(0))

    # leading whitespace: first position that is not whitespace (positions at
    # or beyond the string end count as non-ws, so all-ws rows land on lens)
    ws_end = jnp.minimum(first_true(~is_ws, L), lens)
    all_ws = ws_end >= lens

    c0 = char_at(ws_end)
    has_sign = (c0 == ord("+")) | (c0 == ord("-"))
    negative = c0 == ord("-")
    p0 = ws_end + has_sign.astype(jnp.int32)

    def match(p, word):
        ok = jnp.ones((n,), jnp.bool_)
        for k, ch in enumerate(word):
            ok &= char_at(p + k) == ord(ch)
        return ok

    is_nan = match(p0, "nan")
    inf3 = match(p0, "inf")
    inf8 = inf3 & match(p0 + 3, "inity")
    inf_exact = (inf3 & (p0 + 3 == lens)) | (inf8 & (p0 + 8 == lens))

    # ---- digit run [p0, stop) : digits plus at most the first dot ----
    after_p0 = pos_mat >= p0[:, None]
    dot_in_tail = is_dot & after_p0
    first_dot = first_true(dot_in_tail, L)
    run_char = is_digit | (pos_mat == first_dot[:, None])
    # break at first position >= p0 that is not a run char
    brk = after_p0 & ~run_char
    stop = first_true(brk, L)
    stop = jnp.minimum(stop, lens)
    in_run = after_p0 & (pos_mat < stop[:, None])
    dot_in_run = (first_dot < stop) & (first_dot >= p0)
    digit_in_run = is_digit & in_run

    # leading zeros before the dot (while the value is still zero)
    nonzero_digit = digit_in_run & (c != 48)
    first_sig = first_true(nonzero_digit, L)  # first nonzero digit anywhere
    pre_dot = pos_mat < first_dot[:, None]
    lead_zero = digit_in_run & pre_dot & (pos_mat < first_sig[:, None])
    n_lead_zeros = jnp.sum(lead_zero, axis=1).astype(jnp.int32)

    sig_mask = digit_in_run & ~lead_zero
    n_sig = jnp.sum(sig_mask, axis=1).astype(jnp.int32)  # digit chars kept
    n_digit_chars = jnp.sum(digit_in_run, axis=1).astype(jnp.int32)
    # significant digits before the dot
    decimal_pos = jnp.sum(sig_mask & pre_dot, axis=1).astype(jnp.int32)

    # rank of each significant digit (0-based within the kept sequence);
    # value of the first min(n_sig, 19) digits as u64, plus the 20th digit
    # (post-dot zeros count as significant chars but keep the value small, so
    # the reference's +1-digit rule is reachable for 0.00...ddd inputs)
    rank = jnp.cumsum(sig_mask.astype(jnp.int32), axis=1) - 1
    pow10 = jnp.asarray(np.array([10**k for k in range(20)], dtype=np.uint64))
    digit_vals = (c - jnp.uint8(48)).astype(jnp.uint64)
    k19 = jnp.minimum(n_sig, 19)
    take19 = sig_mask & (rank < 19)
    w19 = pow10[jnp.clip(jnp.where(take19, (k19[:, None] - 1 - rank), 0), 0, 19)]
    val19 = jnp.sum(jnp.where(take19, digit_vals * w19, jnp.uint64(0)), axis=1)
    d20 = jnp.sum(
        jnp.where(sig_mask & (rank == 19), digit_vals, jnp.uint64(0)), axis=1
    )

    # ---- manual exponent at `stop` ----
    ce = char_at(stop)
    has_exp = ce == ord("e")
    pe = stop + 1
    cs = char_at(pe)
    exp_has_sign = has_exp & ((cs == ord("+")) | (cs == ord("-")))
    exp_neg = exp_has_sign & (cs == ord("-"))
    pd = pe + exp_has_sign.astype(jnp.int32)
    # up to max_exp_digits digit chars considered; the value saturates so
    # absurdly long exponents stay order-of-magnitude correct (-> 0.0/inf)
    exp_digits = jnp.zeros((n,), jnp.int32)
    exp_val = jnp.zeros((n,), jnp.int32)
    still = jnp.ones((n,), jnp.bool_)
    for k in range(max_exp_digits):
        ck = char_at(pd + k)
        is_d = (ck >= 48) & (ck <= 57) & still & (pd + k < lens)
        exp_val = jnp.where(
            is_d,
            jnp.minimum(exp_val * 10 + (ck - 48).astype(jnp.int32), 99999),
            exp_val)
        exp_digits = exp_digits + is_d.astype(jnp.int32)
        still = still & is_d
    p_after_exp = jnp.where(has_exp, pd + exp_digits, stop)

    # ---- trailing: one f/d then whitespace then end ----
    cf = char_at(p_after_exp)
    has_suffix = (cf == ord("f")) | (cf == ord("d"))
    pt = p_after_exp + has_suffix.astype(jnp.int32)
    tail = (pos_mat >= pt[:, None]) & in_str
    tail_nonws = jnp.any(tail & ~is_ws, axis=1)

    # zero-value rows allow only whitespace after the number (no f/d suffix)
    tail0 = (pos_mat >= p_after_exp[:, None]) & in_str
    tail0_nonws = jnp.any(tail0 & ~is_ws, axis=1)

    fields = dict(
        lens=lens, all_ws=all_ws, negative=negative,
        is_nan=is_nan, inf3=inf3, inf_exact=inf_exact,
        n_lead_zeros=n_lead_zeros, n_sig=n_sig, n_digit_chars=n_digit_chars,
        decimal_pos=decimal_pos, dot_in_run=dot_in_run,
        val19=val19, d20=d20,
        has_exp=has_exp, exp_neg=exp_neg, exp_val=exp_val,
        exp_digits=exp_digits,
        has_suffix=has_suffix, tail_nonws=tail_nonws, tail0_nonws=tail0_nonws,
    )
    return tuple(fields[k].astype(dt) for k, dt in _SCAN_FIELDS)


# twin: s2f_scan
def _scan_padded_np(padded, lens, max_exp_digits: int = 4):
    """numpy twin of _scan_padded: the same single-pass prefix-mask sweep
    over one [n, L] byte rectangle, lane-for-lane (round 20)."""
    n, L = padded.shape
    lens = lens.astype(np.int32)
    pos_mat = np.arange(L, dtype=np.int32)[None, :]
    in_str = pos_mat < lens[:, None]
    c = padded
    lower = np.where((c >= 65) & (c <= 90), c + 32, c)  # ascii tolower

    is_ws = ((c <= 0x1F) | (c == 32)) & in_str
    is_digit = (c >= 48) & (c <= 57) & in_str
    is_dot = (c == 46) & in_str

    def first_true(mask, default):
        """index of first True per row, else default."""
        any_ = np.any(mask, axis=1)
        idx = np.argmax(mask, axis=1).astype(np.int32)
        return np.where(any_, idx, np.int32(default))

    def char_at(p):
        """lowercased char at position p (0 beyond string)."""
        pc = np.clip(p, 0, L - 1)
        v = np.take_along_axis(lower, pc[:, None], axis=1)[:, 0]
        return np.where((p >= 0) & (p < lens), v, np.uint8(0))

    ws_end = np.minimum(first_true(~is_ws, L), lens)
    all_ws = ws_end >= lens

    c0 = char_at(ws_end)
    has_sign = (c0 == ord("+")) | (c0 == ord("-"))
    negative = c0 == ord("-")
    p0 = ws_end + has_sign.astype(np.int32)

    def match(p, word):
        ok = np.ones((n,), np.bool_)
        for k, ch in enumerate(word):
            ok &= char_at(p + k) == ord(ch)
        return ok

    is_nan = match(p0, "nan")
    inf3 = match(p0, "inf")
    inf8 = inf3 & match(p0 + 3, "inity")
    inf_exact = (inf3 & (p0 + 3 == lens)) | (inf8 & (p0 + 8 == lens))

    after_p0 = pos_mat >= p0[:, None]
    dot_in_tail = is_dot & after_p0
    first_dot = first_true(dot_in_tail, L)
    run_char = is_digit | (pos_mat == first_dot[:, None])
    brk = after_p0 & ~run_char
    stop = first_true(brk, L)
    stop = np.minimum(stop, lens)
    in_run = after_p0 & (pos_mat < stop[:, None])
    dot_in_run = (first_dot < stop) & (first_dot >= p0)
    digit_in_run = is_digit & in_run

    nonzero_digit = digit_in_run & (c != 48)
    first_sig = first_true(nonzero_digit, L)
    pre_dot = pos_mat < first_dot[:, None]
    lead_zero = digit_in_run & pre_dot & (pos_mat < first_sig[:, None])
    n_lead_zeros = np.sum(lead_zero, axis=1).astype(np.int32)

    sig_mask = digit_in_run & ~lead_zero
    n_sig = np.sum(sig_mask, axis=1).astype(np.int32)
    n_digit_chars = np.sum(digit_in_run, axis=1).astype(np.int32)
    decimal_pos = np.sum(sig_mask & pre_dot, axis=1).astype(np.int32)

    rank = np.cumsum(sig_mask.astype(np.int32), axis=1) - 1
    pow10 = np.array([10**k for k in range(20)], dtype=np.uint64)
    digit_vals = (c - np.uint8(48)).astype(np.uint64)
    k19 = np.minimum(n_sig, 19)
    take19 = sig_mask & (rank < 19)
    w19 = pow10[np.clip(np.where(take19, (k19[:, None] - 1 - rank), 0), 0, 19)]
    val19 = np.sum(np.where(take19, digit_vals * w19, np.uint64(0)), axis=1)
    d20 = np.sum(
        np.where(sig_mask & (rank == 19), digit_vals, np.uint64(0)), axis=1
    )

    ce = char_at(stop)
    has_exp = ce == ord("e")
    pe = stop + 1
    cs = char_at(pe)
    exp_has_sign = has_exp & ((cs == ord("+")) | (cs == ord("-")))
    exp_neg = exp_has_sign & (cs == ord("-"))
    pd = pe + exp_has_sign.astype(np.int32)
    exp_digits = np.zeros((n,), np.int32)
    exp_val = np.zeros((n,), np.int32)
    still = np.ones((n,), np.bool_)
    for k in range(max_exp_digits):
        ck = char_at(pd + k)
        is_d = (ck >= 48) & (ck <= 57) & still & (pd + k < lens)
        exp_val = np.where(
            is_d,
            np.minimum(exp_val * 10 + (ck - 48).astype(np.int32), 99999),
            exp_val)
        exp_digits = exp_digits + is_d.astype(np.int32)
        still = still & is_d
    p_after_exp = np.where(has_exp, pd + exp_digits, stop)

    cf = char_at(p_after_exp)
    has_suffix = (cf == ord("f")) | (cf == ord("d"))
    pt = p_after_exp + has_suffix.astype(np.int32)
    tail = (pos_mat >= pt[:, None]) & in_str
    tail_nonws = np.any(tail & ~is_ws, axis=1)

    tail0 = (pos_mat >= p_after_exp[:, None]) & in_str
    tail0_nonws = np.any(tail0 & ~is_ws, axis=1)

    fields = dict(
        lens=lens, all_ws=all_ws, negative=negative,
        is_nan=is_nan, inf3=inf3, inf_exact=inf_exact,
        n_lead_zeros=n_lead_zeros, n_sig=n_sig, n_digit_chars=n_digit_chars,
        decimal_pos=decimal_pos, dot_in_run=dot_in_run,
        val19=val19, d20=d20,
        has_exp=has_exp, exp_neg=exp_neg, exp_val=exp_val,
        exp_digits=exp_digits,
        has_suffix=has_suffix, tail_nonws=tail_nonws, tail0_nonws=tail0_nonws,
    )
    return fields


def _scan_rect_np(padded, lens):
    """Optimized host scan over one zero-filled [n, L] rectangle.

    Equivalent to _scan_padded_np (the pinned twin mirror, kept as the
    cheap parity oracle) but restructured for throughput: the run counts
    collapse to O(n) boundary arithmetic (the run is contiguous, so counting
    chars is subtracting positions), the 19-digit value accumulates as a
    Horner sweep over transposed contiguous columns instead of a
    rank-cumsum + pow10 gather over uint64 rectangles, and the tail checks
    reduce to one last-non-ws position per row.  Requires bytes at and
    beyond each row's length to be zero (see _scan_np's rectangle build).
    """
    n, L = padded.shape
    lens = lens.astype(np.int32)
    c = padded
    nonws = (c > 0x1F) & (c != 32)  # sentinel \0 counts as ws
    is_digit = (c - np.uint8(48)) <= 9  # uint8 wraparound: one compare
    pos_mat = np.arange(L, dtype=np.int32)[None, :]
    # one-column gathers run as flat fancy indexing over a shared row-offset
    # vector: np.take_along_axis pays index broadcasting + a (n, 1) reshape
    # per call, which dominates these O(n) probes on a memory-bound host
    rowoff = np.arange(n, dtype=np.int64) * L
    cflat = c.reshape(-1)

    def first_true(mask, default):
        # checking mask at its own argmax is cheaper than a second np.any
        # reduction over the whole rectangle
        idx = np.argmax(mask, axis=1).astype(np.int32)
        found = mask.reshape(-1)[rowoff + idx]
        return np.where(found, idx, np.int32(default))

    def char_at(p):
        """lowercased char at position p (0 beyond string)."""
        pc = np.clip(p, 0, L - 1)
        v = cflat[rowoff + pc]
        v = np.where((v >= 65) & (v <= 90), v + 32, v)
        return np.where((p >= 0) & (p < lens), v, np.uint8(0))

    ws_end = np.minimum(first_true(nonws, L), lens)
    all_ws = ws_end >= lens

    c0 = char_at(ws_end)
    has_sign = (c0 == ord("+")) | (c0 == ord("-"))
    negative = c0 == ord("-")
    p0 = ws_end + has_sign.astype(np.int32)

    # nan / inf / infinity: only rows whose first payload char is n/i can
    # match, so the 8-char block compare runs on that (usually tiny) subset
    cp0 = char_at(p0)
    cand = np.nonzero((cp0 == ord("n")) | (cp0 == ord("i")))[0]
    is_nan = np.zeros((n,), np.bool_)
    inf3 = np.zeros((n,), np.bool_)
    inf_exact = np.zeros((n,), np.bool_)
    if cand.size:
        cs_ = c[cand]
        ps = p0[cand]
        ar8 = np.arange(8, dtype=np.int32)
        g = np.take_along_axis(cs_, np.minimum(ps[:, None] + ar8, L - 1), axis=1)
        g = np.where((g >= 65) & (g <= 90), g + 32, g)
        g = np.where(ps[:, None] + ar8 < lens[cand][:, None], g, np.uint8(0))

        def match(k0, word):
            ok = np.ones((cand.size,), np.bool_)
            for k, ch in enumerate(word):
                ok &= g[:, k0 + k] == ord(ch)
            return ok

        nan_s = match(0, "nan")
        inf3_s = match(0, "inf")
        inf8_s = inf3_s & match(3, "inity")
        is_nan[cand] = nan_s
        inf3[cand] = inf3_s
        inf_exact[cand] = (inf3_s & (ps + 3 == lens[cand])) | (
            inf8_s & (ps + 8 == lens[cand])
        )

    # digit run [p0, stop): contiguous digits plus at most the first dot.
    # Only ws/sign chars precede p0, so the first dot / first nonzero digit
    # anywhere IS the first one at >= p0 — no after-p0 masking needed.
    first_dot = first_true(c == 46, L)
    run_char = is_digit | (pos_mat == first_dot[:, None])
    after_p0 = pos_mat >= p0[:, None]
    stop = np.minimum(first_true(after_p0 & ~run_char, L), lens)
    dot_in_run = (first_dot < stop) & (first_dot >= p0)
    first_sig = first_true((c - np.uint8(49)) <= 8, L)  # c in '1'..'9'

    # the run is contiguous, so every count is boundary arithmetic:
    # [p0, min(first_dot, first_sig, stop)) are exactly the leading zeros
    n_digit_chars = (stop - p0) - dot_in_run.astype(np.int32)
    n_lead_zeros = np.minimum(np.minimum(first_dot, first_sig), stop) - p0
    n_sig = n_digit_chars - n_lead_zeros
    decimal_pos = np.minimum(first_dot, stop) - p0 - n_lead_zeros

    # first-19-digit value: Horner sweep over transposed contiguous columns.
    # sig = in-run digit past the dot or at/after the first nonzero digit,
    # i.e. a digit at position in [min(first_dot + 1, first_sig), stop).
    # The u8 digit columns feed the u64 accumulator unconverted — numpy's
    # buffered in-ufunc cast is ~35% cheaper than materializing a u64
    # column per iteration on a memory-bound host.
    sig_lo = np.minimum(first_dot + 1, first_sig)
    # sig = in-run digit at position >= sig_lo; the per-column flags come
    # from the one transposed digit rectangle plus two O(n) scalar-vs-row
    # compares per column — no (n, L) sig mask or second transpose copy
    dig_t = np.ascontiguousarray(c.T) - np.uint8(48)
    digit_t = dig_t <= np.uint8(9)
    val19 = np.zeros((n,), np.uint64)
    d20 = np.zeros((n,), np.uint64)
    cnt = np.zeros((n,), np.int32)
    capped = bool((n_sig > 19).any())  # else cnt never reaches 19
    one, nine = np.uint8(1), np.uint8(9)
    for j in range(min(L, int(stop.max(initial=0)))):  # sig positions < stop
        sig_j = digit_t[j] & (sig_lo <= j) & (j < stop)
        d_j = dig_t[j]
        take = sig_j & (cnt < 19) if capped else sig_j
        # val19 = val19 * 10 + d_j where take, else unchanged — as two
        # in-place u64 ops with arithmetic selects (x10/x1 multiplier,
        # digit-or-zero addend): no np.where temporaries on the hot loop
        np.multiply(val19, one + take * nine, out=val19, casting="unsafe")
        np.add(val19, d_j * take, out=val19, casting="unsafe")
        if capped:
            d20 = np.where(sig_j & (cnt == 19), d_j, d20)
            cnt += sig_j
    # mirror semantics: np.minimum(n_sig, 19) digits accumulated, 20th in d20

    # manual exponent: 4-char block gather at pd, then one vectorized
    # consecutive-digit accumulate (4 digits max out at 9999, so the
    # mirror's 99999 saturation clamp can never fire here)
    ce = char_at(stop)
    has_exp = ce == ord("e")
    pe = stop + 1
    cs2 = char_at(pe)
    exp_has_sign = has_exp & ((cs2 == ord("+")) | (cs2 == ord("-")))
    exp_neg = exp_has_sign & (cs2 == ord("-"))
    pd = pe + exp_has_sign.astype(np.int32)
    ar4 = np.arange(4, dtype=np.int32)
    ge = cflat[rowoff[:, None] + np.clip(pd[:, None] + ar4, 0, L - 1)]
    dmask = ((ge - np.uint8(48)) <= 9) & (pd[:, None] + ar4 < lens[:, None])
    run4 = np.logical_and.accumulate(dmask, axis=1)
    exp_digits = np.sum(run4, axis=1).astype(np.int32)
    pw4 = np.array([1, 10, 100, 1000], np.int32)
    shift = np.clip(exp_digits[:, None] - 1 - ar4, 0, 3)
    exp_val = np.sum(
        run4 * (ge - np.uint8(48)).astype(np.int32) * pw4[shift], axis=1
    ).astype(np.int32)
    p_after_exp = np.where(has_exp, pd + exp_digits, stop)

    cf = char_at(p_after_exp)
    has_suffix = (cf == ord("f")) | (cf == ord("d"))
    pt = p_after_exp + has_suffix.astype(np.int32)
    # trailing checks via the last non-ws position (sentinel zeros are ws)
    nonws_rev = nonws[:, ::-1]
    last_nonws = np.where(
        all_ws,  # all_ws == "no non-ws byte anywhere" (sentinel \0 is ws)
        np.int32(-1),
        np.int32(L - 1) - np.argmax(nonws_rev, axis=1).astype(np.int32),
    )
    tail_nonws = last_nonws >= pt
    tail0_nonws = last_nonws >= p_after_exp

    return dict(
        lens=lens, all_ws=all_ws, negative=negative,
        is_nan=is_nan, inf3=inf3, inf_exact=inf_exact,
        n_lead_zeros=n_lead_zeros, n_sig=n_sig, n_digit_chars=n_digit_chars,
        decimal_pos=decimal_pos, dot_in_run=dot_in_run,
        val19=val19, d20=d20,
        has_exp=has_exp, exp_neg=exp_neg, exp_val=exp_val,
        exp_digits=exp_digits,
        has_suffix=has_suffix, tail_nonws=tail_nonws, tail0_nonws=tail0_nonws,
    )


_SCAN_FIELDS_NP = {
    "lens": np.int32, "all_ws": np.bool_, "negative": np.bool_,
    "is_nan": np.bool_, "inf3": np.bool_, "inf_exact": np.bool_,
    "n_lead_zeros": np.int32, "n_sig": np.int32, "n_digit_chars": np.int32,
    "decimal_pos": np.int32, "dot_in_run": np.bool_, "val19": np.uint64,
    "d20": np.uint64, "has_exp": np.bool_, "exp_neg": np.bool_,
    "exp_val": np.int32, "exp_digits": np.int32, "has_suffix": np.bool_,
    "tail_nonws": np.bool_, "tail0_nonws": np.bool_,
}


def _scan_np(col: StringColumn):
    """Host mirror of _scan: pow2 length buckets over the numpy byte arrays
    (so short numerics never pay a long outlier's rectangle), each scanned by
    _scan_rect_np over a zero-filled rectangle clamped to the bucket's true
    max length (host rectangles have no jit shape cache to feed, so nothing
    forces the width itself up to a power of two)."""
    chars = np.asarray(col.chars)
    offsets = np.asarray(col.offsets)
    lens_all = (offsets[1:] - offsets[:-1]).astype(np.int32)
    n = lens_all.shape[0]
    buckets = length_buckets(lens_all, min_width=4)
    # bucketing only pays when it prunes padded work (long outliers);
    # a flat length profile runs as ONE rectangle, skipping the
    # per-field scatter-backs entirely
    w_max = int(lens_all.max(initial=0))
    bucketed_work = sum(w * nv for w, _, nv in buckets)
    mono = bool(n and n * w_max <= bucketed_work)
    if mono:
        buckets = [(w_max, np.arange(n, dtype=np.int64), n)]
    outs = {k: np.zeros(n, dt) for k, dt in _SCAN_FIELDS_NP.items()}
    for _, rows_np, n_valid in buckets:
        rows_np = rows_np[:n_valid]
        lens = lens_all[rows_np]
        width = max(int(lens.max(initial=0)), 1)
        in_row = np.arange(width, dtype=np.int32)[None, :] < lens[:, None]
        if mono:
            # all rows in offset order: the chars buffer between
            # offsets[0] and offsets[-1] IS the row-major concatenation
            # of every row's bytes, so one boolean scatter fills the
            # rectangle — no (n, W) int32 index matrix, no gather, no
            # zeroing multiply
            padded = np.zeros((n_valid, width), np.uint8)
            padded[in_row] = chars[int(offsets[0]):int(offsets[-1])]
        else:
            starts = offsets[rows_np].astype(np.int32)
            idx = starts[:, None] + np.arange(
                width, dtype=np.int32)[None, :]
            pad_chars = np.concatenate(
                [chars, np.zeros((width,), np.uint8)]
            )
            padded = pad_chars[idx]
            padded *= in_row
        fields = _scan_rect_np(padded, lens)
        if n_valid == n:
            for k, dt in _SCAN_FIELDS_NP.items():
                outs[k] = fields[k].astype(dt)
        else:
            for k, dt in _SCAN_FIELDS_NP.items():
                outs[k][rows_np] = fields[k].astype(dt)
    return outs


_EXP10_BITS = _EXP10.view(np.int64)
_POW10_U64 = np.array([10**k for k in range(20)], dtype=np.uint64)
_NAN_BITS = np.int64(np.float64(np.nan).view(np.int64))


def _exp10_bits(k):
    """binary64 bit pattern of 10^k (same clipped table as _exp10)."""
    idx = jnp.clip(k + _EXP10_OFFSET, 0, len(_EXP10) - 1)
    return jnp.asarray(_EXP10_BITS)[idx]


# twin: s2f_assemble
@jax.jit
def _assemble_device(f):
    """Device replication of the reference's final double assembly
    (cast_string_to_float.cu:134-199) in exact integer binary64 arithmetic
    (utils/softfloat) — TPU f64 is emulated, so the bit-exact math runs as
    uint64 lane ops.  Returns (bits int64, valid, except_) device arrays;
    the host `_assemble` remains as the debug/equivalence oracle."""
    lens = f["lens"].astype(jnp.int64)
    neg = f["negative"]
    sign_bit = neg.astype(jnp.int64) << jnp.int64(63)
    n = lens.shape[0]

    valid = jnp.ones((n,), bool)
    except_ = jnp.zeros((n,), bool)

    nan_rows = f["is_nan"]
    bad_nan = nan_rows & (lens != 3)
    valid &= ~bad_nan
    except_ |= bad_nan

    inf_rows = f["inf3"] & ~nan_rows
    ok_inf = inf_rows & f["inf_exact"]
    valid &= ~(inf_rows & ~f["inf_exact"])  # no ANSI error (cu :276)

    plain = ~nan_rows & ~inf_rows
    seen_digit = (f["n_digit_chars"] > 0) | (f["n_lead_zeros"] > 0)
    no_digits = plain & ~seen_digit
    valid &= ~no_digits
    except_ |= no_digits

    # 19-significant-char accumulation + truncation accounting (:395-445)
    n_sig = f["n_sig"].astype(jnp.int64)
    val19 = f["val19"]
    over = n_sig > 19
    can_add = over & (val19 <= jnp.uint64(MAX_HOLDING)) & (
        val19 * jnp.uint64(10) + f["d20"] <= jnp.uint64(MAX_HOLDING)
    )
    digits = jnp.where(can_add, val19 * jnp.uint64(10) + f["d20"], val19)
    real_digits = jnp.minimum(n_sig, 19)
    truncated = jnp.where(can_add, n_sig - 18, jnp.where(over, n_sig - 19, 0))
    total_digits = real_digits + truncated
    exp_base = truncated - jnp.where(
        f["dot_in_run"], total_digits - f["decimal_pos"].astype(jnp.int64), 0
    )

    bad_exp = plain & f["has_exp"] & (f["exp_digits"] == 0)
    valid &= ~bad_exp
    except_ |= bad_exp
    manual = jnp.where(f["exp_neg"], -f["exp_val"], f["exp_val"]).astype(jnp.int64)
    manual = jnp.where(f["has_exp"], manual, 0)

    zero = plain & (digits == 0) & seen_digit
    bad_zero_tail = zero & f["tail0_nonws"]
    valid &= ~bad_zero_tail
    except_ |= bad_zero_tail

    nonzero = plain & (digits != 0)
    bad_tail = nonzero & f["tail_nonws"]
    valid &= ~bad_tail
    except_ |= bad_tail

    # final assembly (:153-199) in softfloat binary64
    exp_ten = exp_base + manual
    digits_bits = u64_to_f64_bits(digits) | sign_bit
    nd = jnp.ones((n,), jnp.int64)  # decimal digit count of `digits`
    for k in range(1, 20):
        nd += (digits >= _POW10_U64[k]).astype(jnp.int64)

    too_big = exp_ten > 308
    sub_shift = -307 - exp_ten
    subnormal = ~too_big & (sub_shift > 0)
    dsub = f64_div_bits(digits_bits, _exp10_bits(nd - 1 + sub_shift))
    res_sub = f64_mul_bits(dsub, _exp10_bits(exp_ten + nd - 1 + sub_shift))
    e10 = _exp10_bits(jnp.abs(exp_ten))
    res_norm = jnp.where(
        exp_ten < 0,
        f64_div_bits(digits_bits, e10),
        f64_mul_bits(digits_bits, e10),
    )
    inf_bits = sign_bit | jnp.int64(0x7FF0000000000000)
    res = jnp.where(too_big, inf_bits,
                    jnp.where(subnormal, res_sub, res_norm))

    out = jnp.zeros((n,), jnp.int64)
    out = jnp.where(nan_rows, _NAN_BITS, out)
    out = jnp.where(ok_inf, inf_bits, out)
    out = jnp.where(zero, sign_bit, out)
    out = jnp.where(nonzero, res, out)
    return out, valid, except_


_scan_padded_jit = jax.jit(_scan_padded, static_argnums=(2,))


# twin: s2f_assemble
def _assemble(f, out_dtype_np):
    """Host: replicate the reference's final double assembly (:134-199).

    Promoted from debug oracle to the XLA:CPU fast path in round 20 (the
    backend-adaptive `cast_device_parse` dispatch): hardware binary64 is
    exactly the arithmetic the softfloat device twin emulates."""
    f = {k: np.asarray(v) for k, v in f.items()}
    lens = f["lens"].astype(np.int64)
    n = lens.shape[0]
    out = np.zeros((n,), np.float64)
    valid = np.ones((n,), bool)
    except_ = np.zeros((n,), bool)

    sign = np.where(f["negative"], -1.0, 1.0)

    # nan: always writes NaN; only the bare 3-char string is valid
    nan_rows = f["is_nan"]
    out[nan_rows] = np.nan
    bad_nan = nan_rows & (lens != 3)
    valid[bad_nan] = False
    except_[bad_nan] = True

    # inf / infinity
    inf_rows = f["inf3"] & ~nan_rows
    ok_inf = inf_rows & f["inf_exact"]
    out[ok_inf] = np.where(f["negative"][ok_inf], -np.inf, np.inf)
    valid[inf_rows & ~f["inf_exact"]] = False  # no ANSI error (cu :276 comment)

    plain = ~nan_rows & ~inf_rows

    # no digits at all -> invalid + except (includes empty / all-ws strings)
    seen_digit = (f["n_digit_chars"] > 0) | (f["n_lead_zeros"] > 0)
    no_digits = plain & ~seen_digit
    valid[no_digits] = False
    except_[no_digits] = True

    # 19-significant-char accumulation with the reference's truncation
    # accounting (cast_string_to_float.cu:395-445).  The "+1 digit" rule only
    # fires when post-dot zeros pad the window (value stays <= max_holding/10,
    # e.g. "0.0123...": zeros count as chars but not value); for a normalized
    # 19-digit value digits*10 always overflows max_holding.
    n_sig = f["n_sig"].astype(np.int64)
    val19 = f["val19"]
    real_digits = np.minimum(n_sig, 19)
    over = n_sig > 19
    # the val19 <= MAX_HOLDING clause both mirrors the reference's outer
    # check and keeps the *10 below from wrapping u64
    can_add = over & (val19 <= np.uint64(MAX_HOLDING)) & (
        val19 * np.uint64(10) + f["d20"] <= np.uint64(MAX_HOLDING)
    )
    digits = np.where(can_add, val19 * np.uint64(10) + f["d20"], val19)
    # bug-compat: the reference counts one extra truncated char when it adds
    # the 20th digit without incrementing real_digits (:437)
    truncated = np.where(can_add, n_sig - 18, np.where(over, n_sig - 19, 0))

    total_digits = real_digits + truncated
    exp_base = truncated - np.where(
        f["dot_in_run"], total_digits - f["decimal_pos"].astype(np.int64), 0
    )

    # manual exponent; 'e' with no digits is invalid
    bad_exp = plain & f["has_exp"] & (f["exp_digits"] == 0)
    valid[bad_exp] = False
    except_[bad_exp] = True
    manual = np.where(f["exp_neg"], -f["exp_val"], f["exp_val"]).astype(np.int64)
    manual = np.where(f["has_exp"], manual, 0)

    zero = plain & (digits == 0) & seen_digit
    bad_zero_tail = zero & f["tail0_nonws"]
    valid[bad_zero_tail] = False
    except_[bad_zero_tail] = True
    out = np.where(zero, sign * 0.0, out)

    nonzero = plain & (digits != 0)
    bad_tail = nonzero & f["tail_nonws"]
    valid[bad_tail] = False
    except_[bad_tail] = True

    # final assembly in binary64 (cast_string_to_float.cu:153-199)
    exp_ten = (exp_base + manual).astype(np.int64)
    digitsf = sign * digits.astype(np.float64)
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        res = np.zeros((n,), np.float64)
        too_big = exp_ten > 308
        res[too_big] = np.where(f["negative"][too_big], -np.inf, np.inf)
        sub_shift = -307 - exp_ten
        subnormal = ~too_big & (sub_shift > 0)
        if subnormal.any():
            nd = np.char.str_len(
                digits[subnormal].astype("U32")
            ).astype(np.int64)  # number of digits
            dsub = digitsf[subnormal] / _exp10(nd - 1 + sub_shift[subnormal])
            e2 = exp_ten[subnormal] + nd - 1 + sub_shift[subnormal]
            res[subnormal] = dsub * _exp10(e2)
        normal = ~too_big & ~subnormal
        exponent = _exp10(np.abs(exp_ten[normal]))
        dn = digitsf[normal]
        res[normal] = np.where(exp_ten[normal] < 0, dn / exponent, dn * exponent)
    out = np.where(nonzero, res, out)

    if out_dtype_np == np.float32:
        with np.errstate(over="ignore"):  # double->float32 overflow -> inf
            out = out.astype(np.float32)
    return out, valid, except_


def _device_parse_enabled() -> bool:
    v = config.get("cast_device_parse")
    if v == "auto":
        return jax.default_backend() != "cpu"
    return bool(v)


def _string_to_float_host(col: StringColumn, ansi_mode: bool, dtype: DType):
    """XLA:CPU arm: bucketed numpy scan + the hardware-binary64 assembly
    twin, no device round-trips (round 20)."""
    f = _scan_np(col)
    out_np = np.float32 if dtype.kind == Kind.FLOAT32 else np.float64
    out, valid, except_ = _assemble(f, out_np)

    in_valid = np.asarray(col.is_valid())
    except_ = except_ & in_valid
    if ansi_mode and except_.any():
        row = int(np.argmax(except_))
        offs = np.asarray(col.offsets)
        bad = bytes(np.asarray(col.chars)[offs[row] : offs[row + 1]])
        raise CastException(bad.decode("utf-8", errors="replace"), row)

    validity = jnp.asarray(valid & in_valid)
    if dtype.kind == Kind.FLOAT64:
        data = jnp.asarray(out.view(np.int64))  # bit-pattern convention
    else:
        data = jnp.asarray(out)
    return Column(data, validity, dtype)


def string_to_float(
    col: StringColumn, ansi_mode: bool, dtype: DType = FLOAT64
) -> Column:
    """Parse a string column into FLOAT32/FLOAT64 with Spark semantics.

    Invalid rows become null, or raise CastException (with the first bad row
    index) when ``ansi_mode`` (CastStringJni.cpp CATCH_CAST_EXCEPTION path).
    Backend-adaptive: on accelerators the lane scan + softfloat assembly run
    on device; on XLA:CPU the twin numpy pipeline avoids the transfer tax
    (``cast_device_parse`` pins either arm).
    """
    if dtype.kind not in (Kind.FLOAT32, Kind.FLOAT64):
        raise TypeError("string_to_float produces FLOAT32 or FLOAT64")
    if not _device_parse_enabled():
        return _string_to_float_host(col, ansi_mode, dtype)
    f = _scan(col)
    bits, valid, except_ = _assemble_device(f)

    in_valid = col.is_valid()
    except_ = except_ & in_valid
    # error control flow is the one host decision: a scalar any() sync, with
    # the failing row's bytes pulled only on the (exceptional) throw path
    if ansi_mode and bool(jnp.any(except_)):
        row = int(jnp.argmax(except_))
        offs = np.asarray(col.offsets)
        bad = bytes(np.asarray(col.chars[offs[row] : offs[row + 1]]))
        raise CastException(bad.decode("utf-8", errors="replace"), row)

    validity = valid & in_valid
    if dtype.kind == Kind.FLOAT64:
        data = bits  # bit-pattern convention for FLOAT64 columns
    else:
        data = jax.lax.bitcast_convert_type(
            f64_bits_to_f32_bits(bits), jnp.float32
        )
    return Column(data, validity, dtype)
