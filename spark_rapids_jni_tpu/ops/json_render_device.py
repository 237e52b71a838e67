"""Device-resident rendering for ``get_json_object``.

jnp re-expression of the host render pipeline in ops/get_json_object.py —
per-byte escape tables (`_byte_info`), per-token emission tables, path-name
matching, float re-rendering, and the segment->bytes expansion (`_render`)
— so a bucket's bytes never leave the device: the only host interaction is
three scalar shape syncs (float count, float source width, output width),
each padded to a power of two to bound the compile-variant set.  The host
numpy pipeline remains the debug oracle (config ``json_device_render``).

Reference parity target is unchanged: get_json_object.cu:891 runs the whole
evaluation + output write in one kernel; this module restores that residency
on the TPU shape (rectangles + gathers instead of per-thread byte loops).

Float re-rendering uses the Spark-exact parse (cast_string_to_float's
device scan + softfloat assembly) followed by the Ryu digit core
(float_to_string._d2d/_emit).  For numbers with <= 15 significant digits and
|exp10| <= 22 this equals the host oracle's correctly-rounded strtod; beyond
that the two-step rounding may differ by 1 ulp from python/Java parsing —
the same territory where the CUDA reference's own stod diverges.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.ops import json_tokenizer as jt
from spark_rapids_jni_tpu.ops.get_json_object import (
    _CONST_LEN,
    _CONST_MAXLEN,
    _CONST_TAB,
    _CONSTS,
    _CTRL_SHORT,
    _HEX_UP,
    _SEG_COND_CLOSE,
    _SEG_COND_OPEN,
    _SEG_CONST,
    _SEG_ESC_TOK,
    _SEG_RAW_TOK,
    _UNESC,
)

_I32 = jnp.int32
_I64 = jnp.int64
_U8 = jnp.uint8

_UNESC_J = jnp.asarray(_UNESC)
_CTRL_SHORT_J = jnp.asarray(_CTRL_SHORT)
_HEX_UP_J = jnp.asarray(_HEX_UP)
_CONST_TAB_J = jnp.asarray(_CONST_TAB)
_CONST_LEN_J = jnp.asarray(_CONST_LEN)


class DByteInfo(NamedTuple):
    """Device twin of get_json_object._ByteInfo (all jnp, [n, L]-shaped)."""

    b: jnp.ndarray
    cls_esc: jnp.ndarray
    cls_u: jnp.ndarray
    cp: jnp.ndarray
    ulen: jnp.ndarray
    len_e: jnp.ndarray
    cum_u: jnp.ndarray
    cum_e: jnp.ndarray
    cum_uni: jnp.ndarray


# one-hot row read (rationale + measurement in its docstring)
_take_rows = jt._take_rows


def _searchsorted_rows(a, v):
    """Per-row searchsorted-right: a [n, L] row-sorted, v [n, W] -> [n, W].

    Implemented as a count of ``a[i, :] <= v[i, w]`` rather than binary
    search: per-row dynamic gathers scalarize on TPU (round-5 device
    profile), while the O(L*W) compare-and-sum is pure vector work that
    XLA fuses without materializing the [n, L, W] intermediate.
    """
    return (a[:, :, None] <= v[:, None, :]).sum(axis=1, dtype=_I32)


@jax.jit
def byte_info_device(b, lens, st_before):
    """Port of _byte_info's numpy passes (the automaton result is shared)."""
    n, L = b.shape

    in_dq = st_before == jt._S_DQ
    in_sq = st_before == jt._S_SQ
    cls_esc_all = (st_before == jt._S_DQE) | (st_before == jt._S_SQE)
    cls_u = cls_esc_all & (b == ord("u"))
    cls_esc = cls_esc_all & ~cls_u
    cls_hex = jnp.zeros_like(cls_u)
    for k in range(1, 5):
        cls_hex = cls_hex.at[:, k:].set(cls_hex[:, k:] | cls_u[:, :-k])
    close_q = (in_dq & (b == ord('"'))) | (in_sq & (b == ord("'")))

    d = b.astype(_I32)
    hexval = jnp.zeros(b.shape, _I32)
    hexval = jnp.where((b >= ord("0")) & (b <= ord("9")), d - ord("0"), hexval)
    hexval = jnp.where((b >= ord("a")) & (b <= ord("f")), d - ord("a") + 10,
                       hexval)
    hexval = jnp.where((b >= ord("A")) & (b <= ord("F")), d - ord("A") + 10,
                       hexval)
    cp = jnp.zeros(b.shape, _I32)
    for k in range(1, 5):
        sh = jnp.zeros(b.shape, _I32)
        sh = sh.at[:, :-k].set(hexval[:, k:])
        cp = cp | (sh << (4 * (4 - k)))
    ulen = jnp.where(cp < 0x80, 1, jnp.where(cp < 0x800, 2, 3)).astype(_I32)

    normal = (in_dq | in_sq) & ~((in_dq | in_sq) & (b == ord("\\"))) \
        & ~close_q & ~cls_hex
    is_ctrl = normal & (b < 32)
    short_ctrl = is_ctrl & (_CTRL_SHORT_J[jnp.minimum(b, _U8(31))] != 0)

    len_u = jnp.zeros(b.shape, _I32)
    len_u = jnp.where(normal, 1, len_u)
    len_u = jnp.where(cls_esc, 1, len_u)
    len_u = jnp.where(cls_u, ulen, len_u)

    len_e = jnp.zeros(b.shape, _I32)
    len_e = jnp.where(normal, 1, len_e)
    len_e = jnp.where(normal & (b == ord('"')), 2, len_e)
    len_e = jnp.where(short_ctrl, 2, len_e)
    len_e = jnp.where(is_ctrl & ~short_ctrl, 6, len_e)
    two_byte = (b == ord('"')) | (b == ord("\\"))
    for ch in b"bfnrt":
        two_byte = two_byte | (b == ch)
    len_e = jnp.where(cls_esc, jnp.where(two_byte, 2, 1), len_e)
    len_e = jnp.where(cls_u, ulen, len_e)

    def excl_cum(x):
        return jnp.pad(jnp.cumsum(x.astype(_I64), axis=1), ((0, 0), (1, 0)))

    return DByteInfo(
        b=b, cls_esc=cls_esc, cls_u=cls_u, cp=cp, ulen=ulen, len_e=len_e,
        cum_u=excl_cum(len_u), cum_e=excl_cum(len_e),
        cum_uni=excl_cum(cls_u.astype(_I64)),
    )


def _utf8_byte(cp, ulen, k):
    b1 = jnp.where(ulen == 1, cp,
                   jnp.where(ulen == 2, 0xC0 | (cp >> 6), 0xE0 | (cp >> 12)))
    b2 = jnp.where(ulen == 2, 0x80 | (cp & 0x3F), 0x80 | ((cp >> 6) & 0x3F))
    b3 = 0x80 | (cp & 0x3F)
    return jnp.where(k == 0, b1, jnp.where(k == 1, b2, b3)).astype(_U8)


def _emission_byte(bi: DByteInfo, ri, si, k, escaped: bool):
    """Device port of get_json_object._emission_byte (same case logic).

    ``ri`` is retained for signature stability but unused: all source
    reads go through the one-hot ``_take_rows`` (si is row-aligned).
    """
    del ri
    c = _take_rows(bi.b, si)
    u = _take_rows(bi.cls_u, si)
    esc = _take_rows(bi.cls_esc, si)
    if not escaped:
        out = jnp.where(esc, _UNESC_J[c], c)
        out = jnp.where(u, _utf8_byte(_take_rows(bi.cp, si),
                                      _take_rows(bi.ulen, si), k), out)
        return out.astype(_U8)
    is_ctrl = c < 32
    short = jnp.where(is_ctrl, _CTRL_SHORT_J[jnp.minimum(c, _U8(31))], _U8(0))
    long_bytes = jnp.select(
        [k == 0, k == 1, k == 2, k == 3, k == 4],
        [jnp.full(c.shape, ord("\\"), _U8), jnp.full(c.shape, ord("u"), _U8),
         jnp.full(c.shape, ord("0"), _U8), jnp.full(c.shape, ord("0"), _U8),
         jnp.where(c >= 16, _U8(ord("1")), _U8(ord("0")))],
        default=_HEX_UP_J[c % 16],
    )
    ctrl_out = jnp.where(short != 0,
                         jnp.where(k == 0, _U8(ord("\\")), short), long_bytes)
    norm_out = jnp.where(
        c == ord('"'),
        jnp.where(k == 0, _U8(ord("\\")), _U8(ord('"'))), c)
    out = jnp.where(is_ctrl, ctrl_out, norm_out)
    two = (c == ord('"')) | (c == ord("\\"))
    for ch in b"bfnrt":
        two = two | (c == ch)
    esc_out = jnp.where(two, jnp.where(k == 0, _U8(ord("\\")), c), _UNESC_J[c])
    esc_out = jnp.where((c == ord('"')) & (k == 1), _U8(ord('"')), esc_out)
    out = jnp.where(esc, esc_out, out)
    out = jnp.where(u, _utf8_byte(_take_rows(bi.cp, si),
                                  _take_rows(bi.ulen, si), k), out)
    return out.astype(_U8)


@jax.jit
def token_tables_device(bi: DByteInfo, kind, start, end):
    """Device port of _token_tables."""
    n, T = kind.shape
    L = bi.b.shape[1]
    s64 = start.astype(_I64)
    e64 = end.astype(_I64)

    is_str = (kind == jt.VALUE_STRING) | (kind == jt.FIELD_NAME)
    ps = jnp.minimum(s64 + 1, L)
    pe = jnp.clip(e64 - 1, 0, L)
    pay_u = _take_rows(bi.cum_u, pe) - _take_rows(bi.cum_u, ps)
    pay_e = _take_rows(bi.cum_e, pe) - _take_rows(bi.cum_e, ps)
    has_uni = (_take_rows(bi.cum_uni, pe) - _take_rows(bi.cum_uni, ps)) > 0

    span = e64 - s64
    is_int = kind == jt.VALUE_NUMBER_INT
    neg0 = is_int & (span == 2) \
        & (_take_rows(bi.b, jnp.minimum(s64, L - 1)) == ord("-")) \
        & (_take_rows(bi.b, jnp.minimum(s64 + 1, L - 1)) == ord("0"))

    one = (kind == jt.START_OBJECT) | (kind == jt.END_OBJECT) | \
        (kind == jt.START_ARRAY) | (kind == jt.END_ARRAY)
    len_raw = jnp.zeros((n, T), _I64)
    len_esc = jnp.zeros((n, T), _I64)
    len_raw = jnp.where(one, 1, len_raw)
    len_raw = jnp.where(kind == jt.VALUE_TRUE, 4, len_raw)
    len_raw = jnp.where(kind == jt.VALUE_FALSE, 5, len_raw)
    len_raw = jnp.where(kind == jt.VALUE_NULL, 4, len_raw)
    len_raw = jnp.where(is_int, jnp.where(neg0, 1, span), len_raw)
    len_esc = jnp.where(one | (kind == jt.VALUE_TRUE) | (kind == jt.VALUE_FALSE)
                        | (kind == jt.VALUE_NULL) | is_int, len_raw, len_esc)
    len_raw = jnp.where(is_str, pay_u, len_raw)
    len_esc = jnp.where(is_str, pay_e + 2, len_esc)
    return len_raw, len_esc, has_uni, neg0


@functools.partial(jax.jit, static_argnums=(6,))
def _name_match_one(bi: DByteInfo, kind, start, len_raw, has_uni, end,
                    name: bytes):
    """[n, T] bool: token payload unescapes to exactly ``name``.

    Two paths, selected PER ROW:

    - **fast** (no escape in the row's candidate payloads, the
      overwhelmingly common case): a [n, L] match table built from
      ``len(name)`` static byte-shift compares (pure vector ops), then a
      single gather per token.  A payload with no escapes and no unicode
      emits its raw bytes verbatim, so raw-width == m plus byte equality
      is exact.
    - **slow** (the row has a candidate with a 2-byte escape): the
      original per-character searchsorted walk through the cum_u
      emission mapping.

    The round-5 device profile showed the searchsorted walk was 64% of a
    warm get_json_object call on the v5e (134 s of 208 s at 2^18 rows) —
    per-(token, char) gathers scalarize on TPU.  The fast path replaces
    ~8 gather rounds per character with one gather per name.  Selection
    is per-row (an outer ``lax.cond`` still skips the slow walk entirely
    when NO row needs it): one escaped field name routes only ITS row
    through the escape-aware walk — every clean row keeps the fast
    table result, instead of the whole batch changing path.
    """
    n, T = kind.shape
    L = bi.b.shape[1]
    # FIELD_NAME only: name matches are consumed solely at field-name
    # tokens (the object-field step), and gating on string VALUES too
    # would let a common escaped value route rows down the slow path;
    # the host matcher (get_json_object.py _name_matches) is narrowed
    # identically — the fuzz tier asserts parity on these tables.
    is_str = kind == jt.FIELD_NAME
    m = len(name)
    ok = is_str & ~has_uni & (len_raw == m)
    if m == 0:
        return ok
    ps = jnp.minimum(start.astype(_I64) + 1, L)
    raw_w = end.astype(_I64) - start.astype(_I64) - 2  # quoted payload width
    no_esc = raw_w == m  # every non-unicode escape shrinks 2 raw -> 1 emitted
    # rows with an escaped same-emitted-width candidate (the only tokens
    # where fast and slow can disagree)
    need_slow = jnp.any(ok & ~no_esc, axis=1)

    def fast(_):
        bpad = jnp.pad(bi.b, ((0, 0), (0, m)))
        table = jnp.ones((n, L), bool)
        for q, ch in enumerate(name):
            table = table & (bpad[:, q:q + L] == ch)
        hit = _take_rows(table, jnp.minimum(ps, L - 1))
        return ok & no_esc & hit

    def mixed(_):
        base = _take_rows(bi.cum_u, ps)
        acc = ok
        for q, ch in enumerate(name):
            tgt = base + q
            si = jnp.minimum(_searchsorted_rows(bi.cum_u[:, 1:], tgt), L - 1)
            k = (tgt - _take_rows(bi.cum_u, si)).astype(_I32)
            got = _emission_byte(bi, None, si, k, escaped=False)
            acc = acc & (got == ch)
        return jnp.where(need_slow[:, None], acc, fast(0))

    return jax.lax.cond(jnp.any(need_slow), mixed, fast, 0)


def name_matches_device(bi, kind, start, len_raw, has_uni, end, names):
    return [
        jnp.zeros(kind.shape, bool) if nm is None
        else _name_match_one(bi, kind, start, len_raw, has_uni, end, nm)
        for nm in names
    ]


# ---------------------------------------------------------------- floats ---

_FLOAT_W = 32  # Double.toString max ~24 chars + quoted-Infinity room


@functools.partial(jax.jit, static_argnums=(4, 5))
def _float_gather(b, kind, start, end, NF: int, WS: int):
    """Compact float-token source texts into [NF, WS] byte slots."""
    n, T = kind.shape
    L = b.shape[1]
    fmask = kind == jt.VALUE_NUMBER_FLOAT
    rank = (jnp.cumsum(fmask.reshape(-1).astype(_I64)) - 1).reshape(n, T)
    fidx = jnp.where(fmask, rank, -1)

    slot = jnp.where(fmask, rank, NF).reshape(-1)
    rows2d = jnp.broadcast_to(jnp.arange(n, dtype=_I64)[:, None], (n, T))
    frow = jnp.zeros((NF,), _I64).at[slot].set(rows2d.reshape(-1), mode="drop")
    fs = jnp.zeros((NF,), _I64).at[slot].set(
        start.astype(_I64).reshape(-1), mode="drop")
    fe = jnp.zeros((NF,), _I64).at[slot].set(
        end.astype(_I64).reshape(-1), mode="drop")

    lane = jnp.arange(WS, dtype=_I64)[None, :]
    src = jnp.clip(fs[:, None] + lane, 0, L - 1)
    # whole-row gather (contiguous, embedding-shaped — TPU-friendly),
    # then the one-hot in-row read; the fused 2-D b[frow, src] gather
    # scalarized (round-5 profile: 4.4 s of the warm call)
    raw = _take_rows(b[frow], src)
    flen_src = (fe - fs).astype(_I32)
    raw = jnp.where(lane < flen_src[:, None], raw, _U8(0))
    return raw, flen_src, fidx


@jax.jit
def _float_render(bits):
    """Ryu digits + Java formatting of parsed float bits, with the
    quoted-Infinity quirk (ftos_converter.cuh:1154)."""
    from spark_rapids_jni_tpu.ops.float_to_string import _d2d, _emit_fast

    u = bits.astype(jnp.uint64)
    mant = u & jnp.uint64((1 << 52) - 1)
    expo = (u >> jnp.uint64(52)) & jnp.uint64(0x7FF)
    is_nan = (expo == 0x7FF) & (mant != 0)
    is_inf = (expo == 0x7FF) & (mant == 0)
    is_zero = (expo == 0) & (mant == 0)
    negative = bits < 0
    output, e10 = _d2d(u)
    special_id = jnp.where(
        is_nan, _I32(4),
        jnp.where(is_inf, jnp.where(negative, _I32(3), _I32(2)),
                  jnp.where(is_zero,
                            jnp.where(negative, _I32(1), _I32(0)), _I32(-1))))
    # the strength-reduced renderer (byte-identical to the _emit oracle,
    # which compiles far slower on the TPU)
    padded, lens = _emit_fast(output, e10, negative, special_id, False)
    lens = lens.astype(_I64)

    # quoted-Infinity: shift right by one and wrap in quotes
    out_len = jnp.where(is_inf, lens + 2, lens)
    lane_w = jnp.arange(_FLOAT_W, dtype=_I64)[None, :]
    wide = jnp.pad(padded, ((0, 0), (0, max(_FLOAT_W - padded.shape[1], 0))))
    wide = wide[:, :_FLOAT_W]
    # a shift by 0 or 1 lane is a select between two static slices, not a
    # per-row gather; lanes outside the text are masked below either way
    shifted = jnp.concatenate([wide[:, :1], wide[:, :-1]], axis=1)
    gathered = jnp.where(is_inf[:, None], shifted, wide)
    in_text = (lane_w >= is_inf[:, None]) & \
        (lane_w < (lens + is_inf)[:, None])
    ftext = jnp.where(in_text, gathered, _U8(0))
    quote_pos = is_inf[:, None] & ((lane_w == 0) |
                                   (lane_w == out_len[:, None] - 1))
    ftext = jnp.where(quote_pos, _U8(ord('"')), ftext)
    return ftext, out_len


def float_texts_device(b, kind, start, end, NF: int, WS: int):
    """Device float re-rendering with a static float-slot count.

    Returns (ftext [NF, _FLOAT_W] uint8, flen [NF] int64, fidx [n, T] int64).
    Slots beyond the real float count are zero.  Parsing is the Spark-exact
    device parse; rendering is the Ryu digit core.

    Composed of three separately-jitted stages (gather -> parse -> render)
    so each compiles once per NF geometry and the parse/render modules are
    shared across buckets — one fused module was a pathological XLA compile.
    """
    from spark_rapids_jni_tpu.ops.cast_string_to_float import (
        _assemble_device,
        _scan_padded_jit,
        _SCAN_FIELDS,
    )

    raw, flen_src, fidx = _float_gather(b, kind, start, end, NF, WS)
    # full-width exponent reading (the 4-digit cap is a cast quirk)
    fields = _scan_padded_jit(raw, flen_src, WS)
    fdict = {k: v for (k, _), v in zip(_SCAN_FIELDS, fields)}
    bits, _valid, _exc = _assemble_device(fdict)
    ftext, out_len = _float_render(bits)
    return ftext, out_len, fidx


# ---------------------------------------------------------------- render ---


@jax.jit
def resolve_and_measure(segs, close_grp, close_dirty, close_nc, err,
                        kind, len_raw, len_esc, fidx, flen):
    """Resolve case-6 conditionals + per-segment lengths + output lengths.

    ``segs``: [S, n, 2, 2] scan outputs.  Returns (stype, sarg, slen [n, 2S],
    out_len [n]).
    """
    S, n = segs.shape[0], segs.shape[1]
    allseg = jnp.transpose(segs, (1, 0, 2, 3)).reshape(n, S * 2, 2)
    stype = allseg[:, :, 0]
    sarg = allseg[:, :, 1]

    # close events -> per-(row, open-step) dirty/nc tables (device scatter)
    rowsSn = jnp.broadcast_to(jnp.arange(n, dtype=_I32)[None, :], (S, n))
    g = jnp.where(close_grp >= 0, close_grp, S)
    res_dirty = jnp.zeros((n, S + 1), _I32).at[
        rowsSn.reshape(-1), g.reshape(-1)].set(
        close_dirty.reshape(-1), mode="drop")
    res_nc = jnp.zeros((n, S + 1), bool).at[
        rowsSn.reshape(-1), g.reshape(-1)].set(
        close_nc.reshape(-1), mode="drop")
    res_seen = jnp.zeros((n, S + 1), bool).at[
        rowsSn.reshape(-1), g.reshape(-1)].set(True, mode="drop")

    is_open = stype == _SEG_COND_OPEN
    is_close = stype == _SEG_COND_CLOSE
    gi = jnp.clip(sarg, 0, S)
    seen = _take_rows(res_seen, gi)
    d = _take_rows(res_dirty, gi)
    nc = _take_rows(res_nc, gi)
    open_id = jnp.where(
        d > 1, jnp.where(nc, _CONSTS.index(b",["), _CONSTS.index(b"[")),
        jnp.where((d == 1) & nc, _CONSTS.index(b","), _CONSTS.index(b"")))
    close_id = jnp.where(d > 1, _CONSTS.index(b"]"), _CONSTS.index(b""))
    sarg = jnp.where(is_open & seen, open_id, sarg)
    stype = jnp.where(is_open & seen, _SEG_CONST, stype)
    sarg = jnp.where(is_close & seen, close_id, sarg)
    stype = jnp.where(is_close & seen, _SEG_CONST, stype)
    unres = (stype == _SEG_COND_OPEN) | (stype == _SEG_COND_CLOSE)
    stype = jnp.where(unres, 0, stype)

    T = kind.shape[1]
    targ = jnp.clip(sarg, 0, T - 1)
    slen = jnp.zeros((n, S * 2), _I64)
    slen = jnp.where(stype == _SEG_CONST,
                     _CONST_LEN_J[jnp.clip(sarg, 0, len(_CONSTS) - 1)], slen)
    slen = jnp.where(stype == _SEG_RAW_TOK, _take_rows(len_raw, targ), slen)
    slen = jnp.where(stype == _SEG_ESC_TOK, _take_rows(len_esc, targ), slen)
    is_float_tok = _take_rows(kind, targ) == jt.VALUE_NUMBER_FLOAT
    tok_ref = (stype == _SEG_RAW_TOK) | (stype == _SEG_ESC_TOK)
    f_sel = tok_ref & is_float_tok
    NF = flen.shape[0]
    fi = jnp.clip(_take_rows(fidx, targ), 0, max(NF - 1, 0))
    if NF:
        slen = jnp.where(f_sel, flen[fi], slen)

    segcum = jnp.cumsum(slen, axis=1)
    out_len = jnp.where(err, 0, segcum[:, -1])
    return stype, sarg, segcum, out_len


@functools.partial(jax.jit, static_argnums=(11,))
def render_device(bi: DByteInfo, stype, sarg, segcum, out_len, err,
                  kind, start, end, tok_tabs, floats, W: int):
    """Materialize output bytes [n, W] from resolved segments (device port
    of _render's emission pass)."""
    len_raw, len_esc, neg0 = tok_tabs
    ftext, flen, fidx = floats
    n = stype.shape[0]
    T = kind.shape[1]
    L = bi.b.shape[1]
    S2 = stype.shape[1]

    j = jnp.broadcast_to(jnp.arange(W, dtype=_I64)[None, :], (n, W))
    si = jnp.minimum(_searchsorted_rows(segcum, j), S2 - 1)
    prev = jnp.where(si > 0, _take_rows(segcum, jnp.maximum(si - 1, 0)), 0)
    d = j - prev
    st = _take_rows(stype, si)
    sa = _take_rows(sarg, si)
    ta = jnp.clip(sa, 0, T - 1)
    tk = _take_rows(kind, ta)
    ts = _take_rows(start, ta).astype(_I64)

    out = jnp.zeros((n, W), _U8)
    cm = st == _SEG_CONST
    out = jnp.where(cm, _CONST_TAB_J[jnp.clip(sa, 0, len(_CONSTS) - 1),
                                     jnp.clip(d, 0, _CONST_MAXLEN - 1)], out)

    is_str = (tk == jt.VALUE_STRING) | (tk == jt.FIELD_NAME)
    is_int = tk == jt.VALUE_NUMBER_INT
    is_float = tk == jt.VALUE_NUMBER_FLOAT
    one_char = (tk == jt.START_OBJECT) | (tk == jt.END_OBJECT) | \
        (tk == jt.START_ARRAY) | (tk == jt.END_ARRAY)
    lit = (tk == jt.VALUE_TRUE) | (tk == jt.VALUE_FALSE) | \
        (tk == jt.VALUE_NULL)
    tokm = (st == _SEG_RAW_TOK) | (st == _SEG_ESC_TOK)
    escm = st == _SEG_ESC_TOK

    im = tokm & is_int
    n0 = _take_rows(neg0, ta)
    src_byte = _take_rows(bi.b, jnp.clip(ts + d, 0, L - 1))
    out = jnp.where(im, jnp.where(n0, _U8(ord("0")), src_byte), out)
    sm = tokm & (one_char | lit)
    out = jnp.where(sm, src_byte, out)

    NF = flen.shape[0]
    if NF:
        fm = tokm & is_float
        fi2 = jnp.clip(_take_rows(fidx, ta), 0, NF - 1)
        out = jnp.where(
            fm, ftext[fi2, jnp.clip(d, 0, ftext.shape[1] - 1)], out)

    strm = tokm & is_str
    ps = jnp.minimum(ts + 1, L)
    # raw (unescape) variant
    rm = strm & ~escm
    base_u = _take_rows(bi.cum_u, ps)
    tgt = base_u + d
    siU = jnp.minimum(_searchsorted_rows(bi.cum_u[:, 1:], tgt), L - 1)
    kU = (tgt - _take_rows(bi.cum_u, siU)).astype(_I32)
    rbyte = _emission_byte(bi, None, siU, kU, False)
    out = jnp.where(rm, rbyte, out)
    # escaped variant: quote + payload + quote
    em = strm & escm
    elen = _take_rows(len_esc, ta)
    quote = (d == 0) | (d == elen - 1)
    base_e = _take_rows(bi.cum_e, ps)
    tgt_e = jnp.maximum(base_e + (d - 1), 0)
    siE = jnp.minimum(_searchsorted_rows(bi.cum_e[:, 1:], tgt_e), L - 1)
    kE = (tgt_e - _take_rows(bi.cum_e, siE)).astype(_I32)
    ebyte = _emission_byte(bi, None, siE, kE, True)
    out = jnp.where(em, jnp.where(quote, _U8(ord('"')), ebyte), out)

    in_bounds = j < out_len[:, None]
    return jnp.where(in_bounds, out, _U8(0))
