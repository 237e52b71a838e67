"""Pallas TPU kernels for the murmur3 fixed-width hot path.

The XLA path in ``hashing.py`` expresses the Spark murmur3 chain as ~10
fused elementwise u32 ops; XLA handles that well, but it leaves tiling to
the compiler and re-materializes the running-hash vector between column
contributions at HBM.  These kernels express one column *contribution*
(running hash in, updated hash out — the unit from which
``murmur_hash32`` chains columns, reference murmur_hash.cu:44-48) as a
single VMEM-resident Pallas kernel:

- ``mm_hash_int_pallas``  == hashing._mm_hash_int  (one 4-byte round + fmix)
- ``mm_hash_long_pallas`` == hashing._mm_hash_long (two rounds + fmix)

Everything is uint32 lane arithmetic — no 64-bit types enter the kernel
(the TPU x64 rewrite has no 64-bit bitcast; int64 inputs are split into
u32 limbs *outside* with plain shifts, which the rewrite does support).

Off-TPU the kernels run in Pallas interpret mode, so correctness is
CI-testable on the CPU mesh; selection is via the ``hash_backend`` config
flag ("xla" default, "pallas" to route murmur3 fixed-width contributions
here).  No benchmark cell measures the two backends yet; an A/B on
hardware belongs in ``benchmark/``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_U32 = jnp.uint32

# Index-map constants must be explicitly 32-bit: under jax_enable_x64 a
# plain Python ``0`` lowers as i64 and Mosaic rejects the index-map
# function ("failed to legalize 'func.return' (i32, i64)") — reproduced
# and fixed against the live v5e backend (round 5).
_I0 = np.int32(0)

# VMEM block: 512 sublane-rows x 128 lanes of u32 = 256 KiB per operand.
_BLOCK_ROWS = 512
_LANES = 128
_TILE = _BLOCK_ROWS * _LANES


def _use_interpret() -> bool:
    # Interpret mode exists for the CPU test mesh only: on any accelerator
    # the kernel lowers through Mosaic or fails loudly.
    return jax.default_backend() == "cpu"


# ---- kernel bodies (u32 lane math, mirrors hashing.py primitives) --------


def _mix_k1(k1):
    k1 = k1 * _U32(0xCC9E2D51)
    k1 = (k1 << _U32(15)) | (k1 >> _U32(17))
    return k1 * _U32(0x1B873593)


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = (h1 << _U32(13)) | (h1 >> _U32(19))
    return h1 * _U32(5) + _U32(0xE6546B64)


def _fmix(h, length_u32):
    h = h ^ length_u32
    h = h ^ (h >> _U32(16))
    h = h * _U32(0x85EBCA6B)
    h = h ^ (h >> _U32(13))
    h = h * _U32(0xC2B2AE35)
    return h ^ (h >> _U32(16))


# ---- u64-as-u32-limb-pair arithmetic (xxhash64 kernels) -------------------
# No 64-bit types exist inside Mosaic on this TPU; every u64 op is spelled
# in u32 lanes, multiplies via 16-bit splits (four 16x16->32 partials).
# Constants stay PYTHON ints (Pallas rejects closed-over array constants);
# the helpers accept int or u32-array operands interchangeably.


def _lo16(x):
    return x & 0xFFFF


def _hi16(x):
    return x >> 16


def _mul32_full(a, b):
    """(hi, lo) u32 pair = full 64-bit product of two u32 lanes/ints."""
    a0, a1 = _lo16(a), _hi16(a)
    b0, b1 = _lo16(b), _hi16(b)
    p00 = a0 * b0
    p01 = a0 * b1
    p10 = a1 * b0
    p11 = a1 * b1
    mid = _hi16(p00) + _lo16(p01) + _lo16(p10)
    lo = _lo16(p00) | (_lo16(mid) << 16)
    hi = p11 + _hi16(p01) + _hi16(p10) + _hi16(mid)
    return hi, lo


def _mul64(ah, al, bh, bl):
    """Low 64 bits of a 64x64 product, as a (hi, lo) u32 pair."""
    hi, lo = _mul32_full(al, bl)
    return hi + al * bh + ah * bl, lo


def _add64(ah, al, bh, bl):
    lo = al + bl
    carry = (lo < al).astype(_U32)
    return ah + bh + carry, lo


def _rotl64(h, l, r: int):
    if r == 32:
        return l, h
    if r < 32:
        return ((h << r) | (l >> (32 - r)), (l << r) | (h >> (32 - r)))
    r -= 32
    return ((l << r) | (h >> (32 - r)), (h << r) | (l >> (32 - r)))


def _shr64(h, l, r: int):
    if r == 32:
        return jnp.zeros_like(h), h
    if r < 32:
        return h >> r, (l >> r) | (h << (32 - r))
    return jnp.zeros_like(h), h >> (r - 32)


def _xor64(ah, al, bh, bl):
    return ah ^ bh, al ^ bl


def _p(v: int):
    return v >> 32, v & 0xFFFFFFFF


# xxhash64 primes as (hi, lo) int pairs (hashing.py _XX_P*)
_XP1 = _p(0x9E3779B185EBCA87)
_XP2 = _p(0xC2B2AE3D27D4EB4F)
_XP3 = _p(0x165667B19E3779F9)
_XP4 = _p(0x85EBCA77C2B2AE63)
_XP5_PLUS_4 = _p((0x27D4EB2F165667C5 + 4) & ((1 << 64) - 1))
_XP5_PLUS_8 = _p((0x27D4EB2F165667C5 + 8) & ((1 << 64) - 1))


def _xx_finalize_pair(h, l):
    h, l = _xor64(h, l, *_shr64(h, l, 33))
    h, l = _mul64(h, l, *_XP2)
    h, l = _xor64(h, l, *_shr64(h, l, 29))
    h, l = _mul64(h, l, *_XP3)
    return _xor64(h, l, *_shr64(h, l, 32))


def _xx4_kernel(v_ref, sh_ref, sl_ref, oh_ref, ol_ref):
    """xxhash64 of one 4-byte value per lane (hashing._xx_hash_fixed4)."""
    h, l = _add64(sh_ref[:], sl_ref[:], *_XP5_PLUS_4)
    wh, wl = _mul64(jnp.zeros_like(h), v_ref[:], *_XP1)
    h, l = _xor64(h, l, wh, wl)
    h, l = _rotl64(h, l, 23)
    h, l = _mul64(h, l, *_XP2)
    h, l = _add64(h, l, *_XP3)
    oh_ref[:], ol_ref[:] = _xx_finalize_pair(h, l)


def _xx8_kernel(vh_ref, vl_ref, sh_ref, sl_ref, oh_ref, ol_ref):
    """xxhash64 of one 8-byte value per lane (hashing._xx_hash_fixed8)."""
    h, l = _add64(sh_ref[:], sl_ref[:], *_XP5_PLUS_8)
    kh, kl = _mul64(vh_ref[:], vl_ref[:], *_XP2)
    kh, kl = _rotl64(kh, kl, 31)
    kh, kl = _mul64(kh, kl, *_XP1)
    h, l = _xor64(h, l, kh, kl)
    h, l = _rotl64(h, l, 27)
    h, l = _mul64(h, l, *_XP1)
    h, l = _add64(h, l, *_XP4)
    oh_ref[:], ol_ref[:] = _xx_finalize_pair(h, l)


def _int_kernel(v_ref, h_ref, out_ref):
    out_ref[:] = _fmix(_mix_h1(h_ref[:], _mix_k1(v_ref[:])), _U32(4))


def _long_kernel(lo_ref, hi_ref, h_ref, out_ref):
    h = _mix_h1(h_ref[:], _mix_k1(lo_ref[:]))
    h = _mix_h1(h, _mix_k1(hi_ref[:]))
    out_ref[:] = _fmix(h, _U32(8))


# ---- blocking helpers -----------------------------------------------------


def _block_rows_for(n: int) -> int:
    """Row-block height for n elements: full _BLOCK_ROWS for large inputs,
    a pow2-rounded smaller block for small ones so a few-hundred-row
    length bucket doesn't pad (and compute over) a 65k-lane tile."""
    rows_needed = max(1, -(-n // _LANES))
    return min(_BLOCK_ROWS, max(8, 1 << (rows_needed - 1).bit_length()))


def _to_blocks(x, dtype, block_rows: int) -> jnp.ndarray:
    """[n] -> [R, 128] of ``dtype``, R a multiple of block_rows (0-pad)."""
    x = jnp.asarray(x, dtype)
    n = x.shape[0]
    pad = (-n) % (block_rows * _LANES)
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, _LANES)


_KERNELS = {  # name -> (kernel_fn, n_outputs); one launch scaffold for all
    "mm_int": (_int_kernel, 1),
    "mm_long": (_long_kernel, 1),
    "xx4": (_xx4_kernel, 2),
    "xx8": (_xx8_kernel, 2),
}


@functools.partial(jax.jit, static_argnames=("kern",))
def _launch(kern, *flat_u32):
    """Shared row-block launch scaffold for every elementwise hash kernel:
    one place owns block sizing, VMEM specs, grid, and interpret gating."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel, n_out = _KERNELS[kern]
    br = _block_rows_for(flat_u32[0].shape[0])
    blocks = [_to_blocks(x, _U32, br) for x in flat_u32]
    rows = blocks[0].shape[0]
    spec = pl.BlockSpec((br, _LANES), lambda i: (i, _I0),
                        memory_space=pltpu.VMEM)
    shape = jax.ShapeDtypeStruct((rows, _LANES), _U32)
    out = pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[spec] * len(blocks),
        out_specs=spec if n_out == 1 else (spec,) * n_out,
        out_shape=shape if n_out == 1 else (shape,) * n_out,
        interpret=_use_interpret(),
    )(*blocks)
    if n_out == 1:
        return out.reshape(-1)
    return tuple(o.reshape(-1) for o in out)


def _bytes_words_kernel(words_ref, h_ref, nw_ref, out_ref):
    """One murmur word round for one (row-block, word) grid step.

    TPU grids execute sequentially with the word index as the
    fastest-varying dimension, so ``out_ref`` (same block for every w of a
    row block) carries the running hash across the whole word loop in
    VMEM — the lax.scan path re-materializes that carry through the XLA
    loop instead.
    """
    import jax.experimental.pallas as pl

    w = pl.program_id(1)

    @pl.when(w == 0)
    def _():
        out_ref[:] = h_ref[:]

    word = words_ref[0]
    h = out_ref[:]
    upd = _mix_h1(h, _mix_k1(word))
    out_ref[:] = jnp.where(w < nw_ref[:], upd, h)


def mm_bytes_words_pallas(words: jnp.ndarray, nwords: jnp.ndarray,
                          h_u32: jnp.ndarray) -> jnp.ndarray:
    """All aligned-word murmur rounds of hashUnsafeBytes as one Pallas
    kernel: ``words`` [n, Lw] u32, ``nwords`` [n] valid-word counts,
    ``h_u32`` [n] running hashes -> updated [n] hashes.  The <=3 tail-byte
    rounds + fmix stay in the caller (hashing._mm_bytes_tail)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, lw = words.shape
    if n == 0 or lw == 0:
        return jnp.broadcast_to(jnp.asarray(h_u32, _U32), (n,))

    br = _block_rows_for(n)
    h2 = _to_blocks(jnp.broadcast_to(jnp.asarray(h_u32, _U32), (n,)),
                    _U32, br)
    nw2 = _to_blocks(nwords, jnp.int32, br)
    rows = h2.shape[0]
    # words -> [Lw, R, 128] so each grid step streams one word-column block
    wpad = jnp.pad(words, ((0, rows * _LANES - n), (0, 0)))
    w3 = wpad.T.reshape(lw, rows, _LANES)

    row_spec = pl.BlockSpec((br, _LANES), lambda i, w: (i, _I0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _bytes_words_kernel,
        grid=(rows // br, lw),
        in_specs=[
            pl.BlockSpec((1, br, _LANES), lambda i, w: (w, i, _I0),
                         memory_space=pltpu.VMEM),
            row_spec,
            row_spec,
        ],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), _U32),
        interpret=_use_interpret(),
    )(w3, h2, nw2)
    return out.reshape(-1)[:n]





def _seed_limbs(seed, n):
    s = jnp.broadcast_to(jnp.asarray(seed, jnp.uint64), (n,))
    return ((s >> jnp.uint64(32)).astype(_U32),
            (s & jnp.uint64(0xFFFFFFFF)).astype(_U32))


def _pair_to_u64(oh, ol, n):
    return ((oh[:n].astype(jnp.uint64) << jnp.uint64(32))
            | ol[:n].astype(jnp.uint64))


def xx_hash_fixed4_pallas(v_u32: jnp.ndarray, seed) -> jnp.ndarray:
    """Pallas twin of hashing._xx_hash_fixed4; all 64-bit arithmetic runs
    as u32 limb pairs in VMEM (16-bit-split multiplies) instead of the
    XLA x64 rewrite's generic emulation."""
    n = v_u32.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint64)
    sh, sl = _seed_limbs(seed, n)
    oh, ol = _launch("xx4", v_u32.astype(_U32), sh, sl)
    return _pair_to_u64(oh, ol, n)


def xx_hash_fixed8_pallas(v_u64: jnp.ndarray, seed) -> jnp.ndarray:
    """Pallas twin of hashing._xx_hash_fixed8 (8-byte values)."""
    n = v_u64.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.uint64)
    v = jnp.asarray(v_u64, jnp.uint64)
    vh = (v >> jnp.uint64(32)).astype(_U32)
    vl = (v & jnp.uint64(0xFFFFFFFF)).astype(_U32)
    sh, sl = _seed_limbs(seed, n)
    oh, ol = _launch("xx8", vh, vl, sh, sl)
    return _pair_to_u64(oh, ol, n)


def mm_hash_int_pallas(v_i32: jnp.ndarray, h_u32: jnp.ndarray) -> jnp.ndarray:
    """Pallas twin of hashing._mm_hash_int (Spark Murmur3.hashInt round)."""
    n = v_i32.shape[0]
    if n == 0:
        return jnp.zeros((0,), _U32)
    h = jnp.broadcast_to(jnp.asarray(h_u32, _U32), (n,))  # scalar seeds ok
    return _launch("mm_int", v_i32.astype(_U32), h)[:n]


def mm_hash_long_pallas(v_i64: jnp.ndarray, h_u32: jnp.ndarray) -> jnp.ndarray:
    """Pallas twin of hashing._mm_hash_long; 64-bit split happens out here
    (shifts only — safe under the u32-pair x64 rewrite)."""
    n = v_i64.shape[0]
    if n == 0:
        return jnp.zeros((0,), _U32)
    v = v_i64.astype(jnp.uint64)
    lo = (v & jnp.uint64(0xFFFFFFFF)).astype(_U32)
    hi = (v >> jnp.uint64(32)).astype(_U32)
    h = jnp.broadcast_to(jnp.asarray(h_u32, _U32), (n,))
    return _launch("mm_long", lo, hi, h)[:n]
