"""Spark-compatible hash kernels: murmur3_x86_32 and xxhash64.

These are the hash-partition / join primitives the north-star workload needs
(BASELINE.json: "xxhash64/murmur3 hash-partition"; in the reference lineage
they live in spark-rapids-jni's ``murmur_hash.cu``/``xxhash64.cu`` — not in
the mounted snapshot, which predates them, so these are built to the *Spark*
contract directly):

- ``murmur3_hash``: Spark's ``Murmur3Hash`` expression (seed 42), hashing
  each column value as its little-endian byte block(s) and chaining the
  result as the seed for the next column — bit-exact with Spark's
  ``Murmur3_x86_32`` for int/long/float/double/bool/decimal(64) inputs.
- ``xxhash64``: Spark's ``XxHash64`` expression (seed 42), same chaining.
- Strings hash their UTF-8 byte stream: murmur3 as Spark's
  ``hashUnsafeBytes`` (4-byte little-endian blocks, then each tail byte
  *sign-extended* and mixed as a full block), xxhash64 as ``XXH64``'s full
  byte-stream (32-byte accumulator chunks, 8-byte stripes, one 4-byte
  block, byte tail).  Vectorized over a dense ``[n, W]`` padded window (W =
  max string length in the column) with per-row length masking — no ragged
  loops, everything stays shape-static for XLA.

All arithmetic is lane-width uint32 (murmur3) so it vectorizes on the TPU
VPU without 64-bit lanes; xxhash64 runs on emulated uint32 pairs for the
same reason.  Everything is shape-static and fuses into one XLA program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.table import (
    Column, Table, column_nbytes,
    bytes2d_to_words as _bytes_to_u32_lanes,
)
from spark_rapids_jni_tpu.obs import span_fn, spans
from spark_rapids_jni_tpu.runtime import shapes
from spark_rapids_jni_tpu.utils import tracing


def _hash_attrs(table_or_cols, *args, **kwargs):
    cols = (table_or_cols.columns if isinstance(table_or_cols, Table)
            else tuple(table_or_cols))
    if not cols:
        return {}
    # input payload bytes feed the roofline cost model's achieved-GB/s
    return {"rows": cols[0].num_rows,
            "bytes": sum(column_nbytes(c) for c in cols)}

# np (not jnp) scalars: module import must never create a device array —
# an eager jnp constant here dispatches to the default backend at import
# time, and a CPU-only entry point must not touch (or claim) the chip.
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)
DEFAULT_SEED = 42


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _mm3_mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl32(k1, 15)
    return k1 * _C2


def _mm3_mix_h1(h1, k1):
    h1 = h1 ^ _mm3_mix_k1(k1)
    h1 = _rotl32(h1, 13)
    return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _mm3_fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def _canonical_floats(data):
    """-0.0 -> 0.0 and NaN -> canonical quiet NaN, as Java's
    floatToIntBits/doubleToLongBits produce for Spark."""
    data = jnp.where(data == 0.0, jnp.zeros_like(data), data)
    return jnp.where(jnp.isnan(data), jnp.full_like(data, jnp.nan), data)


def _canonical_f32_bits(data):
    """A float32 array's Spark-normalized bits as uint32."""
    return jax.lax.bitcast_convert_type(_canonical_floats(data), jnp.uint32)


def _canonical_f64_words(lo, hi):
    """A plane-pair double's Spark-normalized ``[lo, hi]`` words:
    -0.0 and NaN normalized at the bit level, so TPU (no-x64) hashes
    agree with the x64/Spark path."""
    exp_all_ones = (hi & jnp.uint32(0x7FF00000)) == jnp.uint32(0x7FF00000)
    mant_nonzero = ((hi & jnp.uint32(0x000FFFFF)) | lo) != 0
    is_nan = exp_all_ones & mant_nonzero
    is_negzero = (hi == jnp.uint32(0x80000000)) & (lo == 0)
    hi = jnp.where(is_nan, jnp.uint32(0x7FF80000),
                   jnp.where(is_negzero, jnp.uint32(0), hi))
    lo = jnp.where(is_nan | is_negzero, jnp.uint32(0), lo)
    return [lo, hi]


def _as_u32_words(col: Column):
    """A column's Spark-normalized little-endian uint32 words as a LIST
    of [n] vectors (lo word first).

    Spark normalizes: bool/byte/short/int -> int (one 4-byte block);
    long -> two blocks; float -> int bits; double -> long bits.
    Floats normalize -0.0 to 0.0 (Spark uses the raw bits of the value,
    but -0.0 == 0.0 normalization happens upstream in cudf/Spark hashing).
    64-bit columns are stored plane-major ([2, n] lo/hi), so their words
    are row slices — no interleave/transpose anywhere in the hash path.
    """
    data = col.data
    dt = col.dtype
    if dt.is_string:
        raise NotImplementedError(
            "string columns hash via the byte-stream kernel "
            "(_mm3_string_col); murmur3_hash dispatches there — this "
            "word-normalization helper covers fixed-width columns only")
    k = dt.np_dtype.itemsize
    if dt.np_dtype.kind == "f":
        if k == 8 and data.ndim == 2:
            return _canonical_f64_words(data[0], data[1])
        if k == 4:
            return [_canonical_f32_bits(data)]
        data = _canonical_floats(data)
        pair = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(data, jnp.uint64).reshape(-1, 1),
            jnp.uint32).reshape(-1, 2)
        return [pair[:, 0], pair[:, 1]]
    if data.ndim == 2:  # int64 plane pairs (64-bit without x64): raw bits
        return [data[0], data[1]]
    if k == 8:
        pair = jax.lax.bitcast_convert_type(
            data.reshape(-1, 1), jnp.uint32).reshape(-1, 2)
        return [pair[:, 0], pair[:, 1]]
    # bool/int8/int16/int32 -> sign-extend to int32, reinterpret
    as_i32 = data.astype(jnp.int32)
    return [jax.lax.bitcast_convert_type(as_i32, jnp.uint32)]


# ---------------------------------------------------------------------------
# String byte-stream windows
# ---------------------------------------------------------------------------

def _string_window(col: Column, W: int):
    """Dense padded byte window of a string column: uint8 [n, W] (zeros past
    each string's length) plus int32 lengths [n].  Dense-padded columns are
    a static slice/pad; Arrow columns fall back to a per-row slice-window
    gather (slow on TPU — hot paths should pass padded columns)."""
    return col.chars_window(W), col.str_lens()




def _byte_at(b: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    """Per-row byte at data-dependent position (clamped; callers mask)."""
    W = b.shape[1]
    idx = jnp.clip(pos, 0, W - 1)[:, None]
    return jnp.take_along_axis(b, idx, axis=1)[:, 0]


def _word_at(w: jnp.ndarray, pos: jnp.ndarray) -> jnp.ndarray:
    nw = w.shape[1]
    idx = jnp.clip(pos, 0, nw - 1)[:, None]
    return jnp.take_along_axis(w, idx, axis=1)[:, 0]


def _resolve_str_window(cols, max_str_len: Optional[int]) -> int:
    """Static W for the padded windows.

    Dense-padded columns carry their width statically (``chars2d.shape[1]``)
    so they resolve under jit/shard_map with no sync.  Arrow columns
    host-sync the offsets unless the caller provides ``max_str_len`` (the
    analogue of the reference's host sync before data-dependent kernel
    planning, ``row_conversion.cu:1521``)."""
    def _len_arr(c):  # offsets, or per-row lens for sharded padded columns
        return c.offsets if c.offsets is not None else c.lens

    from spark_rapids_jni_tpu.table import string_tail
    for col in cols:
        if col.dtype.is_string and getattr(col, "capped", False) \
                and (string_tail(col) is None
                     or isinstance(_len_arr(col), jax.core.Tracer)):
            # the flag survives tracing via pytree aux; the host tail
            # does not — and without it the hash of a capped row would
            # silently cover zero-truncated bytes
            raise ValueError(
                "hashing a width-capped string column requires eager "
                "execution with its overflow tail attached; to_arrow() "
                "the column (or drop the cap) first")
    concrete = all(not isinstance(_len_arr(c), jax.core.Tracer)
                   for c in cols if c.dtype.is_string)
    actual_max = 0
    if concrete:
        for col in cols:
            if col.dtype.is_string and col.num_rows:
                if col.is_padded and string_tail(col):
                    # width-capped column: the device window is the cap;
                    # longer rows are host-patched by the hash functions
                    actual_max = max(actual_max, col.chars2d.shape[1])
                    continue
                # host-side: str_lens() is an eager device op that would
                # compile one tiny program per raw shape, defeating the
                # bucket policy's compile bound
                if col.lens is not None:
                    lens = np.asarray(col.lens)
                elif col.offsets is not None:
                    arr = np.asarray(col.offsets)
                    lens = arr[1:] - arr[:-1]
                else:
                    lens = np.asarray(col.str_lens())
                col_max = int(lens.max())
                actual_max = max(actual_max, col_max)
                if col.is_padded and col_max > col.chars2d.shape[1]:
                    # rows longer than the padded matrix with no tail:
                    # the tail was lost; hashing zero-truncated bytes
                    # would silently mis-partition (loud-failure
                    # contract, see table._require_string_tail)
                    raise ValueError(
                        "string column has rows longer than its padded "
                        "width but no overflow tail attached; refusing "
                        "to hash truncated bytes")
    if max_str_len is not None:
        # an undersized window would silently truncate the byte stream —
        # validate whenever the offsets are concrete (free in eager mode)
        if concrete and actual_max > int(max_str_len):
            raise ValueError(f"max_str_len={max_str_len} < actual max "
                             f"string length {actual_max}")
        return int(max_str_len)
    if concrete:
        return actual_max
    if all(c.is_padded for c in cols if c.dtype.is_string):
        # padded width >= every length; bytes past a length are zero, so a
        # wider window hashes identically
        return max((c.chars2d.shape[1] for c in cols if c.dtype.is_string),
                   default=0)
    raise ValueError("string hashing on Arrow-layout columns under jit "
                     "requires an explicit max_str_len")


def _mm3_string_col(col: Column, h: jnp.ndarray, W: int) -> jnp.ndarray:
    """Spark ``Murmur3_x86_32.hashUnsafeBytes``: little-endian 4-byte blocks,
    then each tail byte sign-extended and mixed as its own block, fmix with
    the byte length."""
    Wp = (W + 3) // 4 * 4
    b, lens = _string_window(col, Wp)
    nblocks = lens // 4
    hc = h
    if Wp:
        words = _bytes_to_u32_lanes(b)
        for j in range(Wp // 4):
            mixed = _mm3_mix_h1(hc, words[:, j])
            hc = jnp.where(j < nblocks, mixed, hc)
        for t in range(3):
            pos = nblocks * 4 + t
            # Java's getByte sign-extends: 0x80.. bytes mix as negative ints
            byte = _byte_at(b, pos)
            k1 = jax.lax.bitcast_convert_type(
                byte.astype(jnp.int8).astype(jnp.int32), jnp.uint32)
            hc = jnp.where(pos < lens, _mm3_mix_h1(hc, k1), hc)
    return _mm3_fmix(hc, lens)


def _tail_subcolumn(tail) -> Column:
    """The overflow tail as a small dense-padded column (k rows at the
    tail's own width) — re-hashed by the NORMAL device kernel with
    per-row entry states, so the patch path is the same code as the hot
    path (no parallel host implementation to drift)."""
    from spark_rapids_jni_tpu.table import STRING, ragged_positions
    lens = tail.lens()
    k = len(lens)
    Wt = (int(lens.max()) + 3) // 4 * 4
    mat = np.zeros((k, Wt), np.uint8)
    rep, intra = ragged_positions(lens)
    mat[rep, intra] = tail.data
    offsets = np.zeros(k + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    return Column(STRING, jnp.zeros((0,), jnp.uint8), None,
                  jnp.asarray(offsets), None, jnp.asarray(mat))


def _patch_capped_rows(col: Column, hc, h_entry, kernel_fn, scatter_fn):
    """Replace hash values of a capped column's tail rows: gather each
    row's entry state, run the device hash kernel over the tail
    sub-column, scatter the results back."""
    from spark_rapids_jni_tpu.table import string_tail
    tail = string_tail(col) if col.is_padded else None
    if tail is None or not len(tail):
        return hc
    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree_util.tree_leaves((hc, h_entry))):
        raise ValueError(
            "hashing a width-capped string column requires eager "
            "execution (host tail patch); convert with to_arrow() or "
            "drop the cap before jit")
    sub = _tail_subcolumn(tail)
    rows = jnp.asarray(tail.rows.astype(np.int32))
    vals = kernel_fn(sub, rows)
    return scatter_fn(hc, rows, vals)


def _murmur3_chain(cols, seed: int, W: int) -> jnp.ndarray:
    """The per-column murmur3 chain (no capped-tail patching — callers
    route capped columns through the eager entry)."""
    n = cols[0].num_rows
    h = jnp.full((n,), seed, dtype=jnp.uint32)

    def _mm3_kernel(sub, rows):
        return _mm3_string_col(sub, h[rows], sub.chars2d.shape[1])

    def _mm3_scatter(hc, rows, vals):
        return hc.at[rows].set(vals)

    for col in cols:
        if col.dtype.is_string:
            hc = _mm3_string_col(col, h, W)
            hc = _patch_capped_rows(col, hc, h, _mm3_kernel,
                                    _mm3_scatter)
        else:
            words = _as_u32_words(col)
            hc = h
            for w in words:
                hc = _mm3_mix_h1(hc, w)
            hc = _mm3_fmix(hc, len(words) * 4)
        if col.validity is not None:
            h = jnp.where(col.valid_bools(), hc, h)
        else:
            h = hc
    return jax.lax.bitcast_convert_type(h, jnp.int32)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _murmur3_jit(cols, seed: int, W: int) -> jnp.ndarray:
    """The whole chain as ONE program.  Eagerly the chain dispatches
    hundreds of tiny per-shape vector ops; under the shape-bucket policy
    each bucket then compiles exactly one program, which is what lets
    the guard test count compiles-per-op against the bucket count."""
    return _murmur3_chain(cols, seed, W)


def _hash_bucketed(cols, bucket, W: int):
    """Resolve the bucket plan for a hash entry: ``(b, Wb)`` row/width
    buckets, or None to take the eager unbucketed path (opt-out, inside
    a trace, nested columns, or capped columns whose host tail patch
    requires eager per-shape execution)."""
    f = shapes.resolve(bucket)
    if f is None or any(c.children or getattr(c, "capped", False)
                        for c in cols):
        return None
    n = cols[0].num_rows
    return shapes.bucket_rows(n, f), shapes.bucket_width(W, f)


def _dispatch_hash(op: str, pcols, seed: int, Wb: int, xla_jit):
    """Pick the tiled Pallas kernel or the generic XLA chain for one
    bucketed hash call (``SRJ_TPU_PALLAS`` knob, ``runtime/shapes``
    bucket already applied).  Pallas covers fixed-width non-nested
    columns plus dense-padded string columns (the bucketed char window
    ``Wb`` sets their word rows); Arrow-layout or width-capped
    strings and decimal128 stay on the XLA chain via ``choose()``'s
    per-op eligibility hook, which stamps ``impl=xla,
    reason=ineligible``.  Either way the span is stamped with ``impl=``
    and the program is registered with the flight recorder under
    ``(op, sig, bucket)``.

    The Pallas path runs under :func:`runtime.resilience.run` with the
    XLA chain as its twin: transients retry, deterministic Pallas
    failures fall through to XLA in the same call, and the per-``(op,
    sig, bucket)`` circuit breaker quarantines a kernel whose failure
    rate crosses the threshold (both lowerings are bit-exact by
    construction, so the fallback is invisible to callers)."""
    from spark_rapids_jni_tpu.ops import pallas_kernels
    impl, interp = pallas_kernels.choose(op, jax.default_backend(),
                                         sig=pcols)
    if impl == "pallas":
        b = pcols[0].num_rows
        sig = (len(pcols), tuple(str(c.dtype) for c in pcols), Wb)
        if op == "murmur3_hash":
            fn = functools.partial(pallas_kernels.murmur3_cols,
                                   seed=seed, W=Wb, interpret=interp)
        else:
            fn = functools.partial(pallas_kernels.xxhash64_cols,
                                   seed=seed, W=Wb, interpret=interp)
        # the recorder lowers from flat array avals — close over the
        # column treedef so the registered fn rebuilds the tuple
        leaves, treedef = jax.tree_util.tree_flatten(pcols)
        pallas_kernels.register(
            op, sig, b,
            lambda *ls: fn(jax.tree_util.tree_unflatten(treedef, ls)),
            tuple(leaves), impl="pallas")

        def _primary(cols):
            pallas_kernels.stamp_impl("pallas", interp)
            return fn(cols)

        def _twin(cols):
            pallas_kernels.stamp_impl("xla")
            with spans.launch("hash.xla", xla_jit):
                return xla_jit(cols, seed, Wb)

        from spark_rapids_jni_tpu.runtime import resilience
        return resilience.run(op, _primary, pcols, sig=sig, bucket=b,
                              impl="pallas", fallback=_twin)
    pallas_kernels.stamp_impl("xla")
    with spans.launch("hash.xla", xla_jit):
        return xla_jit(pcols, seed, Wb)


@span_fn(attrs=_hash_attrs)
def murmur3_hash(table_or_cols, seed: int = DEFAULT_SEED,
                 max_str_len: Optional[int] = None, *,
                 bucket="auto") -> jnp.ndarray:
    """Spark ``Murmur3Hash(cols)``: returns int32 [n].

    Null rows of a column leave the running hash unchanged (Spark skips
    null fields).  String columns hash their UTF-8 bytes; pass
    ``max_str_len`` when calling under jit (otherwise it is derived from
    the offsets with a host sync).  Width-capped padded columns hash
    their device window and host-patch the tail rows (eager only).

    ``bucket``: shape-bucket policy (``runtime/shapes.py``).  ``"auto"``
    pads rows/window to the geometric bucket and runs the whole chain as
    one jitted program per bucket; ``None`` keeps the exact-shape eager
    chain."""
    cols = (table_or_cols.columns if isinstance(table_or_cols, Table)
            else tuple(table_or_cols))
    n = cols[0].num_rows
    W = _resolve_str_window(cols, max_str_len) \
        if any(c.dtype.is_string for c in cols) else 0
    plan = _hash_bucketed(cols, bucket, W)
    if plan is None:
        return _murmur3_chain(cols, seed, W)
    b, Wb = plan
    shapes.note(n, b)
    with shapes.pad_span():
        pcols = tuple(shapes.pad_column(c, b, width=Wb or None)
                      for c in cols)
    with tracing.op_scope("murmur3_hash", b):
        out = _dispatch_hash("murmur3_hash", pcols, int(seed), Wb,
                             _murmur3_jit)
    with shapes.unpad_span():
        return shapes.unpad_array(out, n)


@span_fn(attrs=lambda hashes, divisor: {"rows": hashes.shape[0]})
def pmod(hashes: jnp.ndarray, divisor: int) -> jnp.ndarray:
    """Spark's positive-mod used by HashPartitioning."""
    m = hashes % jnp.int32(divisor)
    return jnp.where(m < 0, m + jnp.int32(divisor), m)


def hash_partition_ids(table_or_cols, num_partitions: int,
                       seed: int = DEFAULT_SEED,
                       max_str_len: Optional[int] = None,
                       bucket="auto") -> jnp.ndarray:
    """Row -> partition id, exactly as Spark HashPartitioning does."""
    return pmod(murmur3_hash(table_or_cols, seed, max_str_len,
                             bucket=bucket),
                num_partitions)


# ---------------------------------------------------------------------------
# xxhash64 (on uint32-pair arithmetic so it runs without 64-bit lanes)
# ---------------------------------------------------------------------------

_XXP1 = (0x9E3779B1, 0x85EBCA87)  # 0x9E3779B185EBCA87 as (hi, lo)
_XXP2 = (0xC2B2AE3D, 0x27D4EB4F)
_XXP3 = (0x165667B1, 0x9E3779F9)
_XXP4 = (0x85EBCA77, 0xC2B2AE63)
_XXP5 = (0x27D4EB2F, 0x165667C5)


def _u64(hi, lo):
    return (jnp.uint32(hi), jnp.uint32(lo))


def _add64(a, b):
    lo = a[1] + b[1]
    carry = (lo < a[1]).astype(jnp.uint32)
    return (a[0] + b[0] + carry, lo)


def _mul64(a, b):
    """64x64->low 64 multiply on uint32 halves."""
    a_hi, a_lo = a
    b_hi, b_lo = b
    # partial products of 16-bit limbs would be exact; uint32*uint32 in XLA
    # keeps only low 32 bits, so split into 16-bit limbs for the low product
    def mul32_wide(x, y):
        x_lo = x & jnp.uint32(0xFFFF)
        x_hi = x >> 16
        y_lo = y & jnp.uint32(0xFFFF)
        y_hi = y >> 16
        ll = x_lo * y_lo
        lh = x_lo * y_hi
        hl = x_hi * y_lo
        hh = x_hi * y_hi
        mid = (ll >> 16) + (lh & jnp.uint32(0xFFFF)) + (hl & jnp.uint32(0xFFFF))
        lo = (ll & jnp.uint32(0xFFFF)) | (mid << 16)
        hi = hh + (lh >> 16) + (hl >> 16) + (mid >> 16)
        return hi, lo
    hi1, lo = mul32_wide(a_lo, b_lo)
    hi = hi1 + a_lo * b_hi + a_hi * b_lo
    return (hi, lo)


def _xor64(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _rotl64(a, r):
    hi, lo = a
    if r == 32:
        return (lo, hi)
    if r < 32:
        return ((hi << r) | (lo >> (32 - r)), (lo << r) | (hi >> (32 - r)))
    r -= 32
    hi, lo = lo, hi
    return ((hi << r) | (lo >> (32 - r)), (lo << r) | (hi >> (32 - r)))


def _shr64(a, r):
    hi, lo = a
    if r >= 32:
        return (jnp.zeros_like(hi), hi >> (r - 32))
    return (hi >> r, (lo >> r) | (hi << (32 - r)))


def _xx_round(acc, inp):
    acc = _add64(acc, _mul64(inp, _u64(*_XXP2)))
    acc = _rotl64(acc, 31)
    return _mul64(acc, _u64(*_XXP1))


def _xx_fmix(h):
    h = _xor64(h, _shr64(h, 33))
    h = _mul64(h, _u64(*_XXP2))
    h = _xor64(h, _shr64(h, 29))
    h = _mul64(h, _u64(*_XXP3))
    return _xor64(h, _shr64(h, 32))


def _col_u64_blocks(col: Column):
    """Spark XxHash64 normalization: every fixed-width value becomes one
    8-byte block (long); float->int bits->long, double->long bits."""
    words = _as_u32_words(col)
    if len(words) == 1:
        # sign-extend int32 word to 64 bits
        lo = words[0]
        hi = jnp.where(
            jax.lax.bitcast_convert_type(lo, jnp.int32) < 0,
            jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        return (hi, lo)
    return (words[1], words[0])  # little-endian pair -> (hi, lo)


def _where64(cond, a, b):
    return (jnp.where(cond, a[0], b[0]), jnp.where(cond, a[1], b[1]))


def _const64(v: int):
    """A python 64-bit constant as a (hi, lo) uint32 pair."""
    v &= 0xFFFFFFFFFFFFFFFF
    return _u64(v >> 32, v & 0xFFFFFFFF)


# the primes as plain ints, derived from the single (hi, lo) source above
_XXP1_I = (_XXP1[0] << 32) | _XXP1[1]
_XXP2_I = (_XXP2[0] << 32) | _XXP2[1]


def _xx64_string_col(col: Column, h, W: int):
    """Spark ``XXH64.hashUnsafeBytes`` over UTF-8 bytes, seeded by the
    running hash ``h``: 32-byte accumulator chunks (v1..v4) while
    ``offset <= len-32``, +length, 8-byte stripes, one 4-byte block if
    >=4 bytes remain, then single bytes; finally avalanche.  All loops are
    static over the padded window with per-row masks."""
    Wp = (W + 7) // 8 * 8
    b, lens = _string_window(col, Wp)
    n = lens.shape[0]
    zeros = jnp.zeros((n,), jnp.uint32)
    words = _bytes_to_u32_lanes(b) if Wp else jnp.zeros((n, 0), jnp.uint32)

    def w64(j):  # j-th little-endian 8-byte word as (hi, lo)
        return (words[:, 2 * j + 1], words[:, 2 * j])

    seed = h
    # --- >=32-byte accumulator path ---
    nchunks = lens // 32                       # chunks while offset<=len-32
    if Wp >= 32:
        v1 = _add64(seed, _const64(_XXP1_I + _XXP2_I))
        v2 = _add64(seed, _const64(_XXP2_I))
        v3 = seed
        v4 = _add64(seed, _const64(-_XXP1_I))
        for g in range(Wp // 32):
            active = g < nchunks
            v1 = _where64(active, _xx_round(v1, w64(4 * g)), v1)
            v2 = _where64(active, _xx_round(v2, w64(4 * g + 1)), v2)
            v3 = _where64(active, _xx_round(v3, w64(4 * g + 2)), v3)
            v4 = _where64(active, _xx_round(v4, w64(4 * g + 3)), v4)
        big = _add64(_add64(_rotl64(v1, 1), _rotl64(v2, 7)),
                     _add64(_rotl64(v3, 12), _rotl64(v4, 18)))

        def merge(acc, v):
            acc = _xor64(acc, _xx_round((zeros, zeros), v))
            return _add64(_mul64(acc, _u64(*_XXP1)), _u64(*_XXP4))
        big = merge(merge(merge(merge(big, v1), v2), v3), v4)
        hash_ = _where64(lens >= 32, big, _add64(seed, _u64(*_XXP5)))
    else:
        hash_ = _add64(seed, _u64(*_XXP5))
    hash_ = _add64(hash_, (zeros, lens.astype(jnp.uint32)))

    # --- 8-byte stripes: longs j in [nchunks*4, lens//8) ---
    nlongs = lens // 8
    for j in range(Wp // 8):
        active = (j >= nchunks * 4) & (j < nlongs)
        k1 = _xx_round((zeros, zeros), w64(j))
        upd = _add64(_mul64(_rotl64(_xor64(hash_, k1), 27), _u64(*_XXP1)),
                     _u64(*_XXP4))
        hash_ = _where64(active, upd, hash_)

    # --- one 4-byte block if len%8 >= 4 (at u32-word index nlongs*2) ---
    if Wp:
        has4 = (lens % 8) >= 4
        w32 = _word_at(words, nlongs * 2)
        upd = _add64(_mul64(_rotl64(
            _xor64(hash_, _mul64((zeros, w32), _u64(*_XXP1))), 23),
            _u64(*_XXP2)), _u64(*_XXP3))
        hash_ = _where64(has4, upd, hash_)

        # --- byte tail: positions [nlongs*8 + (4 if has4), len); after the
        # stripes the remainder is len%8 (0..7) and has4 consumes 4 of it,
        # so at most 3 bytes can ever be active ---
        tail_start = nlongs * 8 + jnp.where(has4, 4, 0).astype(jnp.int32)
        for t in range(3):
            pos = tail_start + t
            byte = _byte_at(b, pos).astype(jnp.uint32)
            upd = _mul64(_rotl64(
                _xor64(hash_, _mul64((zeros, byte), _u64(*_XXP5))), 11),
                _u64(*_XXP1))
            hash_ = _where64(pos < lens, upd, hash_)
    return _xx_fmix(hash_)


def _xx64_chain(cols, seed: int, W: int) -> jnp.ndarray:
    """The per-column xxhash64 chain (see :func:`_murmur3_chain`)."""
    n = cols[0].num_rows
    zeros = jnp.zeros((n,), jnp.uint32)
    h = (zeros, zeros + jnp.uint32(seed))  # seed < 2^32 in practice

    def _xx_kernel(sub, rows):
        return _xx64_string_col(sub, (h[0][rows], h[1][rows]),
                                sub.chars2d.shape[1])

    def _xx_scatter(hc, rows, vals):
        return (hc[0].at[rows].set(vals[0]),
                hc[1].at[rows].set(vals[1]))

    for col in cols:
        if col.dtype.is_string:
            hc = _xx64_string_col(col, h, W)
            hc = _patch_capped_rows(col, hc, h, _xx_kernel, _xx_scatter)
        else:
            blk = _col_u64_blocks(col)
            # single 8-byte block path: h = seed + P5 + 8, per xxhash64 spec
            hc = _add64(_add64(h, _u64(*_XXP5)), _u64(0, 8))
            k1 = _xx_round((zeros, zeros), blk)
            hc = _xor64(hc, k1)
            hc = _rotl64(hc, 27)
            hc = _add64(_mul64(hc, _u64(*_XXP1)), _u64(*_XXP4))
            hc = _xx_fmix(hc)
        if col.validity is not None:
            v = col.valid_bools()
            hc = (jnp.where(v, hc[0], h[0]), jnp.where(v, hc[1], h[1]))
        h = hc
    return jnp.stack([h[1], h[0]], axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _xx64_jit(cols, seed: int, W: int) -> jnp.ndarray:
    return _xx64_chain(cols, seed, W)


@span_fn(attrs=_hash_attrs)
def xxhash64(table_or_cols, seed: int = DEFAULT_SEED,
             max_str_len: Optional[int] = None, *,
             bucket="auto") -> jnp.ndarray:
    """Spark ``XxHash64(cols)``: returns the hash as uint32 (hi, lo) pair
    stacked into an [n, 2] array (lo word first), chaining per column with
    null fields skipped.  String columns hash their UTF-8 byte stream; pass
    ``max_str_len`` when calling under jit.  ``bucket``: shape-bucket
    policy, as in :func:`murmur3_hash`."""
    cols = (table_or_cols.columns if isinstance(table_or_cols, Table)
            else tuple(table_or_cols))
    n = cols[0].num_rows
    W = _resolve_str_window(cols, max_str_len) \
        if any(c.dtype.is_string for c in cols) else 0
    plan = _hash_bucketed(cols, bucket, W)
    if plan is None:
        return _xx64_chain(cols, seed, W)
    b, Wb = plan
    shapes.note(n, b)
    with shapes.pad_span():
        pcols = tuple(shapes.pad_column(c, b, width=Wb or None)
                      for c in cols)
    with tracing.op_scope("xxhash64", b):
        out = _dispatch_hash("xxhash64", pcols, int(seed), Wb, _xx64_jit)
    with shapes.unpad_span():
        return shapes.unpad_array(out, n)
