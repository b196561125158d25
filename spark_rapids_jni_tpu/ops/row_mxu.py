"""MXU permutation engine for fixed-width JCUDF row conversion.

The TPU-first redesign of the reference's tiled byte-copy kernels
(``copy_to_rows`` ``row_conversion.cu:575-693``, ``copy_from_rows``
``:892-993``, ``copy_validity_to_rows`` ``:710-810``): instead of moving
bytes through scratch memory with per-warp copies, the whole row encode is
expressed as ONE int8 matmul on the systolic array.

Key idea: a JCUDF row is a *static byte permutation* of the table's column
bytes plus an OR-reduction for the validity bitmask.  Both are linear maps
over GF-free mod-256 integer arithmetic:

- every output data byte has exactly one source byte -> a 0/1 entry in a
  permutation matrix ``P``;
- validity byte ``b`` of the row is ``sum_j valid[8b+j] << j`` with
  ``valid`` in {0,1} -> weighted entries ``1 << j`` in the same matrix
  (sums stay < 256, so int32 accumulation truncated to uint8 is exact; the
  int8 cast of weight 128 wraps to -128, which is congruent mod 256).

The table's columns are first packed into a *transposed* ``[W, n] uint32``
word matrix (one "plane" row per word: 64/32-bit columns bitcast straight
in, 16-bit pairs and 8-bit quads packed by fused shifts/ors, validity bits
as 0/1 bytes; the axis-0 concatenate is contiguous copies, never an
interleave), then one ``dot_general`` contracting lhs dims (0, 2) reads the
planes' bytes through a lazily-bitcast ``[W, n, 4]`` uint8 view and emits
the finished ``[n, row_size]`` row matrix on the MXU — the row-major
interleave the reference pays shared-memory traffic for is absorbed into
the systolic array's operand load.  The decode direction is the transposed
permutation producing byte planes ``[W, 4, n]``, recombined into words and
sliced per column (plane rows are contiguous ``[n]`` vectors).

This plays the role of the reference's hot kernels; the pure-XLA
concatenate implementation (``row_conversion._assemble_fixed_rows``) and
the gather-based oracle stay as the independent cross-check paths, the same
dual-implementation strategy the reference's test suite uses
(``src/main/cpp/tests/row_conversion.cpp``).
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.obs import spans
from spark_rapids_jni_tpu.table import Column, DType, Table, pack_bools_2d
from spark_rapids_jni_tpu.ops.row_layout import RowLayout


# ---------------------------------------------------------------------------
# Word plan: how columns map into the packed uint32 word matrix X
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class WordPlan:
    """Static layout of the packed word matrix for one schema.

    ``col_word``/``col_byte`` give, per column, the (word, byte-within-word)
    coordinate of the column's first byte in X.  ``num_words`` is W.
    ``validity_word``/``validity_byte`` locate the encoded validity section:
    in the *forward* plan these hold one 0/1 byte per column; in the
    *inverse* plan they hold the packed validity bytes themselves.
    """

    num_words: int
    col_word: Tuple[int, ...]
    col_byte: Tuple[int, ...]
    validity_word: Tuple[int, ...]
    validity_byte: Tuple[int, ...]


def _build_word_plan(layout: RowLayout, validity_units: int) -> WordPlan:
    """Allocate word slots: 8/4-byte columns word-aligned, 2-byte columns
    packed two per word, 1-byte columns four per word, then
    ``validity_units`` extra bytes packed four per word."""
    col_word = [0] * layout.num_columns
    col_byte = [0] * layout.num_columns
    w = 0
    # widest first, each size class as ONE contiguous plane block:
    # 16-byte (decimal128, 4 words), then 8-byte pairs, then 4-byte
    for i, dt in enumerate(layout.dtypes):
        if layout.col_sizes[i] == 16:
            col_word[i], col_byte[i] = w, 0
            w += 4
    for i, dt in enumerate(layout.dtypes):
        if layout.col_sizes[i] == 8:
            col_word[i], col_byte[i] = w, 0
            w += 2
    for i, dt in enumerate(layout.dtypes):
        if layout.col_sizes[i] == 4:
            col_word[i], col_byte[i] = w, 0
            w += 1
    # 2-byte columns, two per word
    half = 0
    for i, dt in enumerate(layout.dtypes):
        if layout.col_sizes[i] == 2:
            col_word[i], col_byte[i] = w, 2 * (half & 1)
            half += 1
            if half & 1 == 0:
                w += 1
    if half & 1:
        w += 1
    # 1-byte columns, four per word
    quad = 0
    for i, dt in enumerate(layout.dtypes):
        if layout.col_sizes[i] == 1:
            col_word[i], col_byte[i] = w, quad & 3
            quad += 1
            if quad & 3 == 0:
                w += 1
    if quad & 3:
        w += 1
    # validity bytes, four per word
    vw, vb = [], []
    for j in range(validity_units):
        vw.append(w + j // 4)
        vb.append(j % 4)
    w += (validity_units + 3) // 4
    return WordPlan(w, tuple(col_word), tuple(col_byte), tuple(vw),
                    tuple(vb))


@functools.lru_cache(maxsize=64)
def _forward_plan(layout: RowLayout):
    """Forward (encode) plan + its ``[W, 4, row_size]`` int8 matrix."""
    plan = _build_word_plan(layout, layout.num_columns)
    p = np.zeros((plan.num_words, 4, layout.fixed_row_size), dtype=np.uint8)
    for i in range(layout.num_columns):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        for b in range(sz):
            w = plan.col_word[i] + (plan.col_byte[i] + b) // 4
            k = (plan.col_byte[i] + b) % 4
            p[w, k, s + b] = 1
    for c in range(layout.num_columns):
        p[plan.validity_word[c], plan.validity_byte[c],
          layout.validity_offset + c // 8] = np.uint8(1 << (c % 8))
    return plan, p.view(np.int8)


@functools.lru_cache(maxsize=64)
def _inverse_plan(layout: RowLayout):
    """Inverse (decode) plan + its ``[row_size, W, 4]`` int8 matrix."""
    plan = _build_word_plan(layout, layout.validity_bytes)
    p = np.zeros((layout.fixed_row_size, plan.num_words, 4), dtype=np.int8)
    for i in range(layout.num_columns):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        for b in range(sz):
            w = plan.col_word[i] + (plan.col_byte[i] + b) // 4
            k = (plan.col_byte[i] + b) % 4
            p[s + b, w, k] = 1
    for j in range(layout.validity_bytes):
        p[layout.validity_offset + j, plan.validity_word[j],
          plan.validity_byte[j]] = 1
    return plan, p


# ---------------------------------------------------------------------------
# Column <-> uint32 word helpers
# ---------------------------------------------------------------------------

def _as_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-extend any narrow integer/bool column to uint32 bytes-exactly."""
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.uint8)
    unsigned = jnp.dtype(f"uint{x.dtype.itemsize * 8}")
    if x.dtype != unsigned:
        x = jax.lax.bitcast_convert_type(x, unsigned)
    return x.astype(jnp.uint32)


def _col_words(col: Column) -> List[jnp.ndarray]:
    """A column's data as a list of [n] uint32 word arrays (LE order).
    Partial words (16/8-bit columns) return a single low-justified word."""
    data = col.data
    sz = col.dtype.itemsize
    if sz == 16:  # decimal128 [n, 4] limbs: one word per limb lane
        return [data[:, k] for k in range(4)]
    if sz == 8:
        pair = _col_words_pair(col)
        return [pair[0], pair[1]]
    if sz == 4:
        return [jax.lax.bitcast_convert_type(data, jnp.uint32)
                if data.dtype != jnp.uint32 else data]
    return [_as_u32(data)]


def _pack_planes(table: Table, layout: RowLayout, plan: WordPlan,
                 valid_units: List[jnp.ndarray]) -> jnp.ndarray:
    """Build the word matrix *transposed*: [W, n] uint32, one row ("plane")
    per word.  Rows are produced by fused shifts/ors over whole [n]
    columns and joined with an axis-0 concatenate — contiguous copies, no
    interleave.  The interleave the reference pays shared-memory traffic
    for happens inside the MXU's operand load instead (the dot contracts
    lhs dim 0, reading the transposed operand for free)."""
    n = table.num_rows
    words: List = [None] * plan.num_words
    def _add(w: int, term: jnp.ndarray):
        words[w] = term if words[w] is None else words[w] | term
    for i, col in enumerate(table.columns):
        ws = _col_words(col)
        for j, word in enumerate(ws):
            w = plan.col_word[i] + j
            shift = 8 * plan.col_byte[i]
            _add(w, word << shift if shift else word)
    for j, unit in enumerate(valid_units):
        shift = 8 * plan.validity_byte[j]
        _add(plan.validity_word[j], unit << shift if shift else unit)
    zeros = jnp.zeros((n,), jnp.uint32)
    return jnp.concatenate(
        [(w if w is not None else zeros)[None, :] for w in words], axis=0)


# ---------------------------------------------------------------------------
# Pallas pack kernel: raw columns -> [W, n] word planes in one HBM pass
# ---------------------------------------------------------------------------
#
# The XLA _pack_planes materializes per-group pieces and then concatenates
# them (~3x the minimum traffic).  This kernel writes the whole plane
# matrix in a single pass: per grid step it owns a [W, TILE] VMEM block,
# copies the pre-transposed 64-bit planes and validity quads through, and
# assembles the 4/2/1-byte words from raw 1-D column blocks with fused
# shifts.  Only the 64-bit planarization (one batched transpose) and the
# validity bit-unpack stay in XLA (Mosaic cannot lane-merge the bit
# unpack's minor dims).

_PACK_TILE = 2048  # measured best on v5e (4096+ exceeds VMEM and fails)


def _pack_kernel(counts, *refs):
    n8, n4, n2, n1 = counts
    i = 0
    a8t_ref = refs[i] if n8 else None
    i += 1 if n8 else 0
    vq_ref = refs[i]
    i += 1
    c4 = refs[i:i + n4]; i += n4
    c2 = refs[i:i + n2]; i += n2
    c1 = refs[i:i + n1]; i += n1
    out_ref = refs[-1]
    r = 0
    if n8:
        out_ref[0:2 * n8, :] = a8t_ref[...]
        r = 2 * n8
    for j in range(n4):
        out_ref[r + j, :] = c4[j][...]
    r += n4
    for k in range(0, n2, 2):
        a = c2[k][...].astype(jnp.uint32)
        w = a | (c2[k + 1][...].astype(jnp.uint32) << 16) \
            if k + 1 < n2 else a
        out_ref[r + k // 2, :] = w
    r += (n2 + 1) // 2
    for k in range(0, n1, 4):
        w = c1[k][...].astype(jnp.uint32)
        for j in range(1, 4):
            if k + j < n1:
                w = w | (c1[k + j][...].astype(jnp.uint32) << (8 * j))
        out_ref[r + k // 4, :] = w
    r += (n1 + 3) // 4
    out_ref[r:, :] = vq_ref[...]


def _pack_planes_pallas(table: Table, layout: RowLayout,
                        plan: WordPlan, interpret: bool) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    n = table.num_rows
    cols = table.columns
    by_size = {8: [], 4: [], 2: [], 1: []}
    for c in cols:
        by_size[c.dtype.itemsize].append(c)
    n8, n4 = len(by_size[8]), len(by_size[4])
    n2, n1 = len(by_size[2]), len(by_size[1])
    ncols = layout.num_columns
    nvw = (ncols + 3) // 4
    W = plan.num_words

    ins, in_specs = [], []
    if n8:
        # plane-major columns concatenate straight into the [2*n8, n]
        # plane block — contiguous copies, no planarization transpose
        a8t = jnp.concatenate([_col_words_pair(c) for c in by_size[8]],
                              axis=0)
        ins.append(a8t)
        in_specs.append(pl.BlockSpec((2 * n8, _PACK_TILE),
                                     lambda r: (0, r)))
    vq = _validity_quads(table, layout)                    # [nvw, n] u32
    ins.append(vq)
    in_specs.append(pl.BlockSpec((nvw, _PACK_TILE), lambda r: (0, r)))
    for c in by_size[4]:
        d = c.data
        ins.append(d if d.dtype == jnp.uint32
                   else jax.lax.bitcast_convert_type(d, jnp.uint32))
    for c in by_size[2]:
        ins.append(jax.lax.bitcast_convert_type(c.data, jnp.uint16))
    for c in by_size[1]:
        d = c.data
        ins.append(d.astype(jnp.uint8) if d.dtype == jnp.bool_ else
                   (d if d.dtype == jnp.uint8
                    else jax.lax.bitcast_convert_type(d, jnp.uint8)))
    in_specs += [pl.BlockSpec((_PACK_TILE,), lambda r: (r,))
                 for _ in range(n4 + n2 + n1)]
    grid = ((n + _PACK_TILE - 1) // _PACK_TILE,)
    return pl.pallas_call(
        functools.partial(_pack_kernel, (n8, n4, n2, n1)),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((W, _PACK_TILE), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((W, n), jnp.uint32),
        interpret=interpret)(*ins)


def _col_words_pair(col: Column) -> jnp.ndarray:
    """A 64-bit column as [2, n] uint32 word planes (lo, hi)."""
    data = col.data
    if data.ndim == 2:  # already the plane-pair Column layout
        return data.astype(jnp.uint32) if data.dtype != jnp.uint32 else data
    # x64 native [n] 64-bit values: bitcast gives [n, 2], planarize
    return jax.lax.bitcast_convert_type(data, jnp.uint32).T


def _validity_quads(table: Table, layout: RowLayout) -> jnp.ndarray:
    """All columns' validity bits as 0/1 bytes packed 4-per-word: the
    [ceil(ncols/4), n] uint32 validity planes of the word matrix."""
    n = table.num_rows
    nb = (n + 7) // 8
    masks = jnp.stack(
        [c.validity if c.validity is not None
         else jnp.full((nb,), 255, jnp.uint8)
         for c in table.columns])                            # [ncols, nb]
    bits = ((masks[:, :, None] >> jnp.arange(8, dtype=jnp.uint8)) & 1)
    vb = bits.reshape(masks.shape[0], -1)[:, :n]             # [ncols, n] u8
    pad = (-vb.shape[0]) % 4
    if pad:
        vb = jnp.concatenate([vb, jnp.zeros((pad, n), jnp.uint8)], axis=0)
    return (vb[0::4].astype(jnp.uint32)
            | (vb[1::4].astype(jnp.uint32) << 8)
            | (vb[2::4].astype(jnp.uint32) << 16)
            | (vb[3::4].astype(jnp.uint32) << 24))


# ---------------------------------------------------------------------------
# Encode: table -> flat uint8 JCUDF rows (n * fixed_row_size)
# ---------------------------------------------------------------------------

# The dots request int8 output (``preferred_element_type=jnp.int8``):
# every output byte is a mod-256 sum, so the int8 wraparound is exactly
# the intended arithmetic and the i32 accumulator never leaves the MXU —
# measured, this removes a 4x-blob HLO temp and the row-slab chunk loop
# the i32 epilogue needed.
_DOT_CHUNK_ROWS = 512 * 1024  # floor for very wide rows


def _dot_chunk_rows(row_size: int, budget: int = 4 << 30) -> int:
    return max(_DOT_CHUNK_ROWS, budget // (row_size * 4))


@functools.partial(jax.jit, static_argnums=(1, 4, 5))
def _to_rows_mxu_jit(table: Table, layout: RowLayout, p3: jnp.ndarray,
                     start=0, size=None, pack: str = "xla") -> jnp.ndarray:
    from spark_rapids_jni_tpu.table import slice_table_dynamic
    if size is not None and size != table.num_rows:
        table = slice_table_dynamic(table, start, size)
    plan, _ = _forward_plan(layout)
    if pack.startswith("pallas"):
        xt = _pack_planes_pallas(table, layout, plan,
                                 interpret=pack == "pallas_interpret")
    else:
        valid_units = [_as_u32(table.column(c).valid_bools())
                       for c in range(layout.num_columns)]
        xt = _pack_planes(table, layout, plan, valid_units)  # [W, n] u32
    xb = jax.lax.bitcast_convert_type(xt, jnp.uint8)
    rows = jax.lax.dot_general(
        xb.astype(jnp.int8), p3,
        dimension_numbers=(((0, 2), (0, 1)), ((), ())),
        preferred_element_type=jnp.int8)
    # blobs stay 2-D [n, rs] on device: flattening a tiled uint8 matrix
    # is a measured ~17.5 ms/GB relayout copy that the wire boundary
    # alone should pay (np.asarray handles it during D2H)
    return jax.lax.bitcast_convert_type(rows, jnp.uint8)


@functools.lru_cache(maxsize=64)
def _forward_p3_device(layout: RowLayout) -> jnp.ndarray:
    return jnp.asarray(_forward_plan(layout)[1])


def _platform_of_table(table: Table) -> str:
    from spark_rapids_jni_tpu.ops.row_conversion import _platform_of
    return _platform_of(table)


def to_rows_fixed(table: Table, layout: RowLayout,
                  start: int = 0, size=None, pack=None) -> jnp.ndarray:
    """Flat uint8 JCUDF rows (n * fixed_row_size) via the MXU matmul.
    ``start``/``size`` encode one row-batch, slicing inside the jit (the
    sub-table is never materialized; ``start`` is traced so equally-sized
    batches share one executable).  ``pack`` selects the plane-matrix
    builder: the Pallas single-pass kernel (TPU default; SRJ_PALLAS_PACK=0
    disables) or the XLA piece-wise fallback."""
    if pack is None:
        nrows = size if size is not None else table.num_rows
        if os.environ.get("SRJ_PALLAS_PACK", "1") == "0" \
                or nrows < _PACK_TILE:  # tiny operands break Mosaic layout
            pack = "xla"
        else:
            platform = _platform_of_table(table)
            pack = "pallas" if platform == "tpu" else "xla"
    return _to_rows_mxu_jit(table, layout, _forward_p3_device(layout),
                            np.int32(start), size, pack)


# ---------------------------------------------------------------------------
# Fused single-pass encode: pack + dots + validity unpack in one kernel
# ---------------------------------------------------------------------------
#
# The two-stage engine above writes the [W, n] plane matrix to HBM and the
# dot reads it back -- a full extra round trip of the whole table.  The
# fused kernel reads the raw columns in place and builds the DATA-plane
# block in VMEM scratch: 64-bit columns are [2, n] plane pairs (two
# contiguous sublane rows per tile -- the Column layout IS the kernel
# layout, so the planarization transpose the old prep paid is gone);
# 4/2/1-byte columns assemble with fused shifts.  Four int8 dots against
# the byte-major data permutation ([4, Wd, rs]) produce the data bytes.
#
# Validity never materializes as per-row 0/1 bytes in HBM: the kernel
# reads the PACKED [ncols, n/8] masks (8x less traffic than the old
# validity-quad prep), expands bits in VMEM via an int8 repeat-matmul
# plus lane shifts, and adds a fifth dot whose weight matrix places
# ``1 << (c % 8)`` at each column's validity byte (the int8 wrap of 128
# is congruent mod 256).  Encode is single-pass: HBM traffic is exactly
# table bytes in + blob bytes out.
#
# Batching rides scalar prefetch: the batch's start row (in TILE units)
# is a prefetched scalar consumed by the input index maps, so a batch
# encode reads the FULL table's columns in place -- no per-batch slice
# copies, and equal-sized batches share one executable.

_FUSE_TILE = 1024


@functools.lru_cache(maxsize=64)
def _forward_p3k_np(layout: RowLayout) -> np.ndarray:
    """Forward permutation matrix rearranged byte-major: [4, W, row_size]."""
    p = _forward_plan(layout)[1]                 # [W, 4, rs] int8
    return np.ascontiguousarray(np.transpose(p, (1, 0, 2)))


def _data_words(layout: RowLayout) -> int:
    """Word count of the data section (shared by the forward and inverse
    plans: ``_build_word_plan`` lays data words out identically and only
    the trailing validity section differs)."""
    plan = _forward_plan(layout)[0]
    return plan.num_words - (layout.num_columns + 3) // 4


@functools.lru_cache(maxsize=64)
def _forward_p3k_data_np(layout: RowLayout) -> np.ndarray:
    """Data-only byte-major forward permutation: [4, Wd, row_size] (the
    validity plane rows are dropped -- the fused kernel handles validity
    from packed masks instead)."""
    return np.ascontiguousarray(
        _forward_p3k_np(layout)[:, :_data_words(layout), :])


@functools.lru_cache(maxsize=64)
def _validity_weight_np(layout: RowLayout) -> np.ndarray:
    """[ncols, row_size] int8 weights: 0/1 valid bit of column ``c``
    lands as ``1 << (c % 8)`` in validity byte ``c // 8`` (OR-as-sum:
    contributions touch disjoint bits, so int32 accumulation truncated
    to uint8 is exact)."""
    pv = np.zeros((layout.num_columns, layout.fixed_row_size), np.uint8)
    for c in range(layout.num_columns):
        pv[c, layout.validity_offset + c // 8] = np.uint8(1 << (c % 8))
    return pv.view(np.int8)


@functools.lru_cache(maxsize=2)
def _expand_w_np(T: int) -> np.ndarray:
    """[T/8, T] int8 byte-broadcast weights: E[j, 8j+t] = 1 replicates
    packed mask byte j across its 8 row lanes (the expand inverse of
    ``_pack_w_np``)."""
    e = np.zeros((T // 8, T), np.int8)
    for j in range(T // 8):
        e[j, 8 * j:8 * j + 8] = 1
    return e


def _encode_lhs(Wd, planes, vm, e_ref, lhs_ref):
    """Build the single encode operand in VMEM: rows [0, 4*Wd) hold the
    four byte-planes of the data words, rows [4*Wd, 4*Wd + ncols) the
    0/1 validity bits (packed masks expanded via an int8 repeat-matmul
    plus lane shifts).  One operand -> ONE dot (mirroring the decode
    kernel's k-major single-dot shape, ~2x fewer MXU passes than four
    K=Wd dots + a validity dot)."""
    for k in range(4):
        lhs_ref[k * Wd:(k + 1) * Wd, :] = \
            ((planes >> (8 * k)) & 0xFF).astype(jnp.int8)
    # packed masks -> per-row 0/1 bits: replicate each mask byte across
    # its 8 lanes with an int8 dot, then shift by lane % 8.  (vm bytes
    # >= 128 read as negative int8 through the dot; & 0xFF in int32
    # restores the unsigned byte.)
    rep = jax.lax.dot_general(
        vm.astype(jnp.int8), e_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)          # [ncols, T]
    lane = jax.lax.broadcasted_iota(jnp.int32, rep.shape, 1) % 8
    lhs_ref[4 * Wd:, :] = (((rep & 0xFF) >> lane) & 1).astype(jnp.int8)


def _grouped_encode_kernel(Wd, start_ref, planes_ref, vm_ref, pw_ref,
                           e_ref, out_ref, lhs_ref):
    del start_ref  # consumed by the index maps
    # the block carries the FULL inverse-plan plane rows (Mosaic wants
    # sublane blocks divisible by 8 or whole); only the data section
    # feeds the dot
    _encode_lhs(Wd, planes_ref[0:Wd, :], vm_ref[...], e_ref, lhs_ref)
    acc = jax.lax.dot_general(
        lhs_ref[...], pw_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)          # [T, rs]
    out_ref[...] = acc.astype(jnp.uint8)  # int32 -> u8 wraps mod 256


def _split_by_size(table: Table):
    by_size = {16: [], 8: [], 4: [], 2: [], 1: []}
    for c in table.columns:
        by_size[c.dtype.itemsize].append(c)
    return by_size


@functools.lru_cache(maxsize=64)
def _encode_weight_np(layout: RowLayout) -> np.ndarray:
    """[4*Wd + ncols, row_size] int8: the k-major data permutation
    stacked over the validity weights -- the single encode dot's rhs."""
    wd = _data_words(layout)
    return np.ascontiguousarray(np.concatenate(
        [_forward_p3k_data_np(layout).reshape(4 * wd, -1),
         _validity_weight_np(layout)], axis=0))


def _common_encode_tail_specs(layout: RowLayout, T: int):
    """(ins, in_specs) tail shared by both fused encoders: the combined
    weight matrix and the validity expand matrix (constant blocks)."""
    from jax.experimental import pallas as pl
    Wd = _data_words(layout)
    rs = layout.fixed_row_size
    ncols = layout.num_columns
    ins = [jnp.asarray(_encode_weight_np(layout)),
           jnp.asarray(_expand_w_np(T))]
    specs = [pl.BlockSpec((4 * Wd + ncols, rs), lambda i, s: (0, 0)),
             pl.BlockSpec((T // 8, T), lambda i, s: (0, 0))]
    return ins, specs


def _grouped_encode_impl(planes, vmask, layout: RowLayout, size: int,
                         interpret: bool, start_tiles) -> jnp.ndarray:
    """Encode straight from the plane-major backing: the kernel reads
    [Wd, T] data-plane blocks and [ncols, T/8] packed-mask blocks in
    place, builds one [4*Wd + ncols, T] int8 operand in VMEM, and fires
    ONE dot against the combined weight matrix."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    Wd = _data_words(layout)
    rs = layout.fixed_row_size
    ncols = layout.num_columns
    T = _FUSE_TILE
    W_in = planes.shape[0]  # full inverse-plan rows (kernel slices :Wd)
    in_specs = [pl.BlockSpec((W_in, T), lambda i, s: (0, s[0] + i)),
                pl.BlockSpec((ncols, T // 8), lambda i, s: (0, s[0] + i))]
    tail_ins, tail_specs = _common_encode_tail_specs(layout, T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((size + T - 1) // T,),
        in_specs=in_specs + tail_specs,
        out_specs=pl.BlockSpec((T, rs), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.VMEM((4 * Wd + ncols, T), jnp.int8)],
    )
    out = pl.pallas_call(
        functools.partial(_grouped_encode_kernel, Wd),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((size, rs), jnp.uint8),
        interpret=interpret,
    )(jnp.asarray(start_tiles, jnp.int32).reshape(1), planes, vmask,
      *tail_ins)
    return out  # [size, rs]: blobs stay 2-D on device


@functools.lru_cache(maxsize=8)
def _grouped_encode_fn(dev):
    """Per-device jit of the grouped encode with the output FORCED
    row-major: XLA's layout assignment prefers the padding-free
    column-major entry layout for u8 [n, rs] (rs pads 1008->1024 on
    lanes) and inserts a full-blob transpose copy (~6.8 ms per 2GB
    batch) to get it; row-major is what every consumer reads."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding
    fmt = Format(Layout(major_to_minor=(0, 1)), SingleDeviceSharding(dev))
    return jax.jit(_grouped_encode_impl, static_argnums=(2, 3, 4),
                   out_shardings=fmt)


def _not_persisted():
    """Compile what runs inside without writing it to the persistent
    compile cache.  An executable read back from that cache labels
    outputs of a forced non-default layout with the default one (the
    buffer keeps the forced layout), so every program that then reads
    such a blob is compiled for the wrong layout and refused at launch
    (``expected u8[n,rs]{0,1:...} but got {1,0:...}``).  Thread-local;
    the in-memory jit cache still holds the program."""
    from jax._src import config as jax_config
    return jax_config.persistent_cache_min_compile_time_secs(float("inf"))


def _grouped_encode_jit(planes, vmask, layout, size, interpret,
                        start_tiles):
    try:
        dev = next(iter(planes.devices()))
    except Exception:
        dev = jax.devices()[0]
    with _not_persisted():
        return _grouped_encode_fn(dev)(planes, vmask, layout, size,
                                       interpret, start_tiles)


@functools.partial(jax.jit, static_argnums=(1,))
def _pack_grouped_jit(table: Table, layout: RowLayout):
    """Single-pass XLA pack: table columns -> ([Wd, n] u32 data planes,
    [ncols, n/8] packed validity).

    Every piece is an [n]-vector op (16/8-bit columns fuse into words
    with shifts) feeding ONE axis-0 2-D concatenate of [k, n] rows --
    64-bit plane pairs drop in as their [2, n] blocks unchanged.
    Measured: the 2-D concat lowers to parallel copies (~6 ms/GB at 1M),
    where a flat 1-D concat of the same pieces lowered to a serialized
    while-loop of relayouts (~40 ms)."""
    by_size = _split_by_size(table)
    pieces = []
    for c in by_size[16]:
        # decimal128 limbs are [n, 4] uint32: transpose to 4 plane rows
        pieces.append(c.data.T)
    for c in by_size[8]:
        pieces.append(_col_words_pair(c))                    # [2, n]
    for c in by_size[4]:
        d = c.data
        pieces.append((d if d.dtype == jnp.uint32
                       else jax.lax.bitcast_convert_type(d, jnp.uint32)
                       )[None])
    c2 = [jax.lax.bitcast_convert_type(c.data, jnp.uint16)
          .astype(jnp.uint32) for c in by_size[2]]
    for k in range(0, len(c2), 2):
        pieces.append((c2[k] | (c2[k + 1] << 16)
                       if k + 1 < len(c2) else c2[k])[None])
    c1 = [(c.data.astype(jnp.uint8) if c.data.dtype == jnp.bool_ else
           (c.data if c.data.dtype == jnp.uint8
            else jax.lax.bitcast_convert_type(c.data, jnp.uint8)))
          .astype(jnp.uint32) for c in by_size[1]]
    for k in range(0, len(c1), 4):
        w = c1[k]
        for j in range(1, 4):
            if k + j < len(c1):
                w = w | (c1[k + j] << (8 * j))
        pieces.append(w[None])
    planes = jnp.concatenate(pieces, axis=0)
    n = table.num_rows
    nb = (n + 7) // 8
    full = jnp.full((nb,), 255, jnp.uint8)
    # 2-D concat here too: the 1-D concat of 212 mask pieces lowered to
    # a serialized while-loop (~13 ms at 4M); axis-0 rows copy parallel
    vparts = [(c.validity if c.validity is not None else full)[None]
              for c in table.columns]
    vmask = jnp.concatenate(vparts, axis=0)
    return planes, vmask


def table_to_grouped(table: Table, layout: RowLayout = None):
    """Convert a Table to its plane-major :class:`GroupedColumns`
    backing ([Wd, n] u32 data planes + [ncols, n/8] packed validity) --
    the device-native table form: the encode kernel reads it directly,
    ``from_rows_fixed_grouped`` produces it, and consumers extract
    columns lazily.  One copy-speed XLA pass."""
    if layout is None:
        from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
        layout = compute_row_layout(table.dtypes)
    with spans.launch("rows.pack", _pack_grouped_jit):
        planes, vmask = _pack_grouped_jit(table, layout)
    return GroupedColumns(planes, vmask, layout)


class FixedEncoder:
    """Batched encoder over one table: ONE copy-speed pack pass builds
    the plane-major backing (``table_to_grouped``), then every
    ``encode(start, size)`` is a single fused kernel reading plane and
    packed-mask blocks at a prefetched tile offset (``start`` must be a
    multiple of ``_FUSE_TILE``).  Measured: the plane-input kernel runs
    ~3-6x faster than a 200+-operand per-column kernel -- two cheap
    passes beat one slow one."""

    def __init__(self, table: Table, layout: RowLayout,
                 interpret: bool = False):
        self.layout = layout
        self.interpret = interpret
        self.num_rows = table.num_rows
        self.gc = table_to_grouped(table, layout)


    def encode(self, start: int = 0, size: int = None) -> jnp.ndarray:
        n = self.num_rows
        if size is None:
            size = n - start
        if start % _FUSE_TILE:
            raise ValueError(f"start {start} not {_FUSE_TILE}-aligned")
        if start + size > n:
            raise ValueError(
                f"batch [{start}, {start + size}) exceeds {n} rows")
        return _grouped_encode_jit(self.gc.planes, self.gc.vmask,
                                   self.layout, size, self.interpret,
                                   start // _FUSE_TILE)


def to_rows_fixed_grouped(gc, start: int = 0, size: int = None,
                          interpret: bool = False) -> jnp.ndarray:
    """Encode a :class:`GroupedColumns` (the plane-major decoded
    backing) straight back to flat JCUDF rows.  The plane-major fast
    path: one kernel, HBM traffic exactly planes in + blob out; the
    encode twin of ``from_rows_fixed_grouped``."""
    layout = gc.layout
    n = gc.num_rows
    if size is None:
        size = n - start
    if start % _FUSE_TILE:
        raise ValueError(f"start {start} not {_FUSE_TILE}-aligned")
    if start + size > n:
        raise ValueError(f"batch [{start}, {start + size}) exceeds {n}")
    return _grouped_encode_jit(gc.planes, gc.vmask, layout, size,
                               interpret, start // _FUSE_TILE)


# ---------------------------------------------------------------------------
# Transpose-engine encode (the MXU-floor falsification spike): most of a
# JCUDF row is contiguous field bytes, so instead of the permutation
# matmul, copy each maximal run of plane bytes that lands contiguously in
# the row via block transposes, and compute only the validity section
# arithmetically.  No MXU at all: the op becomes pure memory movement.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _copy_runs_np(layout: RowLayout):
    """Maximal (plane byte index, row offset, length) runs where
    consecutive plane-stream bytes map to consecutive row bytes, in
    ascending row order — or None when the schema's mapping is not
    run-decomposable (the dot engine then stays)."""
    _, p = _forward_plan(layout)                 # [W, 4, rs] int8
    Wd = _data_words(layout)
    sub = p.view(np.uint8)[:Wd]
    pos = np.full((Wd * 4,), -1, np.int64)
    for w in range(Wd):
        for k in range(4):
            nz = np.nonzero(sub[w, k])[0]
            if len(nz) > 1:
                return None
            if len(nz):
                pos[4 * w + k] = nz[0]
    runs = []
    b, B = 0, Wd * 4
    while b < B:
        if pos[b] < 0:
            b += 1
            continue
        start_b, start_pos = b, int(pos[b])
        L = 1
        while b + L < B and pos[b + L] == start_pos + L:
            L += 1
        runs.append((start_b, start_pos, L))
        b += L
    # slices read the plane stream at arbitrary positions, so order the
    # concat by ROW position; refuse overlaps (can't happen for a sane
    # forward plan, but the dot engine is always correct)
    runs.sort(key=lambda r: r[1])
    cur = 0
    for _, p0, L in runs:
        if p0 < cur:
            return None
        cur = p0 + L
    return tuple(runs)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _to_rows_transpose_jit(planes, vmask, layout: RowLayout,
                           size: int) -> jnp.ndarray:
    """[size, row_size] u8 rows from the plane-major backing with ZERO
    matmuls: one [Wd, n, 4]->[n, Wd*4] byte-stream transpose, per-run
    slices concatenated in row order, and the validity bytes from a
    bit unpack/repack (disjoint bits sum exactly in uint8)."""
    runs = _copy_runs_np(layout)
    if runs is None:
        raise ValueError("schema is not run-decomposable; use the dot "
                         "engine")
    Wd = _data_words(layout)
    n = size
    rs = layout.fixed_row_size
    ncols = layout.num_columns
    b8 = jax.lax.bitcast_convert_type(planes[:Wd, :n], jnp.uint8)
    stream = jnp.transpose(b8, (1, 0, 2)).reshape(n, Wd * 4)
    pieces = []
    cursor = 0
    for b, p0, L in runs:
        if p0 > cursor:
            pieces.append(jnp.zeros((n, p0 - cursor), jnp.uint8))
        pieces.append(jax.lax.slice(stream, (0, b), (n, b + L)))
        cursor = p0 + L
    if layout.validity_offset > cursor:
        pieces.append(jnp.zeros((n, layout.validity_offset - cursor),
                                jnp.uint8))
    # validity: [ncols, ceil(n/8)] packed-over-rows masks -> per-row
    # bytes (slice to n after unpacking: n need not be 8-aligned)
    iota8 = jnp.arange(8, dtype=jnp.uint8)
    nbytes = (n + 7) // 8
    bits = ((vmask[:, :nbytes, None] >> iota8[None, None, :])
            & jnp.uint8(1)).reshape(ncols, nbytes * 8)[:, :n]
    vb = layout.validity_bytes
    pad = vb * 8 - ncols
    bitsT = bits.T
    if pad:
        bitsT = jnp.concatenate(
            [bitsT, jnp.zeros((n, pad), jnp.uint8)], axis=1)
    vsec = jnp.sum(bitsT.reshape(n, vb, 8) << iota8[None, None, :],
                   axis=2, dtype=jnp.uint8)
    pieces.append(vsec)
    tail = rs - layout.validity_offset - vb
    if tail:
        pieces.append(jnp.zeros((n, tail), jnp.uint8))
    return jnp.concatenate(pieces, axis=1)


def to_rows_fixed_grouped_transpose(gc, size: int = None) -> jnp.ndarray:
    """Transpose-engine twin of :func:`to_rows_fixed_grouped` (full
    batch only): same [n, row_size] u8 output, no MXU."""
    layout = gc.layout
    n = gc.num_rows if size is None else size
    return _to_rows_transpose_jit(gc.planes, gc.vmask, layout, n)


# ---------------------------------------------------------------------------
# Decode: [n, fixed_row_size] uint8 -> columns
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _from_rows_mxu_jit(rows: jnp.ndarray, layout: RowLayout,
                       mode: str = "xla"):
    plan, _ = _inverse_plan(layout)
    x, vmask = _planes_and_vmask(_rows2d(rows, layout), layout, mode)

    # every column is one extraction from the decoded planes (the
    # Column layout is plane-major, so 64-bit pairs are 2-row slices)
    cols = []
    for i, dt in enumerate(layout.dtypes):
        data = extract_plane_column(x, plan, layout, i)
        cols.append(Column(dt, data, vmask[i]))
    return cols


def extract_plane_column(x: jnp.ndarray, plan, layout: RowLayout,
                         i: int) -> jnp.ndarray:
    """One column's data from decoded word planes [W, n] (shared by
    GroupedColumns.column, the fixed decode, and the variable-width
    plane decode -- the single source of truth for plane extraction)."""
    from spark_rapids_jni_tpu.table import pair_to_dtype
    dt = layout.dtypes[i]
    sz = layout.col_sizes[i]
    w0 = plan.col_word[i]
    if sz == 16:  # decimal128: 4 plane rows -> [n, 4] limbs
        return x[w0:w0 + 4].T
    if sz == 8:
        return pair_to_dtype(x[w0:w0 + 2], dt.np_dtype)
    if sz == 4:
        return jax.lax.bitcast_convert_type(x[w0], dt.np_dtype)
    word = x[w0] >> (8 * plan.col_byte[i])
    if sz == 2:
        return jax.lax.bitcast_convert_type(
            (word & 0xFFFF).astype(jnp.uint16), dt.np_dtype)
    data = (word & 0xFF).astype(jnp.uint8)
    if dt.np_dtype != np.uint8:
        data = jax.lax.bitcast_convert_type(data, dt.np_dtype)
    return data


def _rows2d(rows: jnp.ndarray, layout: RowLayout) -> jnp.ndarray:
    """[n, rs] view of a blob (2-D passthrough; flat legacy/wire blobs
    reshape INSIDE the consuming jit -- an eager reshape would dispatch
    the full-blob relayout copy as its own program)."""
    if rows.ndim == 2:
        return rows
    return rows.reshape(-1, layout.fixed_row_size)


def _decode_mode(rows: jnp.ndarray, layout: RowLayout,
                 mode: str = None) -> str:
    if mode is not None:
        return mode
    n = rows.size // layout.fixed_row_size
    if n < _FUSE_TILE:   # tiny operands break Mosaic layout (as in pack)
        return "xla"
    from spark_rapids_jni_tpu.ops.row_conversion import _platform_of
    return "pallas" if _platform_of(rows) == "tpu" else "xla"


def from_rows_fixed(rows: jnp.ndarray, layout: RowLayout,
                    mode: str = None) -> List[Column]:
    """Decode JCUDF rows ([n, fixed_row_size] device-native, or a flat
    wire blob) via the transposed MXU permutation (fused Pallas planes
    kernel on TPU)."""
    return _from_rows_mxu_jit(rows, layout,
                              _decode_mode(rows, layout, mode))


# ---------------------------------------------------------------------------
# Fused decode-to-planes: dot + byte recombine in one Pallas kernel
# ---------------------------------------------------------------------------
#
# The XLA decode dot emits [W, 4, n] int8 and recombines through a uint32
# upcast — a 4x-blob temp written and read back (the dominant decode
# cost).  The fused kernel produces the [W, TILE] u32 plane block directly:
# one dot of the k-major inverse permutation ([4W, rs], byte-plane k in
# rows kW..(k+1)W) against the row tile, then an in-VMEM shift-or of the
# four [W, TILE] int32 quadrants.  HBM traffic: read blob once, write
# planes once.

@functools.lru_cache(maxsize=64)
def _inverse_p3k_np(layout: RowLayout, row_size: int = 0) -> np.ndarray:
    """Inverse permutation rearranged k-major 2-D: [4*W, row_size].

    ``row_size`` > fixed_row_size pads the minor dim with zero columns:
    the variable-width padded row is a fixed JCUDF layout at a wider
    stride (string slots = (offset, length) u32 pairs; the char slots
    past ``fixed_end`` contribute nothing to the planes)."""
    p = _inverse_plan(layout)[1]                 # [rs, W, 4] int8
    if row_size and row_size > p.shape[0]:
        p = np.concatenate(
            [p, np.zeros((row_size - p.shape[0],) + p.shape[1:],
                         np.int8)], axis=0)
    elif row_size and row_size < p.shape[0]:
        # trailing rows past row_size carry no entries (only data +
        # validity positions below fixed_end do); truncating is safe
        assert not p[row_size:].any()
        p = p[:row_size]
    return np.ascontiguousarray(
        np.transpose(p, (2, 1, 0)).reshape(-1, p.shape[0]))


@functools.lru_cache(maxsize=2)
def _pack_w_np(T: int) -> np.ndarray:
    """[T, T/8] int8 bit-pack weights: packing 8 consecutive rows into a
    validity byte is a matmul over the row axis (1<<t at (8j+t, j);
    int8 wraps 128 to -128, congruent mod 256)."""
    w = np.zeros((T, T // 8), np.uint8)
    for j in range(T // 8):
        for t in range(8):
            w[8 * j + t, j] = 1 << t
    return w.view(np.int8)


def _fused_decode_kernel(W, ncols, vw0, vbytes, p3_ref, w8_ref,
                         rows_ref, x_ref, vm_ref, bits_ref):
    o = jax.lax.dot_general(
        p3_ref[...], rows_ref[...].astype(jnp.int8),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)        # [4W, T]
    x = (o[0 * W:1 * W] & 0xFF).astype(jnp.uint32) \
        | ((o[1 * W:2 * W] & 0xFF).astype(jnp.uint32) << 8) \
        | ((o[2 * W:3 * W] & 0xFF).astype(jnp.uint32) << 16) \
        | ((o[3 * W:4 * W] & 0xFF).astype(jnp.uint32) << 24)
    x_ref[...] = x
    # validity: unpack the quad-packed bytes to one 0/1 row per column,
    # then bit-pack 8 rows per byte with the MXU (the XLA pack stage
    # this replaces was ~half of grouped-decode time)
    for b in range(vbytes):
        vb = (x[vw0 + b // 4] >> (8 * (b % 4))) & 0xFF
        for j in range(8):
            c = 8 * b + j
            if c >= ncols:
                break
            bits_ref[c, :] = ((vb >> j) & 1).astype(jnp.int8)
    vm = jax.lax.dot_general(
        bits_ref[...], w8_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)        # [ncols, T/8]
    vm_ref[...] = vm.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _decode_planes_pallas_jit(rows: jnp.ndarray, layout: RowLayout,
                              interpret: bool, row_size: int = 0):
    """One fused kernel: blob -> ([W, n] u32 word planes,
    [ncols, ceil(n/8)] packed validity).  ``row_size`` overrides the
    row stride for padded variable-width rows (see ``_inverse_p3k_np``:
    char slots decode to nothing; string slots become u32 plane
    pairs)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    plan = _inverse_plan(layout)[0]
    W = plan.num_words
    rs = row_size or layout.fixed_row_size
    rows2d = rows if rows.ndim == 2 else rows.reshape(-1, rs)
    n = rows2d.shape[0]
    ncols = layout.num_columns
    vbytes = layout.validity_bytes
    vw0 = plan.validity_word[0]
    T = _FUSE_TILE
    p3 = jnp.asarray(_inverse_p3k_np(layout, rs))
    w8 = jnp.asarray(_pack_w_np(T))
    nb = (n + 7) // 8
    x, vm = pl.pallas_call(
        functools.partial(_fused_decode_kernel, W, ncols, vw0, vbytes),
        grid=((n + T - 1) // T,),
        in_specs=[pl.BlockSpec((4 * W, rs), lambda i: (0, 0)),
                  pl.BlockSpec((T, T // 8), lambda i: (0, 0)),
                  pl.BlockSpec((T, rs), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((W, T), lambda i: (0, i)),
                   pl.BlockSpec((ncols, T // 8), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((W, n), jnp.uint32),
                   jax.ShapeDtypeStruct((ncols, nb), jnp.uint8)],
        scratch_shapes=[pltpu.VMEM((ncols, T), jnp.int8)],
        interpret=interpret)(p3, w8, rows2d)
    if n % 8:
        # the last validity byte mixes valid rows with the partial
        # tile's garbage rows: mask bits past n (XLA zeroes them)
        tail = jnp.full((nb,), 255, jnp.uint8) \
            .at[nb - 1].set((1 << (n % 8)) - 1)
        vm = vm & tail[None, :]
    return x, vm


def _decode_planes(rows2d: jnp.ndarray, layout: RowLayout, p3) -> jnp.ndarray:
    """[n, rs] u8 rows -> [W, n] u32 word planes (call under jit).

    XLA path: dot to [W, 4, n] int8 then recombine (the planes round-trip
    a u32 upcast).  Used off-TPU and as the fused kernel's oracle."""
    o = jax.lax.dot_general(
        p3, rows2d.astype(jnp.int8),
        dimension_numbers=(((0,), (1,)), ((), ())),
        preferred_element_type=jnp.int8)                    # [W, 4, n]
    ou = jax.lax.bitcast_convert_type(o, jnp.uint8).astype(jnp.uint32)
    return (ou[:, 0, :] | (ou[:, 1, :] << 8)
            | (ou[:, 2, :] << 16) | (ou[:, 3, :] << 24))    # [W, n]


# ---------------------------------------------------------------------------
# uint32 words <-> uint8 bytes, on the MXU
# ---------------------------------------------------------------------------
#
# A TPU-tiled ``u8[*, 4]`` array (the shape ``bitcast_convert_type``
# produces) pads its 4-lane minor dimension to 128 lanes — a 32x memory
# blowup that OOMs on GB-scale blobs.  A bitcast is only safe when it is
# *consumed by a dot* (fused into the MXU operand load, never materialized),
# so the byte<->word conversions are themselves expressed as identity
# permutation matmuls.

_WB = 128  # words per dot row


@functools.lru_cache(maxsize=2)
def _w2b_p3_np() -> np.ndarray:
    p = np.zeros((_WB, 4, _WB * 4), dtype=np.int8)
    for w in range(_WB):
        for k in range(4):
            p[w, k, 4 * w + k] = 1
    return p


@functools.lru_cache(maxsize=2)
def _b2w_p3_np() -> np.ndarray:
    p = np.zeros((_WB * 4, _WB, 4), dtype=np.int8)
    for w in range(_WB):
        for k in range(4):
            p[4 * w + k, w, k] = 1
    return p


def words_to_bytes(w: jnp.ndarray, total: int) -> jnp.ndarray:
    """uint32[nw] -> little-endian uint8[total] (total <= 4*nw).

    Call under jit; the permutation matrix inlines as a constant (only
    numpy is cached, so no tracer can leak between traces).
    """
    if total == 0:
        return jnp.zeros((0,), jnp.uint8)
    pad = (-w.shape[0]) % _WB
    if pad:
        w = jnp.concatenate([w, jnp.zeros((pad,), jnp.uint32)])
    w2 = w.reshape(-1, _WB)
    p3 = jnp.asarray(_w2b_p3_np())
    parts = []
    chunk = _dot_chunk_rows(4 * _WB)
    for s in range(0, w2.shape[0], chunk):
        e = min(w2.shape[0], s + chunk)
        xb = jax.lax.bitcast_convert_type(w2[s:e], jnp.uint8)
        parts.append(jax.lax.dot_general(
            xb.astype(jnp.int8), p3,
            dimension_numbers=(((1, 2), (0, 1)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.uint8))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return out.reshape(-1)[:total]


def bytes_to_words(b: jnp.ndarray, nwords: int) -> jnp.ndarray:
    """little-endian uint8[nb] -> uint32[nwords] (nwords <= ceil(nb/4)).
    Call under jit (see :func:`words_to_bytes`)."""
    if nwords == 0:
        return jnp.zeros((0,), jnp.uint32)
    pad = (-b.shape[0]) % (4 * _WB)
    if pad:
        b = jnp.concatenate([b, jnp.zeros((pad,), jnp.uint8)])
    b2 = b.reshape(-1, 4 * _WB)
    p3 = jnp.asarray(_b2w_p3_np())
    parts = []
    chunk = _dot_chunk_rows(4 * _WB)
    for s in range(0, b2.shape[0], chunk):
        e = min(b2.shape[0], s + chunk)
        o = jax.lax.dot_general(
            b2[s:e].astype(jnp.int8), p3,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)              # [ck, _WB, 4]
        parts.append(jax.lax.bitcast_convert_type(
            o.astype(jnp.uint8), jnp.uint32))
    w = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return w.reshape(-1)[:nwords]


# ---------------------------------------------------------------------------
# Grouped (dtype-major) decode: the wide-output fast path
# ---------------------------------------------------------------------------
#
# The standard decode materializes one device buffer per column; XLA emits
# ~one kernel per output, and at 212 columns the per-kernel overhead
# dominates (measured ~85 kernels, most of the 70ms/GB decode time).  The
# grouped decode keeps the decode's [W, n] word-plane matrix AS the table
# backing — every byte fully decoded and organized dtype-major (the word
# plan orders 64-bit pairs first, then 4/2/1-byte packed words, exactly
# a dtype-major layout) — plus the packed validity matrix.  Consumers
# extract single columns on demand (`GroupedColumns.column`): one cheap
# slice/shift per column they actually touch (a Spark plan typically
# reads a handful), instead of materializing all 212 up front.  Measured:
# materializing per-class wide arrays eagerly cost ~3x the planes kernel
# itself; holding the planes makes grouped decode = one fused kernel +
# the validity unpack.

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GroupedColumns:
    """Dtype-major decoded table backing.

    ``planes``: uint32 [W, n] decode word-planes (the inverse word plan:
    64-bit columns as adjacent lo/hi plane pairs first, then 4-byte
    planes, 16-bit halves packed two per plane, bytes four per plane);
    ``vmask``: uint8 [ncols, ceil(n/8)] packed validity.
    """

    planes: jnp.ndarray
    vmask: jnp.ndarray
    layout: RowLayout = None

    @property
    def num_rows(self) -> int:
        return self.planes.shape[1]

    def tree_flatten(self):
        return (self.planes, self.vmask), self.layout

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)

    def column(self, i: int) -> Column:
        """Materialize one column (a plane slice + shift/bitcast)."""
        layout = self.layout
        plan = _inverse_plan(layout)[0]
        dt = layout.dtypes[i]
        data = extract_plane_column(self.planes, plan, layout, i)
        validity = self.vmask[i]
        return Column(dt, data, validity)

    def to_table(self) -> Table:
        return Table(tuple(self.column(i)
                           for i in range(self.layout.num_columns)))


def var_fixed_planes(rows2d: jnp.ndarray, layout: RowLayout,
                     fe_pad: int, interpret: bool = False):
    """Planes decode of padded VARIABLE-width rows' fixed section: one
    fused kernel emits the [W, n] word planes (string slots as (offset,
    length) u32 plane pairs) + [ncols, n/8] packed validity — the
    grouped-decode treatment applied to string tables (column
    extraction from plane ROWS is contiguous, where the per-row word
    matrix forced lane-strided slices).

    Only the fixed section feeds the kernel (``rows2d[:, :fe_pad]``,
    sliced under the caller's jit): contracting the char slots too
    would scale MXU work and the permutation matrix's VMEM footprint
    with the declared string widths for zero contribution."""
    return _decode_planes_pallas_jit(rows2d[:, :fe_pad], layout,
                                     interpret, fe_pad)


def _planes_and_vmask(rows, layout: RowLayout, mode: str):
    """Decode planes + packed validity via the mode's engine: the fused
    Pallas kernel emits both in one pass; the XLA path packs validity
    with the shared bit-plane helpers."""
    if mode != "xla":
        return _decode_planes_pallas_jit(rows, layout,
                                         mode == "pallas_interpret")
    from spark_rapids_jni_tpu.table import (
        byte_planes_from_word_planes, packed_masks_from_byte_planes)
    plan = _inverse_plan(layout)[0]
    rows2d = _rows2d(rows, layout)
    # numpy constant (NOT the cached device-array helper: jnp.asarray
    # inside a trace would cache a tracer in the lru_cache and leak)
    x = _decode_planes(rows2d, layout, _inverse_plan(layout)[1])
    vbytes = layout.validity_bytes
    vw0 = plan.validity_word[0]
    vwq = (vbytes + 3) // 4
    vb = byte_planes_from_word_planes(x[vw0:vw0 + vwq], vbytes)
    vmask = packed_masks_from_byte_planes(vb, layout.num_columns)
    return x, vmask


@functools.partial(jax.jit, static_argnums=(1, 2))
def _from_rows_grouped_jit(rows: jnp.ndarray, layout: RowLayout,
                           mode: str = "xla"):
    return _planes_and_vmask(rows, layout, mode)


def from_rows_fixed_grouped(rows: jnp.ndarray, layout: RowLayout,
                            mode: str = None) -> GroupedColumns:
    """Decode JCUDF rows to the dtype-major grouped backing: the
    ``[W, n]`` word-plane matrix plus packed validity, columns extracted
    lazily (instead of one buffer per column)."""
    planes, vmask = _from_rows_grouped_jit(
        rows, layout, _decode_mode(rows, layout, mode))
    return GroupedColumns(planes, vmask, layout)
