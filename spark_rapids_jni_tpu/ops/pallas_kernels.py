"""VMEM-tiled Pallas kernels for the hot non-pack ops, behind one knob.

The pack side already owns its tiling (``row_kernels.py`` per-column
blocks, ``row_mxu.py`` fused MXU permutations).  This module is the
unpack/hash/probe counterpart the mission statement asks for — *Pallas
kernels over HBM-resident columns* instead of generic XLA lowerings:

- :func:`from_rows_fixed` — JCUDF row blob → fixed-width columns.  Each
  grid step streams a VMEM tile of rows, combines the uint8 bytes into
  uint32 words with strided lane slices (no byte-gather index matrices,
  no narrow ``[n, size]`` bitcasts — the two patterns the TPU backend
  rejects / lane-pads 32x), and emits the tile TRANSPOSED as word planes
  ``[W, tile]``.  Plane-major output means 64-bit plane-pair columns and
  the packed validity masks need no further transposes.
- :func:`murmur3_cols` — the Spark murmur3 chain reading each column's
  own buffer and packed validity through bitcast-only views; Spark's
  normalization and the null skip run in VMEM on a dense ``[64, 128]``
  hash state, and the first and last kernel calls seed and finish the
  hash themselves.  Dense-padded string columns keep one prep program
  that lays the char window out as word rows.
- :func:`xxhash64_cols` — the xxhash64 chain over column tiles.  The
  Spark-normalized uint32 word matrix is built outside the kernel
  (pure bitcasts/slices); the kernel replays the *same* mix/fmix helper
  chain from :mod:`ops.hashing` over each VMEM tile, so bit-exactness
  with the XLA lowering is by construction.
- :func:`bloom_might_contain` — bloom probe FUSED with its two hashLong
  evaluations; the bitset rides a constant-index BlockSpec so it stays
  VMEM-resident across every row tile instead of paying k random HBM
  gathers per row.
- :func:`to_rows_fixed` — the PACK inverse: fixed-width columns →
  JCUDF row blob.  The uint32 word planes are OR-assembled outside the
  kernel (pure bitcasts/shifts, the inverse of
  :func:`_cols_from_word_planes`); each grid step streams a
  ``[W, tile]`` plane block into VMEM and expands it to row bytes with
  the repeat+tiled-shift pattern (``table.byte_planes_from_word_planes``
  — the documented TPU-safe byte expansion; no ``[n, 4]`` narrow
  bitcasts, no strided stores).
- :func:`get_json_scan` — the ``get_json`` character automaton
  (``ops.get_json._automaton_pieces``) as a Pallas grid over lane tiles
  of the TRANSPOSED char window: the LUT select-sums and the ~20-field
  carry stay VMEM-resident while a ``fori_loop`` walks the W character
  positions, replacing the ``lax.scan`` step chain for bucketed
  fixed-max-len inputs.  Emits only the fields the extraction tail
  consumes (start/end/found/capturing/bad/deep).
- String columns in either hash: a padded char window rides as
  ``Wp//4`` word rows plus one length row per string column, and the
  in-kernel tail-block masking replays ``hashing._mm3_string_col`` /
  ``_xx64_string_col`` word-for-word (tail bytes come from a
  select-captured word, never a gather).

Selection is per ``(op, sig, bucket)`` behind ``SRJ_TPU_PALLAS``:
``1`` = Pallas everywhere it is supported (interpret-mode off-TPU),
``0`` = generic XLA everywhere (the kill switch), ``auto`` (default) =
Pallas on TPU, XLA on the CPU mesh (tests opt into interpret mode
explicitly).  Every dispatch stamps ``impl=pallas|xla`` on the ambient
span — ``obs profile`` and the tenant chargeback ledger attribute wins
per implementation — and registers with the flight recorder's program
registry under the same impl tag.
"""

from __future__ import annotations

import functools
import os
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from spark_rapids_jni_tpu.obs import spans
from spark_rapids_jni_tpu.runtime import shapes

__all__ = [
    "knob", "choose", "eligible", "stamp_impl", "register",
    "SUPPORTED_OPS",
    "from_rows_fixed", "to_rows_fixed", "get_json_scan",
    "murmur3_cols", "xxhash64_cols",
    "murmur3_fixed", "xxhash64_fixed",
    "bloom_might_contain", "bloom_might_contain_xla",
]

# ops this module has a tiled kernel for (the (op, dtype, bucket) support
# matrix is finer: see the per-op ``_ELIGIBLE`` hooks below and README's
# "Kernel implementations" section)
SUPPORTED_OPS = frozenset({
    "convert_from_rows", "convert_to_rows", "get_json_object",
    "murmur3_hash", "xxhash64", "bloom_might_contain",
})

_ENV = "SRJ_TPU_PALLAS"


def knob() -> str:
    """Normalized ``SRJ_TPU_PALLAS`` value: ``"1"``, ``"0"`` or
    ``"auto"``."""
    raw = os.environ.get(_ENV, "auto").strip().lower()
    if raw in ("1", "on", "true", "yes"):
        return "1"
    if raw in ("0", "off", "false", "no"):
        return "0"
    return "auto"


def eligible(op: str, sig, platform: Optional[str] = None) -> bool:
    """Per-op kernel-coverage check: True when the op's Pallas kernel
    can tile this signature on ``platform`` (default: JAX's backend).
    ``sig`` is op-defined (see ``_ELIGIBLE``); ``None`` means the call
    site did not describe the shape.  A hook that raises counts as
    ineligible (coverage probing must never break selection)."""
    fn = _ELIGIBLE.get(op)
    if fn is None:
        return True
    if platform is None:
        platform = jax.default_backend()
    try:
        return bool(fn(sig, platform))
    except Exception:
        return False


def choose(op: str, platform: Optional[str] = None,
           sig=None) -> Tuple[str, bool]:
    """Resolve one dispatch to ``(impl, interpret)``.

    ``impl`` is ``"pallas"`` or ``"xla"``; ``interpret`` is True when the
    Pallas kernel should run in interpret mode (off-TPU platforms — the
    CPU tier-1 mesh exercises the kernels this way).

    The engine follows what the call can observe: the platform (Pallas
    on TPU, XLA elsewhere), then the per-op :func:`eligible` hook (pass
    ``sig``, the op-defined shape descriptor — e.g. the column tuple for
    the hash ops, ``(ncols, row_size)`` for the row converters), which
    routes signatures the kernel cannot tile (or, for a kernel Mosaic
    cannot lower, the TPU) to XLA with ``impl=xla reason=ineligible``
    stamped on the ambient span.  ``SRJ_TPU_PALLAS``
    forces either engine (``1`` runs the kernels interpret-mode off-TPU,
    ``0`` is the kill switch)."""
    if platform is None:
        platform = jax.default_backend()
    k = knob()
    if k == "0" or op not in SUPPORTED_OPS:
        return "xla", False
    if not eligible(op, sig, platform):
        sp = spans.current_span()
        if sp is not None:
            sp.set(impl="xla", reason="ineligible")
        return "xla", False
    if k == "1" or platform == "tpu":
        return "pallas", platform != "tpu"
    return "xla", False


def stamp_impl(impl: str, interpret: bool = False,
               engine: Optional[str] = None) -> None:
    """Stamp ``impl=`` (and ``interpret=True`` for an interpret-mode
    kernel, ``engine=`` for the op's own engine name, e.g. ``mxu``) on
    the innermost active span (the operator's own span when called from
    an op body) so ``obs profile`` and tenant chargeback split the
    ledger per implementation."""
    sp = spans.current_span()
    if sp is not None:
        sp.set(impl=impl)
        if interpret:
            sp.set(interpret=True)
        if engine:
            sp.set(engine=engine)


def register(op: str, sig, bucket, fn, args=(), impl: str = "") -> None:
    """Forward to the flight recorder's program registry with the impl
    tag (no-op when the recorder is disarmed)."""
    from spark_rapids_jni_tpu.obs import recorder
    recorder.register_program(op, sig, bucket, fn, args, impl=impl)


def _pad_rows(arr: jnp.ndarray, n_padded: int) -> jnp.ndarray:
    n = arr.shape[0]
    if n == n_padded:
        return arr
    pad = [(0, n_padded - n)] + [(0, 0)] * (arr.ndim - 1)
    return jnp.pad(arr, pad)


def _pad_lanes(arr: jnp.ndarray, m: int) -> jnp.ndarray:
    """Zero-pad the MINOR axis up to ``m`` (hash matrices tile over the
    lane dimension)."""
    if arr.shape[-1] == m:
        return arr
    pad = [(0, 0)] * (arr.ndim - 1) + [(0, m - arr.shape[-1])]
    return jnp.pad(arr, pad)


# ---------------------------------------------------------------------------
# row-unpack: JCUDF blob -> word planes -> columns
# ---------------------------------------------------------------------------

#: rows the word-plane kernels move per inner step: their VMEM scratch
#: is ``[row_size, 128]`` u32, because Mosaic lowers a strided load or
#: store only on 32-bit data over a 128-lane base
_LANES = 128


def _row_tile(row_size: int) -> int:
    """Rows per grid step of the word-plane kernels (blob tile in +
    word planes out, double-buffered by Pallas): a multiple of
    :data:`_LANES`, at most 8 inner steps."""
    return shapes.vmem_tile(2 * row_size, floor=_LANES, cap=8 * _LANES)


def _unpack_kernel(rows_ref, out_ref, sc_ref):
    # per 128 rows: widen the bytes to u32 and transpose them into the
    # [row_size, 128] scratch; word j of every row is then the OR of
    # scratch rows 4j..4j+3, read with sublane-strided loads (Mosaic
    # lowers neither lane-strided slices nor sub-32-bit strided loads)
    W = out_ref.shape[0]
    for c in range(rows_ref.shape[0] // _LANES):
        rows = pl.ds(c * _LANES, _LANES)
        sc_ref[...] = rows_ref[rows, :].astype(jnp.uint32).T
        w = sc_ref[pl.ds(0, W, stride=4), :]
        for t in range(1, 4):
            w = w | (sc_ref[pl.ds(t, W, stride=4), :] << (8 * t))
        out_ref[:, rows] = w


@functools.partial(jax.jit, static_argnums=(1, 2))
def _from_rows_planes_jit(rows2d: jnp.ndarray, layout, interpret: bool):
    n, rs = rows2d.shape
    W = rs // 4
    tile = _row_tile(rs)
    npad = max(tile, -(-n // tile) * tile)
    rows2d = _pad_rows(rows2d, npad)
    x = pl.pallas_call(
        _unpack_kernel,
        grid=(npad // tile,),
        in_specs=[pl.BlockSpec((tile, rs), lambda r: (r, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((W, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((W, npad), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((rs, _LANES), jnp.uint32)],
        interpret=interpret,
    )(rows2d)
    x = x[:, :n]
    return _cols_from_word_planes(x, layout)


def _cols_from_word_planes(x: jnp.ndarray, layout):
    """Column data + packed validity masks from word planes ``[W, n]``
    (the plane-major twin of ``row_conversion._cols_from_fwords`` —
    value-identical output arrays, but the 64-bit plane pairs and the
    validity byte planes are row slices here, no transposes)."""
    from spark_rapids_jni_tpu.table import (
        byte_planes_from_word_planes, packed_masks_from_byte_planes)
    vo, vb = layout.validity_offset, layout.validity_bytes
    vbT = byte_planes_from_word_planes(
        x[vo // 4:(vo + vb + 3) // 4], vb, vo % 4)
    vmask = packed_masks_from_byte_planes(vbT, layout.num_columns)
    datas = []
    for i, dt in enumerate(layout.dtypes):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        w0 = s // 4
        if sz == 16:                       # decimal128 [n, 4] limbs
            datas.append(x[w0:w0 + 4].T)
        elif sz == 8:
            pair = x[w0:w0 + 2]            # [2, n] lo/hi planes
            if jax.config.jax_enable_x64:
                datas.append(jax.lax.bitcast_convert_type(
                    jax.lax.bitcast_convert_type(pair.T, jnp.uint64),
                    dt.np_dtype))
            else:
                datas.append(pair)         # plane-pair Column layout
        elif sz == 4:
            datas.append(jax.lax.bitcast_convert_type(x[w0], dt.np_dtype))
        elif sz == 2:
            datas.append(jax.lax.bitcast_convert_type(
                ((x[w0] >> (8 * (s % 4))) & 0xFFFF).astype(jnp.uint16),
                dt.np_dtype))
        else:
            d = ((x[w0] >> (8 * (s % 4))) & 0xFF).astype(jnp.uint8)
            if dt.np_dtype != np.uint8:
                d = jax.lax.bitcast_convert_type(d, dt.np_dtype)
            datas.append(d)
    return datas, [vmask[i] for i in range(layout.num_columns)]


def from_rows_fixed(rows2d: jnp.ndarray, layout, *,
                    interpret: bool = False) -> List:
    """Decode a fixed-width JCUDF 2-D blob into Columns via the
    streaming word-plane kernel.  Byte-identical to the XLA word-space
    decode (``row_conversion._from_rows_fixed_jit``)."""
    from spark_rapids_jni_tpu.table import Column
    datas, masks = _from_rows_planes_jit(rows2d, layout, interpret)
    return [Column(dt, datas[i], masks[i])
            for i, dt in enumerate(layout.dtypes)]


# ---------------------------------------------------------------------------
# row-pack: columns -> word planes -> JCUDF blob
# ---------------------------------------------------------------------------

def _word_planes_from_table(table, layout) -> jnp.ndarray:
    """JCUDF word planes ``[W, n]`` uint32 from fixed-width columns —
    the pack-direction inverse of :func:`_cols_from_word_planes`.  Every
    column's little-endian bytes OR-accumulate into its word lane(s)
    (pure bitcasts and static shifts, no gathers: sub-word columns
    shift into their byte slot, 64-bit plane pairs and decimal128 limbs
    contribute whole planes), validity bytes land at the validity
    offset, and alignment gaps stay zero."""
    from spark_rapids_jni_tpu.ops.row_conversion import _validity_row_bytes
    n = table.num_rows
    W = layout.fixed_row_size // 4
    terms: List[List] = [[] for _ in range(W)]

    def put(byte_off, vec):
        sh = 8 * (byte_off % 4)
        terms[byte_off // 4].append(
            vec << jnp.uint32(sh) if sh else vec)

    for i, dt in enumerate(layout.dtypes):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        data = table.columns[i].data
        if sz == 16:                        # decimal128 [n, 4] limbs
            u = (data if data.dtype == jnp.uint32
                 else jax.lax.bitcast_convert_type(data, jnp.uint32))
            for j in range(4):
                put(s + 4 * j, u[:, j])
        elif sz == 8:
            if data.ndim == 2:              # [2, n] lo/hi planes (no-x64)
                put(s, data[0])
                put(s + 4, data[1])
            else:                           # native 64-bit under x64
                pair = jax.lax.bitcast_convert_type(data, jnp.uint32)
                put(s, pair[:, 0])
                put(s + 4, pair[:, 1])
        elif sz == 4:
            u = (data if data.dtype == jnp.uint32
                 else jax.lax.bitcast_convert_type(data, jnp.uint32))
            put(s, u)
        elif sz == 2:
            u16 = (data if data.dtype == jnp.uint16
                   else jax.lax.bitcast_convert_type(data, jnp.uint16))
            put(s, u16.astype(jnp.uint32))
        else:
            if data.dtype == jnp.bool_:
                u8 = data.astype(jnp.uint8)
            elif data.dtype == jnp.uint8:
                u8 = data
            else:
                u8 = jax.lax.bitcast_convert_type(data, jnp.uint8)
            put(s, u8.astype(jnp.uint32))
    vb = _validity_row_bytes(table, layout)    # [n, validity_bytes]
    vo = layout.validity_offset
    for b in range(layout.validity_bytes):
        put(vo + b, vb[:, b].astype(jnp.uint32))
    zero = jnp.zeros((n,), jnp.uint32)
    planes = []
    for ts in terms:
        acc = zero
        for t in ts:
            acc = acc | t
        planes.append(acc)
    return jnp.stack(planes)


def _pack_kernel(w_ref, out_ref, sc_ref):
    # the unpack run backwards: per 128 rows, byte t of word j lands in
    # scratch row 4j+t through sublane-strided stores, and the
    # transposed scratch narrows to the blob's u8 rows
    W = w_ref.shape[0]
    for c in range(w_ref.shape[1] // _LANES):
        rows = pl.ds(c * _LANES, _LANES)
        w = w_ref[:, rows]                     # [W, 128] u32 planes
        for t in range(4):
            sc_ref[pl.ds(t, W, stride=4), :] = (w >> (8 * t)) & 0xFF
        out_ref[rows, :] = sc_ref[...].T.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _to_rows_planes_jit(table, layout, interpret: bool) -> jnp.ndarray:
    n = table.num_rows
    rs = layout.fixed_row_size
    W = rs // 4
    tile = _row_tile(rs)
    planes = _word_planes_from_table(table, layout)
    npad = max(tile, -(-n // tile) * tile)
    planes = _pad_lanes(planes, npad)
    rows = pl.pallas_call(
        _pack_kernel,
        grid=(npad // tile,),
        in_specs=[pl.BlockSpec((W, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((tile, rs), lambda r: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((npad, rs), jnp.uint8),
        scratch_shapes=[pltpu.VMEM((rs, _LANES), jnp.uint32)],
        interpret=interpret,
    )(planes)
    return rows[:n]


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def _to_rows_planes_batch_jit(table, layout, start, size: int,
                              interpret: bool) -> jnp.ndarray:
    """One equal-sized row batch, sliced with a traced start so every
    full batch reuses ONE compiled program (the multi-batch planner's
    contract, see ``_convert_to_rows_impl``)."""
    from spark_rapids_jni_tpu.table import slice_table_dynamic
    if size != table.num_rows:
        table = slice_table_dynamic(table, start, size)
    return _to_rows_planes_jit(table, layout, interpret)


def to_rows_fixed(table, layout, start=None, size: Optional[int] = None,
                  *, interpret: bool = False) -> jnp.ndarray:
    """Encode fixed-width columns into the JCUDF 2-D blob via the
    streaming word-plane pack kernel.  Byte-identical to the XLA pack
    (``row_conversion._to_rows_fixed_jit``)."""
    if size is None:
        return _to_rows_planes_jit(table, layout, interpret)
    return _to_rows_planes_batch_jit(table, layout, start, size,
                                     interpret)


# ---------------------------------------------------------------------------
# hash kernels: murmur3_x86_32 / xxhash64 over column tiles
# ---------------------------------------------------------------------------

def hashable_fixed(cols) -> bool:
    """True when the columns are all fixed-width ≤ 8-byte scalars (no
    strings, no nested children, no decimals) — the original kernel
    coverage, kept as a helper for call sites that need the
    strings-excluded predicate."""
    return all(
        not c.dtype.is_string and not c.children
        and c.dtype.kind != "decimal128" and c.dtype.itemsize <= 8
        for c in cols)


def hash_cols_eligible(cols) -> bool:
    """The ``choose()`` eligibility hook for the hash ops: fixed-width
    ≤ 8-byte scalars plus DENSE-PADDED string columns (the char window
    rides the stacked word matrix; Arrow-layout or width-capped strings
    would need per-row gathers outside the kernel, so they stay on the
    XLA chain).  No nested children, no decimal128."""
    if not cols:
        return False
    for c in cols:
        if c.children or getattr(c, "capped", False):
            return False
        if c.dtype.is_string:
            if not c.is_padded:
                return False
        elif c.dtype.kind == "decimal128" or c.dtype.itemsize > 8:
            return False
    return True


#: widest char window the murmur3 kernel takes: a column that wide
#: runs alone, and its blocks of one chunk, double-buffered, take 67 MB
#: of a v5e's 128 MiB of VMEM at 4 KiB (the compile takes 17 s)
_MM3_MAX_WINDOW = 1 << 12


def _mm3_cols_eligible(cols) -> bool:
    """The murmur3 hook: :func:`hash_cols_eligible`, with string
    windows of at most :data:`_MM3_MAX_WINDOW` bytes."""
    return hash_cols_eligible(cols) and all(
        c.chars2d.shape[1] <= _MM3_MAX_WINDOW
        for c in cols if c.dtype.is_string)


def _string_window(W: int, mode: str) -> int:
    # the bucketed char window block-aligned to the op's stride
    # (murmur3: 4-byte blocks, xxhash64: 8-byte stripes)
    return (W + 3) // 4 * 4 if mode == "mm3" else (W + 7) // 8 * 8


def _hash_desc(cols, W: int, mode: str) -> tuple:
    """The static per-column descriptor of :func:`_hash_mats`, from the
    dtypes alone: ``("f", 2)`` for a fixed column, ``("s", Wp//4)`` for
    a string column."""
    desc = []
    for c in cols:
        if c.dtype.is_string:
            desc.append(("s", _string_window(W, mode) // 4))
        else:
            desc.append(("f", 2))
    return tuple(desc)


def _desc_rows(desc) -> int:
    # word rows a descriptor stacks: a string adds its length row
    return sum(nw + (kind == "s") for kind, nw in desc)


def _hash_mats(cols, W: int, mode: str):
    """ONE stacked word matrix [K, n] in chain order with a static
    per-column descriptor (:func:`_hash_desc`), plus the validity
    matrix [C, n] uint8.

    The xxhash64 kernel's operands (murmur3 reads the columns as
    stored, see :func:`murmur3_cols`).  Fixed columns contribute their
    Spark-normalized (hi, lo) 8-byte block pair — desc ``("f", 2)``.
    String columns contribute the padded char window as ``Wp//4``
    little-endian word rows plus ONE length row — desc ``("s", Wp//4)``
    — where ``Wp`` aligns the bucketed window ``W`` to 8-byte stripes,
    exactly as the XLA string path does."""
    from spark_rapids_jni_tpu.ops import hashing as H
    from spark_rapids_jni_tpu.table import bytes2d_to_words
    n = cols[0].num_rows
    mats = []
    for c in cols:
        if c.dtype.is_string:
            Wp = _string_window(W, mode)
            if Wp:
                mats.append(bytes2d_to_words(c.chars_window(Wp)).T)
            mats.append(c.str_lens().astype(jnp.uint32)[None, :])
        else:
            mats.append(jnp.stack(H._col_u64_blocks(c)))
    wmat = (jnp.concatenate(mats, axis=0) if mats
            else jnp.zeros((0, n), jnp.uint32))
    vmat = jnp.stack([
        (c.valid_bools() if c.validity is not None
         else jnp.ones((n,), jnp.bool_)).astype(jnp.uint8)
        for c in cols])
    return wmat, _hash_desc(cols, W, mode), vmat


#: word rows per hash kernel call.  The kernel unrolls its column chain
#: one step per word row, so its code grows with the rows it takes: one
#: call over all 212 columns of the reference row compiled for a v5e in
#: over a minute into 1.25 GB of code and ran the chip host out of
#: memory.  28 rows (14 xxhash64 columns) compile in about 2 s.
_HASH_WORDS = 28


def _hash_runs(cols, W: int, mode: str):
    """Split ``cols`` into runs of at most :data:`_HASH_WORDS` word rows
    (a wider column runs alone), in chain order."""
    runs, run, rows = [], [], 0
    for c in cols:
        k = _desc_rows(_hash_desc((c,), W, mode))
        if run and rows + k > _HASH_WORDS:
            runs.append(tuple(run))
            run, rows = [], 0
        run.append(c)
        rows += k
    if run:
        runs.append(tuple(run))
    return runs


@functools.partial(jax.jit, static_argnums=(1, 2))
def _hash_prep(col, W: int, mode: str):
    """One column's word rows and validity row: the XLA half of a hash
    call, compiled per dtype (not per run of dtypes, so a schema's
    compiles are bounded by its distinct types)."""
    wmat, _, vmat = _hash_mats((col,), W, mode)
    return wmat, vmat


def _hash_tile(nrows_of_state: int) -> int:
    # lane-dim tiles: a multiple of 128 lanes, ~2MB of hash state, and
    # at most 2048 lanes — the kernel body is unrolled per 128-lane
    # vreg, so a 32768-lane tile (what a narrow key's budget allows)
    # took two minutes to compile for a v5e
    return shapes.vmem_tile(4 * max(1, nrows_of_state),
                            budget=2 << 20, floor=256, cap=2048)


def _mm3_string_lanes(h, wrows, lens):
    """``hashing._mm3_string_col`` replayed words-major over the row
    slice ``wrows`` [nw, m].  Inside a kernel the tail bytes cannot be
    gathered per-row (`take_along_axis` is TPU-illegal), so the word
    holding them is select-captured while the block loop walks the
    static rows, and Java's getByte sign extension is done
    arithmetically instead of via an int8 bitcast round-trip."""
    from spark_rapids_jni_tpu.ops import hashing as H
    nw = wrows.shape[0]
    nblocks = lens // 4
    hc = h
    if nw:
        wtail = jnp.zeros_like(h)
        for j in range(nw):
            hc = jnp.where(j < nblocks, H._mm3_mix_h1(hc, wrows[j]), hc)
            wtail = jnp.where(nblocks == j, wrows[j], wtail)
        for t in range(3):
            pos = nblocks * 4 + t
            byte = (wtail >> jnp.uint32(8 * t)) & jnp.uint32(0xFF)
            k1 = byte | jnp.where(byte >= jnp.uint32(0x80),
                                  jnp.uint32(0xFFFFFF00), jnp.uint32(0))
            hc = jnp.where(pos < lens, H._mm3_mix_h1(hc, k1), hc)
    return H._mm3_fmix(hc, lens)


def _chain_calls(call, mode: str, cols, h, W: int, interpret: bool):
    """Run a hash kernel over ``cols`` in :func:`_hash_runs`, each call
    chaining from the last one's running hash ``h``."""
    column = 0
    for i, run in enumerate(_hash_runs(cols, W, mode)):
        pieces = []
        for c in run:
            with spans.launch("hash.prep", _hash_prep, column=column):
                pieces.append(_hash_prep(c, W, mode))
            column += 1
        desc = _hash_desc(run, W, mode)
        with spans.launch("hash.kernel", call, run=i,
                          words=_desc_rows(desc)):
            h = call(tuple(pieces), h, desc, interpret)
    return h


# -- murmur3 over the resident column buffers --------------------------------
#
# The kernel reads each fixed-width column's own data buffer and packed
# validity, viewed by bitcast-only reshapes: a 1-, 2- or 4-byte column
# as ``[n/128, 128]``, a 64-bit column's ``[2, n]`` lo/hi planes as
# ``(2, rows)`` blocks, its validity bytes as ``[n/1024, 128]``.  Spark's
# normalization (sign or zero extension, float canonicalization) and the
# null skip run in VMEM on a dense ``[_MM3_CHUNK, 128]`` hash state, so
# every uint32 op fills whole vregs.  A dense-padded string column keeps
# one prep program, which lays its char window out as word rows in the
# same dense form.

#: sublane rows of the running hash in one step of the kernel's chunk
#: loop: 8 vregs of uint32, whose validity is 8 full rows of 128 bytes
_MM3_CHUNK = 64

#: table rows one chunk covers; the padded row count is a multiple
_MM3_ROWS = _MM3_CHUNK * _LANES

#: most sublane rows one grid step moves: 64K table rows
_MM3_MAX_BLOCK = 512

#: VMEM for one kernel call's double-buffered blocks: the column
#: blocks of a grid step, twice, stay within it
_MM3_VMEM = 8 << 20

#: columns per murmur3 kernel call.  The chunk body is unrolled over
#: the run's columns, so the compile grows with them, and a wider run
#: takes smaller blocks.  Over the 212-column reference table at 4M
#: rows on a v5e, runs of 9, 27, 54 and 212 columns took 7.5, 6.8, 8.2
#: and 10.1 ms a call and compiled in 0.8, 2.1, 4.0 and 6.3 s
_MM3_COLS = 27

#: columns per call in interpret mode: XLA:CPU fuses a run's whole
#: chain into one loop, and with the loop emitters (the fusion emitters
#: off, as the test suite runs) its compile grows exponentially with the
#: columns: 8 compile in 2 s, 12 in 50 s
_MM3_INTERPRET_COLS = 8


def _mm3_rows(n: int) -> int:
    """Rows the kernel runs over: ``n`` padded to whole chunks."""
    return max(1, -(-n // _MM3_ROWS)) * _MM3_ROWS


#: storage dtypes narrower than 4 bytes, by how Spark widens them
_MM3_NARROW = {np.dtype(np.int8): "i8", np.dtype(np.int16): "i16",
               np.dtype(np.uint8): "u8", np.dtype(np.uint16): "u16",
               np.dtype(np.bool_): "u8"}


def _mm3_kind(col, Wp: int) -> tuple:
    """How the kernel reads one column: ``(kind, nwords, has_validity)``.
    ``kind`` is the storage form that decides the Spark normalization:
    ``i8``/``i16`` sign-extend, ``u8``/``u16`` zero-extend, ``w32`` is
    taken as it is, ``f32`` canonicalized, ``w64``/``f64`` the two
    words of a 64-bit value (raw or canonicalized), ``str`` a
    dense-padded string's ``Wp // 4`` word rows and length row."""
    valid = col.validity is not None
    dt = col.dtype
    if dt.is_string:
        return ("str", Wp // 4, valid)
    is_float = dt.kind.startswith("float")
    if dt.itemsize == 8:
        return ("f64" if is_float else "w64", 2, valid)
    if is_float:
        return ("f32", 1, valid)
    return (_MM3_NARROW.get(col.data.dtype, "w32"), 1, valid)


_MM3_ITEM_BYTES = {"i8": 1, "u8": 1, "i16": 2, "u16": 2, "w32": 4,
                   "f32": 4, "w64": 8, "f64": 8}


def _mm3_row_bytes(kind: tuple) -> float:
    # bytes a table row of this column brings into VMEM
    k, nw, valid = kind
    size = 4 * (nw + 1) if k == "str" else _MM3_ITEM_BYTES[k]
    return size + valid / 8


def _mm3_vmem(width: float, S: int) -> float:
    # the double-buffered blocks of a grid step of S sublane rows, for
    # columns of ``width`` bytes a row plus the hash in and out
    return 2 * S * _LANES * (width + 8)


def _mm3_runs(desc, interpret: bool = False) -> List[Tuple[int, int]]:
    """``[start, end)`` column ranges of the kernel calls, in chain
    order: at most :data:`_MM3_COLS` columns (:data:`_MM3_INTERPRET_COLS`
    in interpret mode), and blocks of one chunk that fit
    :data:`_MM3_VMEM` (a wider column runs alone)."""
    cap = _MM3_INTERPRET_COLS if interpret else _MM3_COLS
    runs, start, width = [], 0, 0.0
    for i, kind in enumerate(desc):
        w = _mm3_row_bytes(kind)
        if i > start and (i - start == cap or
                          _mm3_vmem(width + w, _MM3_CHUNK) > _MM3_VMEM):
            runs.append((start, i))
            start, width = i, 0.0
        width += w
    runs.append((start, len(desc)))
    return runs


def _mm3_block(desc, npad: int) -> int:
    """Sublane rows per grid step: the most chunks whose blocks fit
    :data:`_MM3_VMEM`, up to :data:`_MM3_MAX_BLOCK`, that divide the
    padded rows."""
    width = sum(_mm3_row_bytes(k) for k in desc)
    S = _MM3_CHUNK
    while (2 * S <= _MM3_MAX_BLOCK and (npad // _LANES) % (2 * S) == 0
           and _mm3_vmem(width, 2 * S) <= _MM3_VMEM):
        S *= 2
    return S


class _RefRows:
    """Rows ``[j, r0:r0+rows, :]`` of a ``[nw, S, 128]`` block, loaded on
    first use: ``_mm3_string_lanes`` indexes its word rows one by one."""

    def __init__(self, ref, r0, rows: int, nw: int):
        self.ref, self.r0, self.rows, self.shape = ref, r0, rows, (nw,)

    def __getitem__(self, j):
        return self.ref[j, pl.ds(self.r0, self.rows), :]


def _mm3_column(kind: tuple, d_ref, r0, h):
    """One column's Spark murmur3 step over the chunk at sublane row
    ``r0``, as ``hashing._murmur3_chain`` takes it (words from
    ``hashing._as_u32_words``)."""
    from spark_rapids_jni_tpu.ops import hashing as H
    k, nw, _ = kind
    rows = _MM3_CHUNK
    if k == "str":
        lens = d_ref[nw, pl.ds(r0, rows), :].astype(jnp.int32)
        return _mm3_string_lanes(h, _RefRows(d_ref, r0, rows, nw), lens)
    if k in ("w64", "f64"):
        span = pl.ds(r0 * _LANES, rows * _LANES)
        words = [d_ref[j, span].reshape(rows, _LANES) for j in (0, 1)]
        if k == "f64":
            words = H._canonical_f64_words(*words)
    else:
        x = d_ref[pl.ds(r0, rows), :]
        if k == "f32":
            x = H._canonical_f32_bits(x)
        elif k in ("i8", "i16"):
            x = jax.lax.bitcast_convert_type(x.astype(jnp.int32),
                                             jnp.uint32)
        elif k in ("u8", "u16"):
            x = x.astype(jnp.uint32)
        elif x.dtype != jnp.uint32:
            x = jax.lax.bitcast_convert_type(x, jnp.uint32)
        words = [x]
    for w in words:
        h = H._mm3_mix_h1(h, w)
    return H._mm3_fmix(h, 4 * len(words))


def _mm3_dense_kernel(desc, seed: int, first: bool, last: bool, *refs):
    """The chain over one run of columns for one grid step of ``S``
    sublane rows, a :data:`_MM3_CHUNK`-row chunk at a time.  Validity
    bytes cover 8 sublane rows each: byte ``16*(r%8) + l//8`` of the
    chunk's ``r//8``-th validity row holds row ``(r, l)``'s bit ``l%8``."""
    refs = list(refs)
    vs_ref, o_ref = refs.pop(), refs.pop()
    h_ref = None if first else refs.pop(0)
    it = iter(refs)
    cols, nv = [], 0
    for kind in desc:
        d_ref = next(it)
        if kind[2]:
            # widened once a step, so the chunk loop slices 32-bit tiles
            vs_ref[nv] = next(it)[...].astype(jnp.int32)
            cols.append((kind, d_ref, nv))
            nv += 1
        else:
            cols.append((kind, d_ref, None))
    rows, vrows = _MM3_CHUNK, _MM3_CHUNK // 8
    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    byte_at = 16 * (sub % 8) + lane // 8
    bit_at = lane % 8

    def chunk(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        h = (jnp.full((rows, _LANES), seed, jnp.uint32) if first
             else h_ref[pl.ds(r0, rows), :])
        for kind, d_ref, j in cols:
            hc = _mm3_column(kind, d_ref, r0, h)
            if j is None:
                h = hc
            else:
                v = vs_ref[j, pl.ds(pl.multiple_of(i * vrows, vrows),
                                    vrows), :]
                v = jnp.broadcast_to(v[:, None, :], (vrows, 8, _LANES))
                v = jnp.take_along_axis(v.reshape(rows, _LANES), byte_at,
                                        axis=1)
                h = jnp.where(((v >> bit_at) & 1) != 0, hc, h)
        o_ref[pl.ds(r0, rows), :] = (
            jax.lax.bitcast_convert_type(h, jnp.int32) if last else h)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[0] // rows, chunk, 0)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _mm3_run(cols, h, desc, seed: int, n: int, last: bool,
             interpret: bool):
    """One kernel call over a run of columns, ``(data, validity)`` each
    (a string column's data is its :func:`_mm3_string_prep` block).
    The first run (``h`` None) seeds the hash itself; the others chain
    from ``h`` ``[npad/128, 128]``.  The last returns the int32 ``[n]``
    hash.  Nothing but bitcasts lies between a column buffer whose rows
    fill whole chunks and the kernel."""
    npad = _mm3_rows(n)
    S = _mm3_block(desc, npad)
    # a column too wide for the budget runs alone, with the VMEM its
    # blocks take
    need = int(_mm3_vmem(sum(_mm3_row_bytes(k) for k in desc), S))
    vmem = None if need <= _MM3_VMEM else need + (8 << 20)
    ops, specs = [], []

    def add(x, block, index):
        ops.append(x)
        specs.append(pl.BlockSpec(block, index, memory_space=pltpu.VMEM))

    if h is not None:
        add(h, (S, _LANES), lambda r: (r, 0))
    for (data, validity), (kind, nw, valid) in zip(cols, desc):
        if kind == "str":
            add(data, (nw + 1, S, _LANES), lambda r: (0, r, 0))
        elif kind in ("w64", "f64"):
            if data.ndim == 1:             # native 64-bit under x64
                data = jax.lax.bitcast_convert_type(data, jnp.uint32).T
            add(_pad_lanes(data, npad), (2, S * _LANES), lambda r: (0, r))
        else:
            if data.dtype == jnp.bool_:
                data = data.astype(jnp.uint8)
            add(_pad_rows(data, npad).reshape(-1, _LANES), (S, _LANES),
                lambda r: (r, 0))
        if valid:
            add(_pad_rows(validity, npad // 8).reshape(-1, _LANES),
                (S // 8, _LANES), lambda r: (r, 0))
    nv = max(1, sum(k[2] for k in desc))
    out = pl.pallas_call(
        functools.partial(_mm3_dense_kernel, desc, seed, h is None, last),
        grid=(npad // (S * _LANES),),
        in_specs=specs,
        out_specs=pl.BlockSpec((S, _LANES), lambda r: (r, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(
            (npad // _LANES, _LANES), jnp.int32 if last else jnp.uint32),
        scratch_shapes=[pltpu.VMEM((nv, S // 8, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=vmem),
        interpret=interpret,
    )(*ops)
    return out.reshape(npad)[:n] if last else out


@functools.partial(jax.jit, static_argnums=(1, 2))
def _mm3_string_prep(col, Wp: int, npad: int):
    """A dense-padded string column as the kernel reads it: its
    ``Wp // 4`` little-endian word rows and its length row, each laid
    out ``[npad/128, 128]``."""
    from spark_rapids_jni_tpu.table import bytes2d_to_words
    rows = [col.str_lens().astype(jnp.uint32)[None, :]]
    if Wp:
        rows.insert(0, bytes2d_to_words(col.chars_window(Wp)).T)
    words = _pad_lanes(jnp.concatenate(rows), npad)
    return words.reshape(words.shape[0], npad // _LANES, _LANES)


def murmur3_cols(cols, seed: int, *, W: int = 0,
                 interpret: bool = False) -> jnp.ndarray:
    """Spark murmur3 chain over fixed-width AND dense-padded string
    columns: int32 ``[n]``.  ``W`` is the bucketed char-window width
    shared by all string columns (0 when none).  Bit-exact with
    ``hashing._murmur3_chain``.  Each program launch is one ``launch``
    span: a ``hash.prep`` per string column, a ``hash.kernel`` per run
    of columns (``native_cols`` read from their own buffers,
    ``prep_cols`` through a prep)."""
    cols = tuple(cols)
    n = cols[0].num_rows
    npad = _mm3_rows(n)
    Wp = _string_window(int(W), "mm3")
    desc = tuple(_mm3_kind(c, Wp) for c in cols)
    ops = []
    for i, (c, kind) in enumerate(zip(cols, desc)):
        data = c.data
        if kind[0] == "str":
            with spans.launch("hash.prep", _mm3_string_prep, column=i):
                data = _mm3_string_prep(c, Wp, npad)
        ops.append((data, c.validity))
    seed = int(seed) & 0xFFFFFFFF
    runs = _mm3_runs(desc, interpret)
    h = None
    for r, (a, b) in enumerate(runs):
        prep = sum(k[0] == "str" for k in desc[a:b])
        with spans.launch("hash.kernel", _mm3_run, run=r,
                          native_cols=b - a - prep, prep_cols=prep):
            h = _mm3_run(tuple(ops[a:b]), h, desc[a:b], seed, n,
                         r == len(runs) - 1, interpret)
    return h


#: historical fixed-only entry point; the generalized kernel accepts
#: the same call shape.
murmur3_fixed = murmur3_cols


def _xx64_string_lanes(h, wrows, lens):
    """``hashing._xx64_string_col`` replayed words-major over the row
    slice ``wrows`` [nw, m] (nw = Wp//4, Wp stripe-aligned to 8).  The
    clamped 4-byte-block and tail-byte words are select-captured from
    the static rows, mirroring ``_word_at``'s clamp semantics."""
    from spark_rapids_jni_tpu.ops import hashing as H
    nw = wrows.shape[0]
    zeros = jnp.zeros_like(h[0])

    def w64(j):
        return (wrows[2 * j + 1], wrows[2 * j])

    seed = h
    nchunks = lens // 32
    if nw >= 8:                                # Wp >= 32
        v1 = H._add64(seed, H._const64(H._XXP1_I + H._XXP2_I))
        v2 = H._add64(seed, H._const64(H._XXP2_I))
        v3 = seed
        v4 = H._add64(seed, H._const64(-H._XXP1_I))
        for g in range(nw // 8):
            active = g < nchunks
            v1 = H._where64(active, H._xx_round(v1, w64(4 * g)), v1)
            v2 = H._where64(active, H._xx_round(v2, w64(4 * g + 1)), v2)
            v3 = H._where64(active, H._xx_round(v3, w64(4 * g + 2)), v3)
            v4 = H._where64(active, H._xx_round(v4, w64(4 * g + 3)), v4)
        big = H._add64(H._add64(H._rotl64(v1, 1), H._rotl64(v2, 7)),
                       H._add64(H._rotl64(v3, 12), H._rotl64(v4, 18)))

        def merge(acc, vv):
            acc = H._xor64(acc, H._xx_round((zeros, zeros), vv))
            return H._add64(H._mul64(acc, H._u64(*H._XXP1)),
                            H._u64(*H._XXP4))

        big = merge(merge(merge(merge(big, v1), v2), v3), v4)
        hash_ = H._where64(lens >= 32, big,
                           H._add64(seed, H._u64(*H._XXP5)))
    else:
        hash_ = H._add64(seed, H._u64(*H._XXP5))
    hash_ = H._add64(hash_, (zeros, lens.astype(jnp.uint32)))

    nlongs = lens // 8
    for j in range(nw // 2):
        active = (j >= nchunks * 4) & (j < nlongs)
        k1 = H._xx_round((zeros, zeros), w64(j))
        upd = H._add64(H._mul64(H._rotl64(H._xor64(hash_, k1), 27),
                                H._u64(*H._XXP1)), H._u64(*H._XXP4))
        hash_ = H._where64(active, upd, hash_)

    if nw:
        has4 = (lens % 8) >= 4
        idx32 = jnp.minimum(nlongs * 2, nw - 1)
        w32 = zeros
        for j in range(nw):
            w32 = jnp.where(idx32 == j, wrows[j], w32)
        upd = H._add64(H._mul64(H._rotl64(
            H._xor64(hash_, H._mul64((zeros, w32), H._u64(*H._XXP1))),
            23), H._u64(*H._XXP2)), H._u64(*H._XXP3))
        hash_ = H._where64(has4, upd, hash_)

        tidx = jnp.minimum(nlongs * 2 + has4.astype(jnp.int32), nw - 1)
        wt = zeros
        for j in range(nw):
            wt = jnp.where(tidx == j, wrows[j], wt)
        tail_start = nlongs * 8 + jnp.where(has4, 4, 0).astype(jnp.int32)
        for t in range(3):
            pos = tail_start + t
            byte = (wt >> jnp.uint32(8 * t)) & jnp.uint32(0xFF)
            upd = H._mul64(H._rotl64(
                H._xor64(hash_, H._mul64((zeros, byte),
                                         H._u64(*H._XXP5))),
                11), H._u64(*H._XXP1))
            hash_ = H._where64(pos < lens, upd, hash_)
    return H._xx_fmix(hash_)


def _xx_kernel(desc, w_ref, v_ref, h_ref, o_ref):
    from spark_rapids_jni_tpu.ops import hashing as H
    w = w_ref[...]
    v = v_ref[...]
    zeros = jnp.zeros((w.shape[1],), jnp.uint32)
    h = (h_ref[1, :], h_ref[0, :])             # (hi, lo) from (lo, hi) rows
    k = 0
    for ci, (kind, nw) in enumerate(desc):
        if kind == "s":
            lens = w[k + nw].astype(jnp.int32)
            hc = _xx64_string_lanes(h, w[k:k + nw], lens)
            k += nw + 1
        else:
            blk = (w[k], w[k + 1])             # (hi, lo)
            hc = H._add64(H._add64(h, H._u64(*H._XXP5)), H._u64(0, 8))
            k1 = H._xx_round((zeros, zeros), blk)
            hc = H._xor64(hc, k1)
            hc = H._rotl64(hc, 27)
            hc = H._add64(H._mul64(hc, H._u64(*H._XXP1)),
                          H._u64(*H._XXP4))
            hc = H._xx_fmix(hc)
            k += 2
        val = v[ci] != 0
        h = (jnp.where(val, hc[0], h[0]), jnp.where(val, hc[1], h[1]))
    o_ref[...] = jnp.stack([h[1], h[0]])       # (lo, hi) rows


@functools.partial(jax.jit, static_argnums=(2, 3))
def _xx64_call(pieces, h, desc, interpret: bool) -> jnp.ndarray:
    """One kernel call over a run's per-column rows, chaining from the
    running hash ``h`` ([2, n] uint32 (lo, hi) rows); keyed on ``desc`` like
    :func:`_mm3_call` (every fixed column is one 8-byte block here, so
    all-fixed runs of one length share a kernel whatever their dtypes)."""
    wmat = jnp.concatenate([w for w, _ in pieces])
    vmat = jnp.concatenate([v for _, v in pieces])
    K, C, n = wmat.shape[0], vmat.shape[0], h.shape[1]
    tile = _hash_tile(K + C + 4)
    npad = max(tile, -(-n // tile) * tile)
    out = pl.pallas_call(
        functools.partial(_xx_kernel, desc),
        grid=(npad // tile,),
        in_specs=[
            pl.BlockSpec((K, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((C, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((2, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((2, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((2, npad), jnp.uint32),
        interpret=interpret,
    )(_pad_lanes(wmat, npad), _pad_lanes(vmat, npad), _pad_lanes(h, npad))
    return out[:, :n]


def xxhash64_cols(cols, seed: int, *, W: int = 0,
                  interpret: bool = False) -> jnp.ndarray:
    """Spark xxhash64 chain over fixed-width AND dense-padded string
    columns ([n, 2] uint32 lo/hi, the ``hashing.xxhash64`` contract).
    ``W`` is the bucketed char-window width shared by all string
    columns (0 when none).  Bit-exact with ``hashing._xx64_chain``."""
    cols = tuple(cols)
    with spans.launch("hash.seed", _xx64_seed):
        h = _xx64_seed(cols[0].num_rows, np.uint32(seed))
    h = _chain_calls(_xx64_call, "xx64", cols, h, int(W), interpret)
    with spans.launch("hash.finish", _xx64_finish):
        return _xx64_finish(h)


@functools.partial(jax.jit, static_argnums=(0,))
def _xx64_seed(n: int, seed) -> jnp.ndarray:
    return jnp.stack([jnp.full((n,), seed, jnp.uint32),
                      jnp.zeros((n,), jnp.uint32)])


@jax.jit
def _xx64_finish(h) -> jnp.ndarray:
    return h.T


#: historical fixed-only entry point; the generalized kernel accepts
#: the same call shape.
xxhash64_fixed = xxhash64_cols


# ---------------------------------------------------------------------------
# get_json scan kernel: the path automaton over VMEM char tiles
# ---------------------------------------------------------------------------

def _gjo_scan_kernel(segs, max_key_len, W, chT_ref, o_ref):
    """One row tile of the get_json path automaton.  The char window
    rides transposed ([W, tile]) so rows are lanes; the automaton's
    ``step`` replays inside a ``fori_loop`` over the W positions with
    the per-position char row loaded at a dynamic sublane offset (a
    plain VMEM strided load — no gathers)."""
    from spark_rapids_jni_tpu.ops.get_json import _automaton_pieces
    make_carry0, step = _automaton_pieces(segs, max_key_len, jnp.int32)
    m = o_ref.shape[1]
    c0 = make_carry0(m)
    # bool carries cross the loop as int32 (Mosaic keeps a bool loop
    # carry in 8-bit lanes, which a v5e cannot truncate back to bool)
    flags = [k for k, v in c0.items() if v.dtype == jnp.bool_]

    def to_i32(c):
        return {k: v.astype(jnp.int32) if k in flags else v
                for k, v in c.items()}

    def body(i, c):
        row = chT_ref[pl.ds(i, 1), :][0]
        c = {k: v != 0 if k in flags else v for k, v in c.items()}
        return to_i32(step(c, (i, row))[0])

    st = jax.lax.fori_loop(0, W, body, to_i32(c0))
    o_ref[...] = jnp.stack([
        st["start"], st["end"],
        st["found"], st["capturing"], st["bad"], st["deep"]])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _gjo_scan_pallas_jit(ch, segs, max_key_len: int, tile: int,
                         interpret: bool):
    n, W = ch.shape
    npad = max(tile, -(-n // tile) * tile)
    # [W, npad] int32: the kernel reads one char row at a dynamic
    # sublane offset, which Mosaic lowers for 32-bit data only
    chT = _pad_lanes(ch.T.astype(jnp.int32), npad)
    o = pl.pallas_call(
        functools.partial(_gjo_scan_kernel, segs, max_key_len, W),
        grid=(npad // tile,),
        in_specs=[pl.BlockSpec((W, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((6, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((6, npad), jnp.int32),
        interpret=interpret,
    )(chT)
    o = o[:, :n]
    return dict(start=o[0], end=o[1], found=o[2] != 0,
                capturing=o[3] != 0, bad=o[4] != 0, deep=o[5] != 0)


def get_json_scan(ch, segs, max_key_len: int, *,
                  interpret: bool = False, tile_rows: int = 0):
    """Run the get_json path automaton over ``ch [n, W]`` (bucketed
    fixed-max-len char windows) as a Pallas row-tile grid, the
    state-transition tables VMEM-resident.  Returns the same
    ``start/end/found/capturing/bad/deep`` fields ``_scan_automaton``'s
    final carry exposes (bool fields as bools), so the downstream
    extract/assemble chain is shared verbatim."""
    if tile_rows <= 0:
        # per-lane VMEM: the int32 char column (4W bytes) + ~40B carry
        tile_rows = shapes.vmem_tile(4 * ch.shape[1] + 64, budget=2 << 20,
                                     floor=256, cap=1 << 15)
    return _gjo_scan_pallas_jit(ch, tuple(segs), int(max_key_len),
                                int(tile_rows), bool(interpret))


# ---------------------------------------------------------------------------
# bloom probe fused with its hashes, bitset VMEM-resident
# ---------------------------------------------------------------------------

def _hash_long(lo, hi, seeds):
    """jnp twin of ``spark_bloom._hash_long`` (Murmur3 hashLong: low
    word, then high, fmix length 8) on the hashing helpers."""
    from spark_rapids_jni_tpu.ops import hashing as H
    return H._mm3_fmix(H._mm3_mix_h1(H._mm3_mix_h1(seeds, lo), hi), 8)


def _bloom_body(bits, lo, hi, valid, k: int, num_bits: int):
    """Shared probe math (int-exact twin of Spark's mightContainLong):
    runs inside the Pallas kernel and as the plain-XLA device lowering."""
    zeros = jnp.zeros_like(lo)
    h1 = _hash_long(lo, hi, zeros)
    h2 = _hash_long(lo, hi, h1)
    ok = jnp.ones(lo.shape, jnp.uint32)
    for i in range(1, k + 1):
        combined = jax.lax.bitcast_convert_type(
            h1 + jnp.uint32(i) * h2, jnp.int32)
        combined = jnp.where(combined < 0, ~combined, combined)
        idx = combined % jnp.int32(num_bits)
        word = bits[idx >> 5]
        ok = ok & ((word >> (idx & 31).astype(jnp.uint32)) & 1)
    return (ok != 0) & (valid != 0)


def _bloom_kernel(k, num_bits, bits_ref, lo_ref, hi_ref, v_ref, o_ref):
    bits = bits_ref[0]
    out = _bloom_body(bits, lo_ref[0], hi_ref[0], v_ref[0], k, num_bits)
    o_ref[...] = out.astype(jnp.uint8)[None, :]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _bloom_pallas_jit(bits32, lo, hi, valid, k: int, num_bits: int,
                      interpret: bool) -> jnp.ndarray:
    n = lo.shape[0]
    nw = bits32.shape[0]
    # budget: bitset (constant block, resident across tiles) + per-tile
    # row state; the bitset side is the dominant term for real filters
    tile = _hash_tile(8)
    npad = max(tile, -(-n // tile) * tile)
    mats = [_pad_lanes(a[None, :], npad)
            for a in (lo, hi, valid.astype(jnp.uint8))]
    out = pl.pallas_call(
        functools.partial(_bloom_kernel, k, num_bits),
        grid=(npad // tile,),
        in_specs=[
            # constant index map: the bitset block is identical for every
            # grid step, so it is fetched once and stays VMEM-resident
            pl.BlockSpec((1, nw), lambda r: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda r: (0, r),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda r: (0, r),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.uint8),
        interpret=interpret,
    )(bits32[None, :], *mats)
    return out[0, :n] != 0


@functools.partial(jax.jit, static_argnums=(4, 5))
def bloom_might_contain_xla(bits32, lo, hi, valid, k: int,
                            num_bits: int) -> jnp.ndarray:
    """The same probe math as one generic XLA program (the ``impl=xla``
    leg of the bench comparison and the kill-switch path)."""
    return _bloom_body(bits32, lo, hi, valid.astype(jnp.uint8), k,
                       num_bits)


def bloom_might_contain(bits32, lo, hi, valid, k: int, num_bits: int,
                        *, interpret: bool = False) -> jnp.ndarray:
    """Fused hash+probe over a VMEM-resident uint32 bitset.  ``bits32``
    is the filter's long[] bitset viewed as little-endian uint32 pairs;
    ``lo``/``hi`` the value words; returns bool [n] (null rows False).
    Requires ``num_bits < 2**31`` (int32 modulus) — callers gate."""
    return _bloom_pallas_jit(bits32, lo, hi, valid, k, num_bits,
                             interpret)


# ---------------------------------------------------------------------------
# per-op eligibility: sig shapes the kernel cannot tile fall to XLA
# ---------------------------------------------------------------------------

def _rows_sig_eligible(sig) -> bool:
    # sig = (num_columns, fixed_row_size): word-plane tiling needs a
    # word-aligned, non-empty row
    return sig[1] > 0 and sig[1] % 4 == 0


#: widest get_json char window the scan kernel takes: at the 256-lane
#: tile floor its int32 char block fits a v5e's 16 MB of scoped VMEM up
#: to 8 KiB chars (16 KiB does not compile)
_GJO_MAX_WINDOW = 1 << 13


def _gjo_sig_eligible(sig) -> bool:
    # sig = (num_path_segments, char_window): at least one segment and a
    # window the row-tile chooser can hold in VMEM
    return sig[0] >= 1 and 1 <= sig[1] <= _GJO_MAX_WINDOW


def _shape_hook(pred):
    # a hook on the signature alone: sig=None (undescribed) never vetoes
    return lambda sig, platform: sig is None or pred(sig)


def _bloom_eligible(sig, platform) -> bool:
    # the bloom probe reads its VMEM bitset through a 1-D gather, which
    # Mosaic cannot lower (it lowers 2-D gathers only): on a TPU, XLA
    # serves it; elsewhere the kernel runs in interpret mode
    return platform != "tpu"


#: ``choose()``'s per-op hooks ``(sig, platform) -> bool``; ops absent
#: here are always eligible.  Hash-op sigs are the column tuples
#: themselves, the rest are static shape tuples — see each predicate.
_ELIGIBLE = {
    "murmur3_hash": _shape_hook(_mm3_cols_eligible),
    "xxhash64": _shape_hook(hash_cols_eligible),
    "get_json_object": _shape_hook(_gjo_sig_eligible),
    "convert_to_rows": _shape_hook(_rows_sig_eligible),
    "convert_from_rows": _shape_hook(_rows_sig_eligible),
    "bloom_might_contain": _bloom_eligible,
}
