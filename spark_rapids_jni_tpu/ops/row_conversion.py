"""Table <-> JCUDF row-blob conversion (the framework's flagship op).

Capability parity with the reference's row conversion engine
(``src/main/cpp/src/row_conversion.cu``; public API
``src/main/cpp/src/row_conversion.hpp:27-49``), re-designed TPU-first:

- The reference tiles tables into 48KB shared-memory blocks and moves bytes
  with ``cuda::memcpy_async`` warps.  Here the whole fixed-width transpose is
  expressed as XLA byte-matrix ops (bitcast + concatenate) that XLA fuses
  into a single memory-bound pass, with an optional Pallas kernel
  (``row_kernels.py``) that owns the tiling explicitly (grid over row tiles,
  VMEM-resident row blocks).
- The reference's two independent implementations (legacy
  ``*_fixed_width_optimized`` vs tiled) form its test oracle
  (``src/main/cpp/tests/row_conversion.cpp``).  We keep that strategy:
  :func:`convert_to_rows_fixed_width_optimized` is a deliberately different
  algorithm (precomputed byte-gather maps) cross-checked against
  :func:`convert_to_rows` by the test suite.
- Row batching: output row blobs are split into <=2GB batches with 32-row
  aligned splits so int32 offsets stay valid (reference
  ``row_conversion.cu:96-103, 1460-1539``); the data-dependent split point
  for string tables requires a device->host sync exactly as the reference
  syncs at ``build_batches`` (``row_conversion.cu:1521``).
- Strings: two-pass (size scan, then copy) like the reference
  (``build_string_row_offsets`` ``row_conversion.cu:216-261``,
  ``copy_strings_to_rows`` ``:827-875``); the ragged char copy is a
  repeat+scatter (to rows) / repeat+gather (from rows) in XLA rather than a
  warp memcpy loop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.table import (
    Column, DType, Table, bytes2d_to_words, pack_bools, pack_bools_2d,
    slice_table, unpack_bools,
)
from spark_rapids_jni_tpu.ops.row_layout import (
    JCUDF_ROW_ALIGNMENT, MAX_BATCH_BYTES, RowLayout, compute_row_layout,
)
from spark_rapids_jni_tpu.utils.tracing import func_range
from spark_rapids_jni_tpu.utils import metrics as _utils_metrics
from spark_rapids_jni_tpu.utils import tracing
from spark_rapids_jni_tpu.obs import metrics as _obs_metrics
from spark_rapids_jni_tpu.obs import span_fn
from spark_rapids_jni_tpu.obs import spans as _obs_spans
from spark_rapids_jni_tpu.runtime import shapes
from spark_rapids_jni_tpu.runtime import staging


def _count_string_bytes(direction: str, nbytes: int) -> None:
    """``srj_tpu_rows_string_bytes_total{direction}``: the string bytes a
    row encode wrote (``encode``) or a decode read (``decode``) -- the
    chars on the compact path; rows x window width on the padded path,
    whose lengths live only on the device.  Counted when executing
    eagerly, as spans are."""
    if nbytes and _utils_metrics.eager():
        _obs_metrics.counter(
            "srj_tpu_rows_string_bytes_total",
            "string bytes of JCUDF row encodes and decodes: the chars "
            "of compact rows; rows x window width of padded rows",
            ("direction",)).inc(int(nbytes), direction=direction)


# ---------------------------------------------------------------------------
# Byte views
# ---------------------------------------------------------------------------

def col_to_bytes(data: jnp.ndarray, dt: DType = None) -> jnp.ndarray:
    """View a fixed-width column as little-endian bytes, shape [n, itemsize].

    ``dt`` disambiguates 2-D data: an 8-byte dtype means [2, n] uint32
    plane pairs (the no-x64/TPU representation, see ``Column.from_numpy``
    — the row-major byte view needs one transpose, oracle/fallback-path
    cost only); anything else (decimal128's [n, 4] limbs) is already
    row-major.  Without ``dt`` a 2-row 2-D array is assumed plane-pair.
    """
    if data.ndim == 2:
        is_pair = (dt.itemsize == 8 if dt is not None
                   else data.shape[0] == 2)
        if is_pair:  # [2, n] uint32 planes -> [n, 8]
            n = data.shape[1]
            return jax.lax.bitcast_convert_type(
                data.T, jnp.uint8).reshape(n, -1)
        return jax.lax.bitcast_convert_type(
            data, jnp.uint8).reshape(data.shape[0], -1)
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.uint8)
    if data.dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(data, jnp.uint8)[:, None]
    return jax.lax.bitcast_convert_type(data, jnp.uint8)


def bytes_to_col(b: jnp.ndarray, np_dtype, dt: DType = None) -> jnp.ndarray:
    """Inverse of :func:`col_to_bytes`: [n, itemsize] uint8 -> [n] dtype
    (or [2, n] uint32 plane pairs for 64-bit dtypes when x64 is
    disabled; [n, 4] uint32 limbs for decimal128)."""
    if dt is not None and dt.kind == "decimal128":
        return jax.lax.bitcast_convert_type(
            b.reshape(-1, 4, 4), jnp.uint32)
    target = jnp.dtype(np_dtype)
    if target.itemsize == 8 and not jax.config.jax_enable_x64:
        return jax.lax.bitcast_convert_type(
            b.reshape(-1, 2, 4), jnp.uint32).T
    if target.itemsize == 1:
        return jax.lax.bitcast_convert_type(b[:, 0], target)
    return jax.lax.bitcast_convert_type(b, target)


def _validity_row_bytes(table: Table, layout: RowLayout) -> jnp.ndarray:
    """Validity bytes per row, shape [n, layout.validity_bytes].

    Byte ``c // 8``, bit ``c % 8`` of column ``c``; 1 = valid (reference
    ``copy_validity_to_rows`` ballot transpose, ``row_conversion.cu:748-777``).
    """
    n = table.num_rows
    out = []
    for b in range(layout.validity_bytes):
        acc = jnp.zeros((n,), dtype=jnp.uint8)
        for j in range(8):
            c = b * 8 + j
            if c >= layout.num_columns:
                break
            col = table.column(c)
            if col.validity is None:
                acc = acc | jnp.uint8(1 << j)
            else:
                acc = acc | (col.valid_bools().astype(jnp.uint8) << j)
        out.append(acc)
    return jnp.stack(out, axis=1) if out else jnp.zeros((n, 0), jnp.uint8)


# ---------------------------------------------------------------------------
# Output container: the LIST<INT8> column analogue
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class RowsColumn:
    """One batch of JCUDF rows: the cudf ``LIST<INT8>`` column the reference
    returns (``row_conversion.cu:1871-1887``): a flat byte buffer plus int32
    row offsets (``offsets[i]`` .. ``offsets[i+1]`` is row ``i``).

    ``row_size``/``str_widths`` are set on *dense-padded* variable-width
    batches: every row occupies ``row_size`` bytes with string column ``si``
    in a fixed ``str_widths[si]``-byte slot (chars addressed by each row's
    (offset, length) pairs, so the blob is self-describing JCUDF — identical
    logical content to the compact wire form, with per-row slack).  Padded
    batches decode via static slices instead of per-row gathers."""

    data: jnp.ndarray      # uint8: [num_rows, row_bytes] device-native,
                           # or flat [total_bytes] (wire/oracle form).
                           # Uniform-size batches stay 2-D on device --
                           # flattening a tiled uint8 matrix is a
                           # measured ~17.5 ms/GB relayout the host/wire
                           # boundary alone should pay.
    offsets: jnp.ndarray   # int32 [num_rows + 1]
    row_size: Optional[int] = None
    str_widths: Optional[Tuple[int, ...]] = None

    @property
    def num_rows(self) -> int:
        return self.offsets.shape[0] - 1

    @property
    def is_padded(self) -> bool:
        return self.row_size is not None

    def rows2d(self, row_size: int) -> jnp.ndarray:
        """[n, row_size] view (2-D passthrough; flat blobs reshape --
        call under jit where possible, see ``data`` comment)."""
        if self.data.ndim == 2:
            return self.data
        return self.data.reshape(-1, row_size)

    def row_bytes(self, i: int) -> bytes:
        offs = np.asarray(self.offsets)
        return np.asarray(self.data).reshape(-1)[
            offs[i]:offs[i + 1]].tobytes()

    def tree_flatten(self):
        return (self.data, self.offsets), (self.row_size, self.str_widths)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


# ---------------------------------------------------------------------------
# Batch planning (host side, mirrors reference build_batches)
# ---------------------------------------------------------------------------

def plan_fixed_batches(num_rows: int, row_size: int,
                       size_limit: int = MAX_BATCH_BYTES) -> List[Tuple[int, int]]:
    """Split [0, num_rows) into batches of <= size_limit bytes, 32-row aligned
    (reference ``build_batches`` ``row_conversion.cu:1460-1539``; 32-row
    alignment keeps validity words intact across splits ``:1506``)."""
    if num_rows == 0:
        return [(0, 0)]
    max_rows = (size_limit // row_size) // 32 * 32
    if max_rows == 0:
        if num_rows <= 32 and num_rows * row_size <= size_limit:
            max_rows = num_rows
        else:
            raise ValueError(
                f"size_limit {size_limit} cannot hold a 32-row-aligned batch "
                f"of {row_size}-byte rows")
    batches = []
    start = 0
    while start < num_rows:
        end = min(num_rows, start + max_rows)
        batches.append((start, end))
        start = end
    return batches


def plan_variable_batches(row_sizes: np.ndarray,
                          size_limit: int = MAX_BATCH_BYTES) -> List[Tuple[int, int]]:
    """Split rows with per-row sizes into <=size_limit batches, 32-row aligned."""
    n = len(row_sizes)
    if n == 0:
        return [(0, 0)]
    cum = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(row_sizes, out=cum[1:])
    batches = []
    start = 0
    while start < n:
        # largest end with cum[end] - cum[start] <= limit
        end = int(np.searchsorted(cum, cum[start] + size_limit, side="right")) - 1
        if end < n:
            end = max(start + 32, end // 32 * 32)
        end = min(end, n)
        if end <= start:
            end = min(n, start + 32)
        if cum[end] - cum[start] > size_limit and end - start <= 32:
            raise ValueError("rows too large for a single batch")
        batches.append((start, end))
        start = end
    return batches


# ---------------------------------------------------------------------------
# Optimized fixed-width path (XLA concat; Pallas variant in row_kernels)
# ---------------------------------------------------------------------------

def _assemble_fixed_rows(table: Table, layout: RowLayout) -> jnp.ndarray:
    """Build the [n, fixed_row_size] uint8 row matrix with one fused XLA
    concatenate: per-column byte views interleaved with padding, validity
    bytes, tail padding.  XLA lowers this to parallel copies into a single
    buffer — the tiling/coalescing work the reference does by hand with
    shared-memory tiles is the compiler's job here."""
    n = table.num_rows
    body = _assemble_fixed_variable(table, [], layout)
    tail = layout.fixed_row_size - layout.fixed_end
    if tail > 0:
        body = jnp.concatenate(
            [body, jnp.zeros((n, tail), jnp.uint8)], axis=1)
    return body


@functools.partial(jax.jit, static_argnums=(1, 3))
def _to_rows_fixed_jit(table: Table, layout: RowLayout,
                       start=0, size=None) -> jnp.ndarray:
    from spark_rapids_jni_tpu.table import slice_table_dynamic
    if size is not None and size != table.num_rows:
        table = slice_table_dynamic(table, start, size)
    # 2-D [n, rs]: blobs stay unflattened on device (all fixed-path
    # engines agree on the shape so cross-engine byte compares line up)
    return _assemble_fixed_rows(table, layout)


def _disassemble_fixed_rows(rows2d: jnp.ndarray,
                            layout: RowLayout) -> List[Column]:
    """Inverse of :func:`_assemble_fixed_rows` for the fixed-width section.

    Decodes in uint32 WORD space: one strided-lane combine turns the blob
    into per-row words (``bytes2d_to_words`` — static slices only, no
    gather, no ``[n, W, 4]`` intermediate) and every column is then a
    contiguous word-column slice + shift (``_col_from_words``).  This is
    the root-cause fix for BENCH_r05's ``from_rows`` failures: the
    previous decode bitcast narrow per-column ``[n, size]`` uint8
    windows (``bytes_to_col``), and those sub-word bitcasts — like the
    per-row dynamic-start gathers of the oracle path — are not legal
    under the TPU backend (``INVALID_ARGUMENT: TPU backend error``).
    Word space is the same trick the pack side uses for its char scatter
    (``_to_rows_variable_jit``) and the padded-variable decode already
    runs (``padded_cols_from_rows`` mode "xla")."""
    if layout.has_strings:
        raise ValueError("string columns require the variable-width path")
    fe_pad = (layout.fixed_end + 3) // 4 * 4
    f_words = bytes2d_to_words(rows2d[:, :fe_pad])      # [n, fe_pad/4]
    datas, masks, _ = _cols_from_fwords(f_words, layout)
    return [Column(dt, datas[i], masks[i])
            for i, dt in enumerate(layout.dtypes)]


@functools.partial(jax.jit, static_argnums=(1,))
def _from_rows_fixed_jit(rows2d: jnp.ndarray, layout: RowLayout):
    return _disassemble_fixed_rows(rows2d, layout)


# ---------------------------------------------------------------------------
# Oracle: independent byte-gather implementation (the "legacy path")
# ---------------------------------------------------------------------------

def _oracle_gather_maps(layout: RowLayout) -> Tuple[np.ndarray, np.ndarray]:
    """Static per-row-byte source maps.  ``src[j]`` indexes into the packed
    column-byte matrix for data bytes, ``vsrc[j]`` indexes validity bytes;
    -1 means "not this source" (padding -> zero)."""
    starts_packed = np.cumsum([0] + list(layout.col_sizes))[:-1]
    src = -np.ones(layout.fixed_row_size, dtype=np.int32)
    vsrc = -np.ones(layout.fixed_row_size, dtype=np.int32)
    for i in range(layout.num_columns):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        for b in range(sz):
            src[s + b] = starts_packed[i] + b
    for b in range(layout.validity_bytes):
        vsrc[layout.validity_offset + b] = b
    assert not np.any((src >= 0) & (vsrc >= 0)), "data/validity slot overlap"
    return src, vsrc


@functools.partial(jax.jit, static_argnums=(1, 3))
def _oracle_to_rows_batch_jit(table: Table, layout: RowLayout,
                              start, size: int) -> jnp.ndarray:
    """One row-batch through the gather oracle, sliced with a traced start
    (equal-sized batches share one executable) — lets the oracle run the
    4M-row axis it cannot hold single-shot (HBM), so the bench's
    ``vs_baseline`` cross-check covers the largest axis too."""
    from spark_rapids_jni_tpu.table import slice_table_dynamic
    if size != table.num_rows:
        table = slice_table_dynamic(table, start, size)
    return _oracle_to_rows_jit(table, layout)


@functools.partial(jax.jit, static_argnums=(1,))
def _oracle_to_rows_jit(table: Table, layout: RowLayout) -> jnp.ndarray:
    packed = jnp.concatenate(
        [col_to_bytes(c.data, c.dtype) for c in table.columns], axis=1)
    vb = _validity_row_bytes(table, layout)
    src, vsrc = _oracle_gather_maps(layout)
    src_j = jnp.asarray(np.maximum(src, 0))
    vsrc_j = jnp.asarray(np.maximum(vsrc, 0))
    data_part = packed[:, src_j]
    val_part = vb[:, vsrc_j] if layout.validity_bytes else jnp.zeros_like(data_part)
    rows = jnp.where(jnp.asarray(src >= 0)[None, :], data_part,
                     jnp.where(jnp.asarray(vsrc >= 0)[None, :], val_part,
                               jnp.uint8(0)))
    return rows


@functools.partial(jax.jit, static_argnums=(1,))
def _oracle_from_rows_jit(rows2d: jnp.ndarray, layout: RowLayout):
    """Oracle inverse: per-element dynamic-slice gathers (distinct from the
    slicing implementation in ``_disassemble_fixed_rows``)."""
    n = rows2d.shape[0]
    flat = rows2d.reshape(-1)
    rs = layout.fixed_row_size
    row_base = jnp.arange(n, dtype=jnp.int32) * rs
    cols = []
    for i, dt in enumerate(layout.dtypes):
        s, sz = layout.col_starts[i], layout.col_sizes[i]
        idx = row_base[:, None] + (s + jnp.arange(sz, dtype=jnp.int32))[None, :]
        byte_slice = flat[idx]
        vbyte = flat[row_base + layout.validity_offset + i // 8]
        valid = ((vbyte >> (i % 8)) & 1).astype(jnp.bool_)
        data = bytes_to_col(byte_slice, None if dt.kind == "decimal128"
                            else dt.np_dtype, dt)
        cols.append(Column(dt, data, pack_bools(valid)))
    return Table(tuple(cols))


# ---------------------------------------------------------------------------
# Public API — fixed-width-optimized (oracle) variants
# ---------------------------------------------------------------------------

def _batch_rows2d(rows2d: jnp.ndarray, layout: RowLayout,
                  size_limit: int) -> List[RowsColumn]:
    n = rows2d.shape[0]
    rs = layout.fixed_row_size
    out = []
    for start, end in plan_fixed_batches(n, rs, size_limit):
        chunk = rows2d[start:end]            # 2-D batch (see RowsColumn)
        offsets = jnp.arange(end - start + 1, dtype=jnp.int32) * rs
        out.append(RowsColumn(chunk, offsets))
    return out


@span_fn(attrs=lambda table, **k: {"rows": table.num_rows})
@func_range()
def convert_to_rows_fixed_width_optimized(
        table: Table, *, size_limit: int = MAX_BATCH_BYTES) -> List[RowsColumn]:
    """Oracle path: fixed-width tables only (parity with the reference legacy
    path which rejects strings, ``row_conversion.cu:2019``)."""
    layout = compute_row_layout(table.dtypes)
    if layout.has_strings:
        raise ValueError("fixed-width-optimized path does not support strings")
    from spark_rapids_jni_tpu.ops import pallas_kernels
    sig = (layout.num_columns, layout.fixed_row_size)
    impl, interp = pallas_kernels.choose(
        "convert_to_rows", _platform_of(table), sig=sig)
    if impl == "pallas":
        from spark_rapids_jni_tpu.runtime import resilience

        def _primary(t):
            pallas_kernels.stamp_impl("pallas", interp)
            return pallas_kernels.to_rows_fixed(t, layout,
                                                interpret=interp)

        def _twin(t):
            pallas_kernels.stamp_impl("xla")
            return _oracle_to_rows_jit(t, layout)

        rows2d = resilience.run("convert_to_rows", _primary, table,
                                sig=sig, bucket=table.num_rows,
                                impl="pallas", fallback=_twin)
    else:
        pallas_kernels.stamp_impl("xla")
        rows2d = _oracle_to_rows_jit(table, layout)
    return _batch_rows2d(rows2d, layout, size_limit)


@span_fn(attrs=lambda rows, dtypes: {"rows": rows.num_rows,
                                     "bytes": int(rows.data.size)})
@func_range()
def convert_from_rows_fixed_width_optimized(
        rows: RowsColumn, dtypes: Sequence[DType]) -> Table:
    layout = compute_row_layout(dtypes)
    if layout.has_strings:
        raise ValueError("fixed-width-optimized path does not support strings")
    rows2d = rows.rows2d(layout.fixed_row_size)
    return _oracle_from_rows_jit(rows2d, layout)


# ---------------------------------------------------------------------------
# Public API — optimized path (XLA / Pallas)
# ---------------------------------------------------------------------------

def _resolve_impl(impl: Optional[str], use_pallas: Optional[bool],
                  platform: str) -> str:
    """Pick the fixed-width engine: ``mxu`` (permutation matmul on the
    systolic array — the TPU hot path), ``xla`` (fused concatenate), or
    ``pallas`` (explicitly tiled kernel).  Auto: mxu on TPU, xla
    elsewhere — unless the ``SRJ_TPU_PALLAS`` knob overrides it (``0``
    forces the generic XLA lowering everywhere, the kill switch out of
    a misbehaving kernel engine; ``1`` forces the explicitly tiled
    Pallas kernels, interpret-mode off-TPU)."""
    if impl is not None:
        if impl not in ("mxu", "xla", "pallas"):
            raise ValueError(f"unknown impl {impl!r}; "
                             "expected 'mxu', 'xla' or 'pallas'")
        return impl
    if use_pallas:
        return "pallas"
    if use_pallas is not None:  # explicit False
        return "xla"
    from spark_rapids_jni_tpu.ops import pallas_kernels
    k = pallas_kernels.knob()
    if k == "0":
        return "xla"
    if k == "1":
        return "pallas"
    return "mxu" if platform == "tpu" else "xla"


def _trim_row_batches(batches: List[RowsColumn], n: int
                      ) -> List[RowsColumn]:
    """Slice a padded-table encode back to ``n`` total rows: drop whole
    padding batches, row-slice the batch straddling ``n`` (offsets are
    uniform per batch, so ``offsets[:keep+1]`` stays consistent)."""
    out, done = [], 0
    for bc in batches:
        k = bc.num_rows
        keep = min(k, n - done)
        if keep == k:
            out.append(bc)
        else:
            rs = (bc.data.shape[1] if bc.data.ndim == 2
                  else bc.data.size // max(k, 1))
            data = (bc.data[:keep] if bc.data.ndim == 2
                    else bc.data[:keep * rs])
            out.append(RowsColumn(data, bc.offsets[:keep + 1],
                                  bc.row_size, bc.str_widths))
        done += keep
        if done >= n:
            break
    return out


def _pad_rows_blob(bc: RowsColumn, b: int, rs: int) -> RowsColumn:
    """Pad a row blob to ``b`` rows of zeros (zero validity bytes decode
    as all-null rows, which the post-decode slice then drops).  The pad
    runs through the donated fill (``shapes.pad_to``): the bucketed blob
    is written into a donated scratch, so padding never holds two copies
    of the row bytes."""
    n = bc.num_rows
    if bc.data.ndim == 2:
        data = shapes.pad_to(bc.data, (b, bc.data.shape[1]))
    else:
        data = shapes.pad_to(bc.data, (b * rs,))
    offsets = jnp.asarray(np.arange(b + 1, dtype=np.int32) * rs)
    return RowsColumn(data, offsets, bc.row_size, bc.str_widths)


@span_fn(attrs=lambda table, **k: {"rows": table.num_rows})
@func_range()
def convert_to_rows(table: Table, *, size_limit: int = MAX_BATCH_BYTES,
                    use_pallas: Optional[bool] = None,
                    impl: Optional[str] = None,
                    bucket="auto") -> List[RowsColumn]:
    """Convert a table to JCUDF row batches (reference ``convert_to_rows``,
    ``row_conversion.cu:1902-1960``).

    Variable-width dispatch: tables whose string columns are dense-padded
    (``chars2d``) encode to padded uniform-size rows — the TPU hot path
    (static shapes end to end).  Arrow-layout string columns take the
    compact wire-exact path (per-row scatter; slow on TPU, fine on CPU).

    ``bucket``: shape-bucket the row axis (``runtime/shapes.py``) so a
    stream of varying batch sizes reuses O(log N) compiled programs; the
    encode runs on the padded table (tail rows invalid → all-null rows)
    and the resulting batches are sliced back.  Arrow-layout string
    tables skip bucketing (their char buffers are content-sized, so the
    jit is content-keyed regardless)."""
    f = shapes.resolve(bucket)
    if (f is not None and shapes.bucketable(table)
            and not any(getattr(c, "capped", False) for c in table.columns)
            and all(c.is_padded for c in _string_cols(table))):
        n = table.num_rows
        b = shapes.bucket_rows(n, f)
        shapes.note(n, b)
        with shapes.pad_span():
            padded = shapes.pad_table(table, b)
        try:
            with tracing.op_scope("convert_to_rows", b):
                out = _convert_to_rows_impl(padded, size_limit,
                                            use_pallas, impl)
        except ValueError:
            # a tight size_limit can hold the exact-shape table but not
            # its bucket-padded twin (plan_fixed_batches' sub-32-row
            # fallback is byte-exact) — padding must never turn a
            # convertible table into an error, so take the exact path
            return _convert_to_rows_impl(table, size_limit, use_pallas, impl)
        with shapes.unpad_span():
            return _trim_row_batches(out, n)
    return _convert_to_rows_impl(table, size_limit, use_pallas, impl)


def _convert_to_rows_impl(table: Table, size_limit: int,
                          use_pallas: Optional[bool],
                          impl: Optional[str]) -> List[RowsColumn]:
    layout = compute_row_layout(table.dtypes)
    if layout.has_strings:
        if all(c.is_padded for c in _string_cols(table)):
            return _to_rows_variable_padded(table, layout, size_limit)
        return _to_rows_variable(table, layout, size_limit)
    platform = _platform_of(table)
    impl = _resolve_impl(impl, use_pallas, platform)
    from spark_rapids_jni_tpu.ops import pallas_kernels
    pallas_kernels.stamp_impl("xla" if impl == "xla" else "pallas",
                              engine=impl)
    n = table.num_rows
    # one batching policy: conversion transients are bounded at <=1GB per
    # encode even when the caller's size_limit would allow bigger batches.
    # (With the fused encoder the transients are VMEM-only; the chunk then
    # just caps each output batch so int32 offsets stay valid.)
    chunk = min(size_limit, 1 << 30)

    # TPU hot path: fused single-pass Pallas encoder reading the
    # plane-pair columns and packed validity masks in place at a
    # prefetched tile offset — no per-batch slice copies, no prep
    # transpose, no plane round trip through HBM.
    import os as _os
    from spark_rapids_jni_tpu.ops import row_mxu
    align = row_mxu._FUSE_TILE
    max_per = chunk // layout.fixed_row_size // align * align
    # the fused encoder packs the table ONCE into its plane-major
    # backing (a full-table-sized copy resident across every batch) and
    # runs one kernel per batch.  Cap that resident prep so tables near
    # the HBM budget keep the batch-sliced XLA path (SRJ_PALLAS_PACK=0
    # also opts out, same escape hatch as the pack kernel)
    prep_bytes = sum(c.data.nbytes for c in table.columns) \
        + ((layout.num_columns * n) // 8)
    prep_ok = prep_bytes <= int(_os.environ.get(
        "SRJ_FUSED_PREP_CAP", str(4 << 30)))
    if (impl == "mxu" and platform == "tpu" and n >= align and max_per
            and prep_ok
            and _os.environ.get("SRJ_PALLAS_PACK", "1") != "0"):
        # pack once, then delegate to the grouped batch planner (the
        # fused kernel's transients are VMEM-only, so batches run up to
        # the int32-offset cap rather than the 1GB transient bound the
        # XLA paths need)
        return convert_to_rows_grouped(row_mxu.table_to_grouped(
            table, layout), size_limit=size_limit)

    def encode(start=0, size=None):
        if impl == "pallas":
            # the word-plane pack kernel (pallas_kernels.to_rows_fixed)
            # under resilient dispatch: the generic XLA assemble is the
            # twin, and the (op, sig, bucket) breaker quarantines a
            # kernel build that keeps failing
            from spark_rapids_jni_tpu.runtime import resilience
            interp = platform != "tpu"
            sig = (layout.num_columns, layout.fixed_row_size)
            b = size if size is not None else n
            st = np.int32(start)   # a transfer, not a program
            leaves, treedef = jax.tree_util.tree_flatten(table)
            pallas_kernels.register(
                "convert_to_rows", sig, b,
                lambda *ls: pallas_kernels.to_rows_fixed(
                    jax.tree_util.tree_unflatten(treedef, ls), layout,
                    st, size, interpret=interp),
                tuple(leaves), impl="pallas")

            def _primary(t):
                pallas_kernels.stamp_impl("pallas", interp)
                return pallas_kernels.to_rows_fixed(
                    t, layout, st, size, interpret=interp)

            def _twin(t):
                pallas_kernels.stamp_impl("xla")
                return _to_rows_fixed_jit(t, layout, st, size)

            return resilience.run("convert_to_rows", _primary, table,
                                  sig=sig, bucket=b, impl="pallas",
                                  fallback=_twin)
        if impl == "mxu":
            from spark_rapids_jni_tpu.ops import row_mxu
            return row_mxu.to_rows_fixed(table, layout, start, size)
        return _to_rows_fixed_jit(table, layout, np.int32(start), size)

    def encode_program(size):
        # the jitted callable each engine's encode launches
        if impl == "pallas":
            return (pallas_kernels._to_rows_planes_jit if size is None
                    else pallas_kernels._to_rows_planes_batch_jit)
        if impl == "mxu":
            return row_mxu._to_rows_mxu_jit
        return _to_rows_fixed_jit

    if len(plan_fixed_batches(n, layout.fixed_row_size, chunk)) == 1:
        # host-built (jnp.asarray of numpy emits no XLA compile): batch
        # offsets are pure bookkeeping and must not count against the
        # operator's compiled-program budget (see runtime/shapes.py)
        offsets = jnp.asarray(
            np.arange(n + 1, dtype=np.int32) * layout.fixed_row_size)
        with _obs_spans.launch("rows.encode", encode_program(None),
                               batch=0, rows=n):
            return [RowsColumn(encode(), offsets)]
    # multi-batch: encode per batch (sliced inside the jit with a traced
    # start) so peak memory stays ~one batch of transients, the way the
    # reference converts per row-batch (row_conversion.cu:1768-1830).
    # Batches are equal-sized (32-row aligned, <=chunk) so that every full
    # batch reuses ONE compiled program and transients + held outputs +
    # the input table fit HBM together.
    nb = -(-n * layout.fixed_row_size // chunk)
    per = min((-(-n // nb) + 31) // 32 * 32,
              chunk // layout.fixed_row_size // 32 * 32)
    out = []
    for batch, start in enumerate(range(0, n, per)):
        size = min(per, n - start)
        offsets = jnp.asarray(
            np.arange(size + 1, dtype=np.int32) * layout.fixed_row_size)
        with _obs_spans.launch("rows.encode", encode_program(size),
                               batch=batch, rows=size):
            data = encode(start, size)
        out.append(RowsColumn(data, offsets))
    return out


@span_fn(attrs=lambda rows, dtypes, **k: {"rows": rows.num_rows,
                                          "bytes": int(rows.data.size)})
@func_range()
def convert_from_rows(rows: RowsColumn, dtypes: Sequence[DType],
                      *, use_pallas: Optional[bool] = None,
                      impl: Optional[str] = None, bucket="auto") -> Table:
    """Convert one batch of JCUDF rows back to a table (reference
    ``convert_from_rows``, ``row_conversion.cu:2032-2250``).

    ``bucket``: shape-bucket the row axis — the blob pads with zero rows
    (zero validity bytes decode as all-null rows) and the decoded table
    is sliced back to the true row count.  Compact wire-form string
    blobs skip bucketing (content-sized, so content-keyed anyway), as do
    blobs carrying width-cap overflow tails (the host-side tail dict
    hangs off the exact RowsColumn object; a padded twin would lose it
    and the decode refuses to silently truncate)."""
    layout = compute_row_layout(dtypes)
    f = shapes.resolve(bucket)
    if (f is not None and (rows.is_padded or not layout.has_strings)
            and getattr(rows, "_string_tails", None) is None):
        n = rows.num_rows
        rs = rows.row_size if rows.row_size is not None \
            else layout.fixed_row_size
        b = shapes.bucket_rows(n, f)
        shapes.note(n, b)
        with shapes.pad_span():
            padded = _pad_rows_blob(rows, b, rs)
        with tracing.op_scope("convert_from_rows", b):
            out = _convert_from_rows_impl(padded, dtypes, layout,
                                          use_pallas, impl)
        with shapes.unpad_span():
            # one program for every column, none where the batch fills
            # its bucket (the eager slice dispatches about 60 programs a
            # column, seconds a 155-column batch)
            return out if n == b else _slice_table(out, 0, n)
    return _convert_from_rows_impl(rows, dtypes, layout, use_pallas, impl)


def _convert_from_rows_impl(rows: RowsColumn, dtypes: Sequence[DType],
                            layout: RowLayout,
                            use_pallas: Optional[bool],
                            impl: Optional[str]) -> Table:
    if layout.has_strings:
        if rows.is_padded:
            return _from_rows_variable_padded(rows, layout)
        return _from_rows_variable(rows, layout)
    n = rows.num_rows
    platform = _platform_of(rows)
    impl = _resolve_impl(impl, use_pallas, platform)
    from spark_rapids_jni_tpu.ops import pallas_kernels
    # impl attribution: the explicitly tiled engines (the planes kernel
    # and the fused MXU decode are both Pallas programs) vs the generic
    # XLA lowering — obs profile and chargeback split the ledger on this
    pallas_kernels.stamp_impl("xla" if impl == "xla" else "pallas",
                              engine=impl)
    sig = (layout.num_columns, layout.fixed_row_size)
    if impl == "pallas":
        from spark_rapids_jni_tpu.runtime import resilience
        rows2d = rows.rows2d(layout.fixed_row_size)
        interp = platform != "tpu"
        pallas_kernels.register(
            "convert_from_rows", sig, n,
            lambda r2d: pallas_kernels.from_rows_fixed(
                r2d, layout, interpret=interp),
            (rows2d,), impl="pallas")

        # resilient dispatch with the generic XLA decode as the twin:
        # a deterministic Pallas failure (the BENCH_r05 lowering
        # rejection class) falls through in the same call, and the
        # (op, sig, bucket) breaker quarantines a kernel whose failure
        # rate crosses the threshold
        def _primary(r2d):
            pallas_kernels.stamp_impl("pallas", interp)
            return pallas_kernels.from_rows_fixed(r2d, layout,
                                                  interpret=interp)

        def _twin(r2d):
            pallas_kernels.stamp_impl("xla")
            return _from_rows_fixed_jit(r2d, layout)

        with _obs_spans.launch("rows.decode",
                               pallas_kernels._from_rows_planes_jit,
                               batch=0, rows=n):
            cols = resilience.run("convert_from_rows", _primary, rows2d,
                                  sig=sig, bucket=n, impl="pallas",
                                  fallback=_twin)
    elif impl == "mxu":
        from spark_rapids_jni_tpu.ops import row_mxu
        if rows.data.size != n * layout.fixed_row_size:
            raise ValueError(
                f"row blob holds {rows.data.size} bytes but offsets "
                f"describe {n} rows of {layout.fixed_row_size}")
        # 2-D blobs go straight in; flat wire blobs reshape inside the jit
        with _obs_spans.launch("rows.decode", row_mxu._from_rows_mxu_jit,
                               batch=0, rows=n):
            cols = row_mxu.from_rows_fixed(rows.data, layout)
    else:
        rows2d = rows.rows2d(layout.fixed_row_size)
        pallas_kernels.register(
            "convert_from_rows", sig, n,
            lambda r2d: _from_rows_fixed_jit(r2d, layout),
            (rows2d,), impl="xla")
        with _obs_spans.launch("rows.decode", _from_rows_fixed_jit,
                               batch=0, rows=n):
            cols = _from_rows_fixed_jit(rows2d, layout)
    return Table(tuple(cols))


@span_fn(attrs=lambda gc, **k: {"rows": gc.num_rows})
@func_range()
def convert_to_rows_grouped(gc, *, size_limit: int = MAX_BATCH_BYTES
                            ) -> List[RowsColumn]:
    """Convert a plane-major :class:`GroupedColumns` backing straight to
    JCUDF row batches — the encode twin of
    :func:`convert_from_rows_grouped`: one fused kernel per batch, HBM
    traffic exactly planes in + blob out (no per-column extraction).

    Build the backing with ``row_mxu.table_to_grouped(table)`` or get it
    from a grouped decode; a decode→compute→encode pipeline never leaves
    the plane-major form."""
    from spark_rapids_jni_tpu.ops import row_mxu
    layout = gc.layout
    n = gc.num_rows
    rs = layout.fixed_row_size
    align = row_mxu._FUSE_TILE
    chunk = min(size_limit, MAX_BATCH_BYTES)
    per_max = chunk // rs // align * align
    if n == 0 or n < align or per_max == 0:
        # tiny tables: materialize and take the standard path.  The
        # inner convert_to_rows buckets and notes padding on its OWN
        # span — stamp the bucket attrs on this op's span too (with the
        # blob bytes so the tail cost is priced), otherwise pad_waste
        # attribution under-counts every small grouped batch.
        f = shapes.resolve("auto")
        if f is not None and n > 0:
            sp = _obs_spans.current_span()
            if sp is not None and "bytes" not in sp.attrs:
                sp.set(bytes=n * rs)
            shapes.note(n, shapes.bucket_rows(n, f))
        return convert_to_rows(gc.to_table(), size_limit=size_limit)
    nb = -(-n * rs // chunk)
    per = min((-(-n // nb) + align - 1) // align * align, per_max)
    out = []
    platform = _platform_of(gc.planes)
    for batch, start in enumerate(range(0, n, per)):
        size = min(per, n - start)
        # the offsets are the same two programs an eager
        # ``jnp.arange(size + 1) * rs`` runs, one launch span each; the
        # rebinding drops the iota before the encode allocates, as the
        # eager expression dropped its temporary
        with _obs_spans.launch("rows.offsets", _row_iota, batch=batch):
            offsets = _row_iota(size + 1)
        with _obs_spans.launch("rows.offsets", _row_scale, batch=batch):
            offsets = _row_scale(offsets, rs)
        with _obs_spans.launch("rows.encode", row_mxu._grouped_encode_impl,
                               batch=batch, rows=size):
            data = row_mxu.to_rows_fixed_grouped(
                gc, start, size, interpret=platform != "tpu")
        out.append(RowsColumn(data, offsets))
    return out


@functools.partial(jax.jit, static_argnums=0)
def _row_iota(n: int) -> jnp.ndarray:
    return jnp.arange(n, dtype=jnp.int32)


@jax.jit
def _row_scale(index, row_size) -> jnp.ndarray:
    """``index * row_size``: a batch's row offsets."""
    return index * row_size


@span_fn(attrs=lambda rows, dtypes: {"rows": rows.num_rows,
                                     "bytes": int(rows.data.size)})
@func_range()
def convert_from_rows_grouped(rows: RowsColumn, dtypes: Sequence[DType]):
    """Decode one batch of fixed-width JCUDF rows to the dtype-major
    :class:`~spark_rapids_jni_tpu.ops.row_mxu.GroupedColumns` backing —
    the preferred consumer path on TPU: one fused kernel decodes the
    blob into a single ``[W, n]`` word-plane matrix (plus the packed
    validity masks), ~2x faster than per-column materialization at 212
    columns, and consumers extract only the columns they touch via
    ``.column(i)`` (``.to_table()`` gives the full Table).
    """
    layout = compute_row_layout(dtypes)
    if layout.has_strings:
        raise ValueError("grouped decode covers fixed-width tables; "
                         "string tables use convert_from_rows")
    from spark_rapids_jni_tpu.ops import row_mxu
    return row_mxu.from_rows_fixed_grouped(rows.data, layout)


def _platform_of(tree) -> str:
    """Platform the data actually lives on (the analogue of the reference's
    per-call ``auto_set_device``, ``RowConversionJni.cpp:30``)."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                return next(iter(leaf.devices())).platform
            except Exception:
                continue
    return jax.default_backend()


# ---------------------------------------------------------------------------
# Variable-width (string) path
# ---------------------------------------------------------------------------

def _string_cols(table: Table) -> List[Column]:
    return [c for c in table.columns if c.dtype.is_string]


# -- dense-padded engine (the TPU hot path) ---------------------------------
#
# Measured on v5e: per-row dynamic-start gathers/scatters run ~1.3s per
# 32MB moved, while static concatenates/slices run at ~126 GB/s — a ~100x
# gap.  The padded engine therefore gives every row the SAME size (fixed
# section + one fixed-width slot per string column) so encode is a pure
# concatenate and decode is pure static slicing; the (offset, length)
# pairs keep the blob self-describing JCUDF.  Compaction to the exact
# wire layout happens only at the host/native boundary
# (:func:`compact_rows_host`), mirroring where the reference pays its own
# data-dependent sync (``build_batches``, ``row_conversion.cu:1521``).

def padded_variable_layout(layout: RowLayout, widths: Sequence[int]):
    """Slot byte-offsets for padded rows: fixed section (word-padded), then
    one ``widths[si]``-byte slot per string column, row rounded to 8."""
    fe_pad = (layout.fixed_end + 3) // 4 * 4
    starts = []
    pos = fe_pad
    for w in widths:
        if w % 4:
            raise ValueError(f"padded char width {w} not a multiple of 4")
        starts.append(pos)
        pos += w
    row_size = (pos + 7) // 8 * 8
    return tuple(starts), fe_pad, row_size


def padded_rows2d(table: Table, layout: RowLayout,
                  slot_starts: Tuple[int, ...], fe_pad: int,
                  row_size: int) -> jnp.ndarray:
    """[n, row_size] dense-padded JCUDF rows — one static concatenate.
    Traceable with no host syncs, so it runs under jit AND shard_map (the
    string shuffle encodes rows per device with this)."""
    n = table.num_rows
    scols = _string_cols(table)
    lens = [c.str_lens() for c in scols]
    pairs = [jnp.stack([jnp.full((n,), s, jnp.uint32),
                        l.astype(jnp.uint32)], axis=1)
             for s, l in zip(slot_starts, lens)]
    pieces = [_assemble_fixed_variable(table, pairs, layout)]
    if fe_pad > layout.fixed_end:
        pieces.append(jnp.zeros((n, fe_pad - layout.fixed_end), jnp.uint8))
    pos = fe_pad
    for c, l in zip(scols, lens):
        w = c.chars2d
        # zero slack bytes so the blob is deterministic regardless of what
        # the padded char matrix carries past each length
        mask = jnp.arange(w.shape[1], dtype=jnp.int32)[None, :] < l[:, None]
        pieces.append(jnp.where(mask, w, jnp.uint8(0)))
        pos += w.shape[1]
    if row_size > pos:
        pieces.append(jnp.zeros((n, row_size - pos), jnp.uint8))
    return jnp.concatenate(pieces, axis=1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 6))
def _to_rows_padded_jit(table: Table, layout: RowLayout,
                        slot_starts: Tuple[int, ...], fe_pad: int,
                        row_size: int, start=0, size=None) -> jnp.ndarray:
    from spark_rapids_jni_tpu.table import slice_table_dynamic
    if size is not None and size != table.num_rows:
        table = slice_table_dynamic(table, start, size)
    # 2-D [n, row_size]: blobs stay unflattened on device
    return padded_rows2d(table, layout, slot_starts, fe_pad, row_size)


def _batch_string_tails(scols: List[Column], start: int,
                        end: int) -> Optional[dict]:
    """Per-string-column overflow tails for batch rows [start, end),
    rebased to batch-local row indices: {si: StringTail} (vectorized
    range slice — no per-entry work)."""
    from spark_rapids_jni_tpu.table import string_tail
    tails = {}
    for si, c in enumerate(scols):
        t = string_tail(c)
        if t is None or not len(t):
            continue
        sub = t.slice_range(start, end)
        if sub is not None:
            tails[si] = sub
    return tails or None


def _attach_rows_tails(rows: RowsColumn, tails: Optional[dict]):
    if tails:
        object.__setattr__(rows, "_string_tails", tails)
    return rows


def _to_rows_variable_padded(table: Table, layout: RowLayout,
                             size_limit: int) -> List[RowsColumn]:
    scols = _string_cols(table)
    widths = tuple(c.chars2d.shape[1] for c in scols)
    slot_starts, fe_pad, row_size = padded_variable_layout(layout, widths)
    n = table.num_rows

    def encode(start=0, size=None):
        return _to_rows_padded_jit(table, layout, slot_starts, fe_pad,
                                   row_size, np.int32(start), size)

    # padded rows are uniform, so the only hard batch bound is the JCUDF
    # int32-offset contract (<=2GB per blob).  Batch slicing costs a full
    # relayout copy per column that XLA never fuses into the assembling
    # concat (measured at 1M x 155+25str: two sliced 500k batches take
    # 23-25 ms — static OR traced starts — where the unsliced 1M encode
    # takes 11 ms), so take the whole table in one program whenever the
    # blob fits the contract; SRJ_VAR_CHUNK caps it for HBM-tight runs
    import os as _os
    env = _os.environ.get("SRJ_VAR_CHUNK")
    cap = MAX_BATCH_BYTES
    if env is not None:
        try:
            cap = int(env)
        except ValueError:
            cap = 0
        if cap <= 0:
            raise ValueError(
                f"SRJ_VAR_CHUNK must be a positive integer, "
                f"got {env!r}") from None
    # MAX_BATCH_BYTES stays the unconditional bound: int32 offsets
    chunk = min(size_limit, cap, MAX_BATCH_BYTES)
    if len(plan_fixed_batches(n, row_size, chunk)) == 1:
        per = n
    else:
        # equal-sized 32-row-aligned batches sharing one compiled program
        # (same policy as the fixed-width path)
        nb = -(-n * row_size // chunk)
        per = min((-(-n // nb) + 31) // 32 * 32,
                  chunk // row_size // 32 * 32)
    out = []
    for batch, start in enumerate(range(0, n, per) if n else [0]):
        size = min(per, n - start)
        with _obs_spans.launch("rows.offsets", _row_iota, batch=batch):
            offsets = _row_iota(size + 1)
        with _obs_spans.launch("rows.offsets", _row_scale, batch=batch):
            offsets = _row_scale(offsets, row_size)
        with _obs_spans.launch("rows.encode", _to_rows_padded_jit,
                               batch=batch, rows=size, strings=len(scols),
                               str_width=max(widths, default=0)):
            data = encode() if size == n else encode(start, size)
        out.append(_attach_rows_tails(
            RowsColumn(data, offsets, row_size, widths),
            _batch_string_tails(scols, start, start + size)))
    _count_string_bytes("encode", n * sum(widths))
    return out


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _from_rows_padded_jit(data: jnp.ndarray, layout: RowLayout,
                          str_widths: Tuple[int, ...],
                          mode: str = "xla") -> Table:
    """The whole decode of a padded batch, the Table included, in one
    program (columns built outside would cost a dispatch each)."""
    row_size = padded_variable_layout(layout, str_widths)[2]
    n = data.shape[0] if data.ndim == 2 \
        else data.shape[0] // row_size
    datas, masks, str_parts = padded_cols_from_rows(
        data, layout, str_widths, n, mode)
    empty = jnp.zeros((0,), jnp.uint8)
    cols, parts = [], iter(str_parts)
    for i, dt in enumerate(layout.dtypes):
        if dt.is_string:
            chars2d, offsets = next(parts)
            cols.append(Column(dt, empty, masks[i], offsets, None, chars2d))
        else:
            cols.append(Column(dt, datas[i], masks[i]))
    return Table(tuple(cols))


def padded_cols_from_rows(data: jnp.ndarray, layout: RowLayout,
                          str_widths: Tuple[int, ...], n: int,
                          mode: str = "xla"):
    """Decode a flat padded blob of ``n`` rows into (datas, masks,
    [(chars2d, offsets)]) (traceable; used by the public decode and by
    per-device shuffle decode).

    ``mode`` picks the fixed-section engine: ``"pallas"`` (TPU hot
    path) runs the fused planes kernel — string slots decode as
    (offset, length) u32 plane PAIRS and every column extraction is a
    contiguous plane-row slice; ``"xla"`` keeps the static-slice +
    strided-lane-combine path (CPU / tiny batches)."""
    slot_starts, fe_pad, row_size = padded_variable_layout(
        layout, str_widths)
    rows2d = data if data.ndim == 2 else data.reshape(n, row_size)
    if mode != "xla":
        from spark_rapids_jni_tpu.ops import row_mxu
        x, vmask = row_mxu.var_fixed_planes(
            rows2d, layout, fe_pad, interpret=mode == "pallas_interpret")
        datas, masks, str_lens = _cols_from_planes(x, vmask, layout)
    else:
        f_words = bytes2d_to_words(rows2d[:, :fe_pad])    # [n, fe_pad/4]
        datas, masks, str_lens = _cols_from_fwords(f_words, layout)
    str_parts = []
    for si, (s, w) in enumerate(zip(slot_starts, str_widths)):
        l = str_lens[si]
        if w == 0:
            chars2d = jnp.zeros((n, 0), jnp.uint8)
        else:
            chars2d = rows2d[:, s:s + w]
            # zero slack: foreign blobs may carry garbage past each length
            m = jnp.arange(w, dtype=jnp.int32)[None, :] < l[:, None]
            chars2d = jnp.where(m, chars2d, jnp.uint8(0))
        offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                   jnp.cumsum(l).astype(jnp.int32)])
        str_parts.append((chars2d, offsets))
    return datas, masks, str_parts


def _from_rows_variable_padded(rows: RowsColumn, layout: RowLayout) -> Table:
    from spark_rapids_jni_tpu.table import attach_string_tail
    from spark_rapids_jni_tpu.ops import row_mxu
    n = rows.num_rows
    mode = "pallas" if (_platform_of(rows) == "tpu"
                        and n >= row_mxu._FUSE_TILE) \
        else "xla"
    with _obs_spans.launch("rows.decode", _from_rows_padded_jit,
                           batch=0, rows=n):
        out = _from_rows_padded_jit(rows.data, layout, rows.str_widths,
                                    mode)
    _count_string_bytes("decode", n * sum(rows.str_widths))
    tails = getattr(rows, "_string_tails", None) or {}
    scols = [c for c in out.columns if c.dtype.is_string]
    for si, t in tails.items():
        attach_string_tail(scols[si], t)
    return out


def compact_rows_host(rows: RowsColumn, dtypes: Sequence[DType]) -> RowsColumn:
    """Dense-padded batch -> exact compact JCUDF wire bytes, on the host.

    The compact layout (chars back-to-back after validity, rows 8-byte
    aligned, pairs pointing at the packed positions) is produced with
    vectorized numpy — this is the host/native boundary where the ragged
    representation is allowed to exist (device code never compacts)."""
    layout = compute_row_layout(dtypes)
    if not rows.is_padded:
        return rows
    n = rows.num_rows
    rs = rows.row_size
    blob = np.asarray(rows.data).reshape(n, rs)
    slot_starts, fe_pad, _ = padded_variable_layout(layout, rows.str_widths)
    fe = layout.fixed_end
    nvar = len(slot_starts)
    lens = np.zeros((n, nvar), np.int64)
    for si, s in enumerate(layout.variable_starts):
        lens[:, si] = blob[:, s + 4:s + 8].copy().view(np.uint32)[:, 0]
    within = np.cumsum(lens, axis=1) - lens          # exclusive, per row
    row_sizes = (fe + lens.sum(axis=1) + 7) // 8 * 8
    out_offs = np.zeros(n + 1, np.int64)
    np.cumsum(row_sizes, out=out_offs[1:])
    out = np.zeros(int(out_offs[-1]), np.uint8)
    # fixed sections: one strided copy
    idx = out_offs[:-1, None] + np.arange(fe)[None, :]
    out[idx.reshape(-1)] = blob[:, :fe].reshape(-1)
    # rewrite pairs to compact offsets
    pair_vals = (fe + within).astype(np.uint32)
    for si, s in enumerate(layout.variable_starts):
        pb = pair_vals[:, si:si + 1].copy().view(np.uint8)   # [n, 4] LE
        out[(out_offs[:-1, None] + s + np.arange(4)[None, :]).reshape(-1)] \
            = pb.reshape(-1)
    # chars: ragged scatter via repeat (C-speed on host).  Width-capped
    # batches hold only each row's first ``w`` bytes in the slot; the
    # overflow tails supply the rest (true lengths came from the pairs)
    from spark_rapids_jni_tpu.table import ragged_positions
    tails = getattr(rows, "_string_tails", None) or {}
    for si, (s, w) in enumerate(zip(slot_starts, rows.str_widths)):
        l = lens[:, si]
        if int(l.sum()) == 0:
            continue
        capped = np.minimum(l, w)
        if int(l.max(initial=0)) > w and si not in tails:
            raise ValueError(
                f"string column {si} has rows longer than its padded "
                f"width {w} but no overflow tail attached; refusing to "
                "emit truncated wire bytes")
        rows_r, intra = ragged_positions(capped)
        src = rows_r * rs + s + intra
        dst = out_offs[rows_r] + fe + within[rows_r, si] + intra
        out[dst] = blob.reshape(-1)[src]
        t = tails.get(si)
        if t is not None and len(t):
            trep, tintra = ragged_positions(t.lens())
            tr = t.rows[trep]
            out[out_offs[tr] + fe + within[tr, si] + tintra] = t.data
    return RowsColumn(jnp.asarray(out),
                      jnp.asarray(out_offs.astype(np.int32)))


@functools.partial(jax.jit, static_argnums=(1,))
def _row_sizes_jit(table: Table, layout: RowLayout) -> jnp.ndarray:
    """Pass 1: per-row total size (reference ``build_string_row_offsets``,
    ``row_conversion.cu:216-261``)."""
    n = table.num_rows
    total = jnp.zeros((n,), dtype=jnp.int32)
    for c in _string_cols(table):
        total = total + (c.offsets[1:] - c.offsets[:-1])
    fixed = layout.fixed_end
    return (fixed + total + (JCUDF_ROW_ALIGNMENT - 1)) \
        // JCUDF_ROW_ALIGNMENT * JCUDF_ROW_ALIGNMENT


def _to_rows_variable(table: Table, layout: RowLayout,
                      size_limit: int) -> List[RowsColumn]:
    if any(c.is_padded for c in _string_cols(table)):
        # mixed padded/Arrow tables: normalize to Arrow for the compact
        # path (host boundary conversion; all-padded tables never get here)
        table = Table(tuple(c.to_arrow() if c.dtype.is_string else c
                            for c in table.columns))
    scol = _string_cols(table)
    # host sync for batch planning (as ref): row sizes + every string
    # column's offsets come back in ONE staged D2H instead of 1 + nscol
    # separate fetches
    fetched = staging.fetch_arrays(
        [_row_sizes_jit(table, layout)] + [c.offsets for c in scol])
    row_sizes, scol_offsets_np = fetched[0], fetched[1:]
    batches = plan_variable_batches(row_sizes, size_limit)
    out = []
    for start, end in batches:
        sizes = row_sizes[start:end]
        offsets = np.zeros(end - start + 1, dtype=np.int32)
        np.cumsum(sizes, out=offsets[1:])
        total_bytes = int(offsets[-1])
        los = tuple(int(offs[start]) for offs in scol_offsets_np)
        char_totals = tuple(int(offs[end]) - lo
                            for offs, lo in zip(scol_offsets_np, los))
        char_slices = _slice_chars_batch_jit(
            [c.chars for c in scol], los, char_totals) if scol else []
        sub = _slice_table(table, start, end)
        data = _to_rows_variable_jit(
            sub, jnp.asarray(offsets), tuple(char_totals), char_slices,
            layout, total_bytes)
        out.append(RowsColumn(data, jnp.asarray(offsets)))
    return out


_slice_table = functools.partial(jax.jit, static_argnums=(1, 2))(slice_table)


@functools.partial(jax.jit, static_argnums=(2, 4, 5))
def _to_rows_variable_jit(table: Table, row_offsets: jnp.ndarray,
                          char_totals: Tuple[int, ...],
                          char_slices: List[jnp.ndarray],
                          layout: RowLayout, total_bytes: int) -> jnp.ndarray:
    """Assemble one batch of variable-width rows.

    The blob is built in uint32 *word* space: the fixed sections scatter as
    whole words (row offsets and ``fixed_end`` are 4-byte aligned), so the
    index matrix is 4x smaller than a byte-granular scatter — the
    difference between fitting in HBM and OOM on wide 1M-row tables.  Char
    bytes scatter-ADD into their word at a byte-lane shift; all writers of
    a word touch disjoint lanes, so the adds reassemble exact bytes.
    """
    n = table.num_rows
    scols = _string_cols(table)
    nvar = len(scols)

    # per-row string lengths and within-row char start offsets
    lens = jnp.stack([(c.offsets[1:] - c.offsets[:-1]) for c in scols],
                     axis=1).astype(jnp.int32)            # [n, nvar]
    within = jnp.cumsum(lens, axis=1) - lens              # exclusive cumsum
    str_row_off = layout.fixed_end + within               # [n, nvar]

    # fixed section with (offset, length) pairs patched in
    pairs = []
    for si in range(nvar):
        pairs.append(jnp.stack([str_row_off[:, si].astype(jnp.uint32),
                                lens[:, si].astype(jnp.uint32)], axis=1))
    F = _assemble_fixed_variable(table, pairs, layout)    # [n, fixed_end]
    fe_pad = (layout.fixed_end + 3) // 4 * 4
    if fe_pad != layout.fixed_end:  # pad to whole words (fe is 1-byte gran.)
        F = jnp.concatenate(
            [F, jnp.zeros((n, fe_pad - layout.fixed_end), jnp.uint8)], axis=1)
    f_words = bytes2d_to_words(F)                          # [n, fe/4]

    nwords = total_bytes // 4                              # rows 8B-aligned
    out = jnp.zeros((nwords,), dtype=jnp.uint32)
    if nwords >= fe_pad // 4:  # else: empty batch, nothing to place
        # one contiguous fe_pad/4-word window per row: a slice-scatter
        # runs ~4x faster than the equivalent element scatter on TPU
        out = jax.lax.scatter(
            out, (row_offsets[:-1, None] // 4).astype(jnp.int32), f_words,
            jax.lax.ScatterDimensionNumbers(
                update_window_dims=(1,), inserted_window_dims=(),
                scatter_dims_to_operand_dims=(0,)),
            mode=jax.lax.GatherScatterMode.CLIP)
    # chars: word index + byte-lane shift, scatter-add per string column.
    # (fixed_end may not be 4-aligned, but rows are: dst_pos is exact.)
    for si, (c, total) in enumerate(zip(scols, char_totals)):
        if total == 0:
            continue
        l = lens[:, si]
        cum = jnp.cumsum(l) - l
        row_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), l,
                             total_repeat_length=total)
        intra = jnp.arange(total, dtype=jnp.int32) - cum[row_ids]
        dst_pos = row_offsets[row_ids] + str_row_off[row_ids, si] + intra
        vals = char_slices[si].astype(jnp.uint32) \
            << (8 * (dst_pos % 4)).astype(jnp.uint32)
        out = out.at[dst_pos // 4].add(vals)
    from spark_rapids_jni_tpu.ops import row_mxu
    return row_mxu.words_to_bytes(out, total_bytes)


def _assemble_fixed_variable(table: Table, pairs: List[jnp.ndarray],
                             layout: RowLayout) -> jnp.ndarray:
    """Like ``_assemble_fixed_rows`` but only up to ``fixed_end`` (no tail
    padding — variable rows place chars there), with each string column's
    slot filled from its uint32 (offset, length) pair data in ``pairs``."""
    n = table.num_rows
    pieces = []
    pos = 0
    si = 0
    for i, col in enumerate(table.columns):
        start, size = layout.col_starts[i], layout.col_sizes[i]
        if start > pos:
            pieces.append(jnp.zeros((n, start - pos), jnp.uint8))
        if col.dtype.is_string:
            pieces.append(jax.lax.bitcast_convert_type(
                pairs[si], jnp.uint8).reshape(n, 8))
            si += 1
        else:
            pieces.append(col_to_bytes(col.data, col.dtype))
        pos = start + size
    if layout.validity_offset > pos:
        pieces.append(jnp.zeros((n, layout.validity_offset - pos), jnp.uint8))
    pieces.append(_validity_row_bytes(table, layout))
    return jnp.concatenate(pieces, axis=1)


def _from_rows_variable(rows: RowsColumn, layout: RowLayout) -> Table:
    # everything except the (data-dependent-size) char gathers happens in
    # ONE compiled program: per-column eager dispatch would cost hundreds
    # of host round trips
    n = rows.num_rows
    with _obs_spans.launch("rows.decode", _extract_fixed_variable_jit,
                           batch=0, rows=n):
        datas, masks, f_words, str_lens = _extract_fixed_variable_jit(
            rows.data, rows.offsets, layout)
    # ONE host sync for all string columns' char totals (the reference
    # syncs once per column at row_conversion.cu:2215; batching the sync
    # and the gather compile makes the data-dependent-shape cost O(1) in
    # the number of string columns)
    with _obs_spans.launch("rows.decode", _str_totals_jit, batch=0,
                           rows=n):
        totals = tuple(int(x) for x in np.asarray(
            _str_totals_jit(str_lens)))
    with _obs_spans.launch("rows.decode", _gather_all_strings_jit,
                           batch=0, rows=n):
        str_parts = _gather_all_strings_jit(
            rows.data, rows.offsets, f_words,
            tuple(layout.variable_starts), str_lens, totals)
    _count_string_bytes("decode", sum(totals))
    empty = jnp.asarray(np.zeros((0,), np.uint8))   # a transfer, no program
    cols = []
    si = 0
    for i, dt in enumerate(layout.dtypes):
        if dt.is_string:
            chars, offsets = str_parts[si]
            si += 1
            cols.append(Column(dt, empty, masks[i], offsets, chars))
        else:
            cols.append(Column(dt, datas[i], masks[i]))
    return Table(tuple(cols))


@jax.jit
def _str_totals_jit(str_lens):
    return jnp.stack([jnp.sum(l) for l in str_lens])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _slice_chars_batch_jit(chars_list, los, sizes):
    """Slice every string column's char range for one batch in a single
    compiled program (per-column eager slicing costs a host round trip
    each)."""
    return [jax.lax.dynamic_slice(c, (lo,), (sz,)) if sz
            else jnp.zeros((0,), jnp.uint8)
            for c, lo, sz in zip(chars_list, los, sizes)]


@functools.partial(jax.jit, static_argnums=(3, 5))
def _gather_all_strings_jit(data, row_offsets, f_words, var_starts,
                            str_lens, totals):
    """Gather every string column's chars in one compiled program."""
    if data.ndim == 2:  # device-native 2-D blob: wire-flatten in-jit
        data = data.reshape(-1)
    out = []
    for si, s in enumerate(var_starts):
        str_off = f_words[:, s // 4].astype(jnp.int32)
        out.append(_gather_one_string(data, row_offsets, str_off,
                                      str_lens[si], totals[si]))
    return out


def _col_from_words(f_words: jnp.ndarray, s: int, dt: DType):
    """Extract one fixed-width column from per-row uint32 words (byte
    offset ``s`` in the row; fields are size-aligned by the layout)."""
    sz = dt.itemsize
    w0 = s // 4
    if sz == 16:  # decimal128: 4 words per row -> [n, 4] limbs
        return f_words[:, w0:w0 + 4]
    if sz == 8:
        pair = f_words[:, w0:w0 + 2]
        if jax.config.jax_enable_x64:
            return jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(pair, jnp.uint64), dt.np_dtype)
        return pair.T  # [2, n] plane-pair Column layout
    if sz == 4:
        return jax.lax.bitcast_convert_type(f_words[:, w0], dt.np_dtype)
    word = f_words[:, w0] >> (8 * (s % 4))
    if sz == 2:
        return jax.lax.bitcast_convert_type(
            (word & 0xFFFF).astype(jnp.uint16), dt.np_dtype)
    data = (word & 0xFF).astype(jnp.uint8)
    if dt.np_dtype != np.uint8:
        data = jax.lax.bitcast_convert_type(data, dt.np_dtype)
    return data


@functools.partial(jax.jit, static_argnums=(2,))
def _extract_fixed_variable_jit(data: jnp.ndarray, offsets: jnp.ndarray,
                                layout: RowLayout):
    """Gather per-row fixed sections as uint32 words ([n, fe_pad/4]; a
    4x smaller index matrix than byte gathers, and no u8[*, 4] tiled
    intermediates), then extract every column's data and packed validity
    mask in the same program."""
    if data.ndim == 2:  # device-native 2-D blob: wire-flatten in-jit
        data = data.reshape(-1)
    n = offsets.shape[0] - 1
    fe_pad = (layout.fixed_end + 3) // 4 * 4
    nwords = data.shape[0] // 4
    from spark_rapids_jni_tpu.ops import row_mxu
    # whole-blob word conversion runs on the MXU at matmul speed, so
    # converting the (unused) char bytes too is cheap; the alternative —
    # four byte-plane gathers of just the fixed sections — quadruples the
    # gather element count, and gathers are the slow primitive here
    words = row_mxu.bytes_to_words(data, nwords)
    if nwords < fe_pad // 4:  # empty/degenerate batch
        f_words = jnp.zeros((n, fe_pad // 4), jnp.uint32)
    else:
        # one contiguous window per row (slice gather ~4x faster than the
        # element gather with an [n, fe/4] index matrix)
        f_words = jax.lax.gather(
            words, (offsets[:-1, None] // 4).astype(jnp.int32),
            jax.lax.GatherDimensionNumbers(
                offset_dims=(1,), collapsed_slice_dims=(),
                start_index_map=(0,)),
            slice_sizes=(fe_pad // 4,),
            mode=jax.lax.GatherScatterMode.CLIP)
    datas, masks, str_lens = _cols_from_fwords(f_words, layout)
    return datas, masks, f_words, str_lens


def _validity_from_fwords(f_words: jnp.ndarray,
                          layout: RowLayout) -> jnp.ndarray:
    """Per-column packed validity masks [ncols, ceil(n/8)] from per-row
    fixed-section words (see ``packed_masks_from_byte_planes`` for why
    this avoids per-column stacks)."""
    from spark_rapids_jni_tpu.table import (
        byte_planes_from_word_planes, packed_masks_from_byte_planes)
    vo, vb = layout.validity_offset, layout.validity_bytes
    w0, w1 = vo // 4, (vo + vb + 3) // 4
    vbT = byte_planes_from_word_planes(f_words[:, w0:w1].T, vb, vo % 4)
    return packed_masks_from_byte_planes(vbT, layout.num_columns)


def _cols_from_planes(x: jnp.ndarray, vmask: jnp.ndarray,
                      layout: RowLayout):
    """Extract every column's data, packed validity mask, and string
    lengths from decoded word planes [W, n] (the variable-width twin of
    ``row_mxu._from_rows_mxu_jit``'s extraction; string slots are
    (offset, length) plane pairs)."""
    from spark_rapids_jni_tpu.ops import row_mxu
    plan = row_mxu._inverse_plan(layout)[0]
    masks = [vmask[i] for i in range(layout.num_columns)]
    datas = []
    str_lens = []
    for i, dt in enumerate(layout.dtypes):
        if dt.is_string:
            datas.append(None)
            str_lens.append(jax.lax.bitcast_convert_type(
                x[plan.col_word[i] + 1], jnp.int32))  # hi plane = length
            continue
        datas.append(row_mxu.extract_plane_column(x, plan, layout, i))
    return datas, masks, str_lens


def _cols_from_fwords(f_words: jnp.ndarray, layout: RowLayout):
    """Extract every column's data, packed validity mask, and string
    lengths from per-row fixed-section words [n, fe_pad/4] (shared by the
    compact-gather and padded-slice decode paths)."""
    vmask = _validity_from_fwords(f_words, layout)          # [ncols, nb]
    masks = [vmask[i] for i in range(layout.num_columns)]
    datas = [None if dt.is_string
             else _col_from_words(f_words, layout.col_starts[i], dt)
             for i, dt in enumerate(layout.dtypes)]
    str_lens = [(f_words[:, s // 4 + 1].astype(jnp.int32))
                for s in layout.variable_starts]
    return datas, masks, str_lens


def _gather_one_string(data: jnp.ndarray, row_offsets: jnp.ndarray,
                       str_off: jnp.ndarray, str_len: jnp.ndarray,
                       total: int):
    n = str_len.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(str_len).astype(jnp.int32)])
    if total == 0:
        return jnp.zeros((0,), jnp.uint8), offsets
    cum = offsets[:-1]
    row_ids = jnp.repeat(jnp.arange(n, dtype=jnp.int32), str_len,
                         total_repeat_length=total)
    intra = jnp.arange(total, dtype=jnp.int32) - cum[row_ids]
    src = row_offsets[row_ids] + str_off[row_ids] + intra
    return data[src], offsets
