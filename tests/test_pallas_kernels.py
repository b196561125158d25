"""Pallas VMEM-tiled kernels (``ops/pallas_kernels.py``): byte-identity
against the generic XLA lowerings under interpret mode on the CPU mesh,
the ``SRJ_TPU_PALLAS`` knob contract plus per-op eligibility hooks, and
the TPU-legality guards — no per-row dynamic-start gather in the lowered
HLO of the row codecs or hash mats builders (the root cause of
BENCH_r05's real-backend failures), and a select-only automaton step for
the get_json scan kernel."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spark_rapids_jni_tpu.table import (
    Table, Column, BOOL8, FLOAT32, FLOAT64, INT8, INT16, INT32, INT64,
    UINT8, UINT16, UINT64,
)
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.utils import FIXED_WIDTH_DTYPES, cycle_dtypes
from spark_rapids_jni_tpu.ops import hashing as H
from spark_rapids_jni_tpu.ops import pallas_kernels as pk
from spark_rapids_jni_tpu.ops import spark_bloom as SB
from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
from spark_rapids_jni_tpu.runtime import shapes

FIXED_DTYPES = [INT32, INT64, INT8, INT16, FLOAT64, BOOL8, FLOAT32]

# bucket edges: k±1 around pow-2 grid points, single row, empty
EDGE_ROWS = [0, 1, 7, 8, 9, 31, 32, 33, 255, 256, 257]


def _make_cols(rng, dtypes, n, pattern="most"):
    cols = []
    for dt in dtypes:
        np_dt = dt.np_dtype
        if np_dt.kind == "f":
            vals = rng.standard_normal(n).astype(np_dt)
        elif dt.kind == "bool8":
            vals = rng.integers(0, 2, n).astype(np_dt)
        else:
            info = np.iinfo(np_dt)
            vals = rng.integers(info.min, info.max, n, dtype=np_dt,
                                endpoint=True)
        if pattern == "none":
            valid = np.zeros(n, dtype=bool)
        elif pattern == "plain":
            valid = None
        else:
            valid = rng.random(n) > 0.1
        cols.append(Column.from_numpy(vals, dt, valid))
    return tuple(cols)


def _assert_cols_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.data),
                                      np.asarray(w.data))
        assert (g.validity is None) == (w.validity is None)
        if g.validity is not None:
            np.testing.assert_array_equal(np.asarray(g.validity),
                                          np.asarray(w.validity))


# ---------------------------------------------------------------------------
# row-unpack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", EDGE_ROWS)
@pytest.mark.parametrize("pattern", ["most", "none"])
def test_from_rows_pallas_byte_identity(n, pattern):
    """The planes kernel (interpret mode) decodes every bucket-edge row
    count byte-identically to the word-slice XLA lowering, including
    all-null validity."""
    rng = np.random.default_rng(100 + n)
    layout = compute_row_layout(FIXED_DTYPES)
    if n == 0:
        rows2d = jnp.zeros((0, layout.fixed_row_size), jnp.uint8)
    else:
        t = Table(_make_cols(rng, FIXED_DTYPES, n, pattern))
        rows2d = rc.convert_to_rows(t)[0].rows2d(layout.fixed_row_size)
    got = pk.from_rows_fixed(rows2d, layout, interpret=True)
    want = rc._from_rows_fixed_jit(rows2d, layout)
    _assert_cols_equal(got, want)


# 8 B, 16 B and the reference benchmark's 1,008 B row (212 columns)
ROW_WIDTHS = [[INT8], [INT32, INT64, INT16],
              cycle_dtypes(FIXED_WIDTH_DTYPES, 212)]


@pytest.mark.parametrize("dts", ROW_WIDTHS, ids=["8B", "16B", "wide"])
def test_from_rows_pallas_row_widths(dts):
    """Identity holds for every row width (the VMEM scratch and the row
    tile follow it), with a tile that does not divide the row count."""
    rng = np.random.default_rng(7)
    layout = compute_row_layout(dts)
    t = Table(_make_cols(rng, dts, 100))
    rows2d = rc.convert_to_rows(t)[0].rows2d(layout.fixed_row_size)
    got = pk.from_rows_fixed(rows2d, layout, interpret=True)
    want = rc._from_rows_fixed_jit(rows2d, layout)
    _assert_cols_equal(got, want)


@pytest.mark.parametrize("pattern", [None, "most", "none"])
def test_convert_from_rows_knob_equivalence(monkeypatch, pattern):
    """The public decode returns identical tables under knob=1 (Pallas,
    interpret on CPU), knob=0 (kill switch: generic XLA), and the auto
    default."""
    rng = np.random.default_rng(11)
    t = Table(_make_cols(rng, FIXED_DTYPES, 130,
                         pattern or "plain"))
    batch = rc.convert_to_rows(t)[0]
    monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
    auto = rc.convert_from_rows(batch, FIXED_DTYPES)
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    pallas = rc.convert_from_rows(batch, FIXED_DTYPES)
    monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
    xla = rc.convert_from_rows(batch, FIXED_DTYPES)
    _assert_cols_equal(pallas.columns, auto.columns)
    _assert_cols_equal(xla.columns, auto.columns)


def test_from_rows_lowering_is_tpu_legal():
    """The decode's lowered HLO must contain no per-row dynamic-start
    gather/scatter (the TPU-illegal pattern behind the BENCH_r05
    ``INVALID_ARGUMENT`` failures).  Constant lane-select gathers from
    the strided word combine are fine — their index operands are tiny
    static vectors over byte lanes, not per-row matrices."""
    n = 64
    layout = compute_row_layout(FIXED_DTYPES)
    low = jax.jit(lambda r: rc._from_rows_fixed_jit(r, layout)).lower(
        jax.ShapeDtypeStruct((n, layout.fixed_row_size), np.uint8)
    ).as_text()
    assert "stablehlo.dynamic_slice" not in low
    assert "dynamic_gather" not in low
    assert "stablehlo.scatter" not in low
    for line in low.splitlines():
        if '"stablehlo.gather"' not in line:
            continue
        assert "indices_are_sorted = true" in line, line
        m = re.search(r"tensor<(\d+)x1xi32>", line)
        assert m, line
        # index vectors address byte lanes within a row (< row size),
        # never a [rows, bytes] gather matrix
        assert int(m.group(1)) <= layout.fixed_row_size, line


# ---------------------------------------------------------------------------
# row-pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [n for n in EDGE_ROWS if n > 0])
@pytest.mark.parametrize("pattern", ["most", "none", "plain"])
def test_to_rows_pallas_byte_identity(n, pattern):
    """The pack kernel (interpret mode) encodes every bucket-edge row
    count byte-identically to the oracle XLA pack, including all-null
    validity and no-validity columns."""
    rng = np.random.default_rng(600 + n)
    layout = compute_row_layout(FIXED_DTYPES)
    t = Table(_make_cols(rng, FIXED_DTYPES, n, pattern))
    got = np.asarray(pk.to_rows_fixed(t, layout, interpret=True))
    want = np.asarray(rc._oracle_to_rows_jit(t, layout))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("dts", ROW_WIDTHS, ids=["8B", "16B", "wide"])
def test_to_rows_pallas_row_widths(dts):
    """Identity holds for every row width (the VMEM scratch and the row
    tile follow it), with a tile that does not divide the row count."""
    rng = np.random.default_rng(17)
    layout = compute_row_layout(dts)
    t = Table(_make_cols(rng, dts, 100))
    got = np.asarray(pk.to_rows_fixed(t, layout, interpret=True))
    want = np.asarray(rc._oracle_to_rows_jit(t, layout))
    np.testing.assert_array_equal(want, got)


def test_to_rows_pallas_batch_slice():
    """The dynamic batch window (start/size) packs identically to
    slicing the oracle's full encode — the multi-batch planner path."""
    rng = np.random.default_rng(23)
    layout = compute_row_layout(FIXED_DTYPES)
    t = Table(_make_cols(rng, FIXED_DTYPES, 200))
    got = np.asarray(pk.to_rows_fixed(t, layout, start=32, size=64,
                                      interpret=True))
    want = np.asarray(rc._oracle_to_rows_jit(t, layout))[32:96]
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("pattern", [None, "most", "none"])
def test_convert_to_rows_knob_equivalence(monkeypatch, pattern):
    """The public encode returns identical row blobs under knob=1
    (Pallas, interpret on CPU), knob=0 (kill switch), and auto."""
    rng = np.random.default_rng(29)
    t = Table(_make_cols(rng, FIXED_DTYPES, 130, pattern or "plain"))
    layout = compute_row_layout(FIXED_DTYPES)

    def blob(knob):
        if knob is None:
            monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("SRJ_TPU_PALLAS", knob)
        batches = rc.convert_to_rows(t)
        return [np.asarray(b.rows2d(layout.fixed_row_size))
                for b in batches]

    auto, pallas, xla = blob(None), blob("1"), blob("0")
    assert len(auto) == len(pallas) == len(xla)
    for a, p, x in zip(auto, pallas, xla):
        np.testing.assert_array_equal(a, p)
        np.testing.assert_array_equal(a, x)


def test_to_rows_lowering_is_tpu_legal():
    """The pack's XLA glue (word-plane builder) and the XLA twin must
    contain no per-row dynamic-start gather/scatter in their lowered
    HLO — the same legality bar as the decode."""
    rng = np.random.default_rng(31)
    layout = compute_row_layout(FIXED_DTYPES)
    t = Table(_make_cols(rng, FIXED_DTYPES, 64))
    for low in (
        jax.jit(lambda tt: pk._word_planes_from_table(tt, layout))
        .lower(t).as_text(),
        jax.jit(lambda tt: rc._to_rows_fixed_jit(tt, layout))
        .lower(t).as_text(),
    ):
        assert "stablehlo.dynamic_slice" not in low
        assert "dynamic_gather" not in low
        assert "stablehlo.scatter" not in low
        for line in low.splitlines():
            if '"stablehlo.gather"' not in line:
                continue
            assert "indices_are_sorted = true" in line, line
            m = re.search(r"tensor<(\d+)x1xi32>", line)
            assert m, line
            # byte packing's lane gathers, or unpack_bools' 128-lane one
            assert int(m.group(1)) <= max(layout.fixed_row_size, 128), \
                line


# ---------------------------------------------------------------------------
# hashes
# ---------------------------------------------------------------------------

HASH_DTYPES = [INT32, INT64, FLOAT64, INT16, FLOAT32, INT8, BOOL8]


@pytest.mark.parametrize("n", [n for n in EDGE_ROWS if n > 0])
@pytest.mark.parametrize("pattern", ["most", "none", "plain"])
def test_murmur3_pallas_byte_identity(n, pattern):
    rng = np.random.default_rng(200 + n)
    cols = _make_cols(rng, HASH_DTYPES, n, pattern)
    b = shapes.bucket_rows(n)
    pcols = tuple(shapes.pad_column(c, b) for c in cols)
    want = np.asarray(H._murmur3_jit(pcols, 42, 0))
    got = np.asarray(pk.murmur3_fixed(pcols, 42, interpret=True))
    np.testing.assert_array_equal(want, got)


# the reference benchmark's nine cudf types, then both floats
CYCLE_DTYPES = [INT8, INT32, INT16, INT64, INT32, BOOL8, UINT16, UINT8,
                UINT64, FLOAT32, FLOAT64]

_NAN_BITS = {4: [0x7FC00001, 0xFFC00000, 0x7F800001],
             8: [0x7FF0000000000001, 0xFFF8000000000000]}


def _edge_values(rng, dt, n):
    """Random values of ``dt`` with Spark's edge cases planted: -0.0,
    0.0 and NaNs of several bit patterns for floats; the type's minimum
    (INT_MIN), maximum and all-ones (0xFF, 0xFFFF) for integers."""
    np_dt = dt.np_dtype
    if np_dt.kind == "f":
        vals = rng.standard_normal(n).astype(np_dt)
        vals[0::6] = -0.0
        vals[1::6] = 0.0
        ui = np.dtype(f"u{np_dt.itemsize}")
        for j, bits in enumerate(_NAN_BITS[np_dt.itemsize]):
            vals[2 + j::6] = np.array(bits, ui).view(np_dt)
        return vals
    if dt.kind == "bool8":
        return rng.integers(0, 2, n).astype(np_dt)
    info = np.iinfo(np_dt)
    vals = rng.integers(info.min, info.max, n, dtype=np_dt, endpoint=True)
    vals[0::5] = info.min
    vals[1::5] = info.max
    vals[2::5] = np.array(-1).astype(np_dt)        # all ones
    vals[3::5] = 0
    return vals


def _np_rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _np_mix(h, k):
    k = _np_rotl(k * np.uint32(0xCC9E2D51), 15) * np.uint32(0x1B873593)
    return _np_rotl(h ^ k, 13) * np.uint32(5) + np.uint32(0xE6546B64)


def _np_fmix(h, length):
    h = h ^ np.uint32(length)
    h = (h ^ (h >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    h = (h ^ (h >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _spark_mm3(sample, seed=42):
    """Plain Spark ``hash()`` over ``[(values, valid)]``: murmur3_x86_32
    chained over the columns, a null field skipped; narrow ints as one
    sign-extended int (unsigned ones zero-extended), 8-byte values as a
    long, floats as their bits with -0.0 -> 0.0 and NaN canonical."""
    h = np.full(len(sample[0][0]), seed, np.uint32)
    with np.errstate(over="ignore"):
        for vals, valid in sample:
            if vals.dtype.kind == "f":
                vals = np.where(vals == 0, vals.dtype.type(0), vals)
                vals = np.where(np.isnan(vals), vals.dtype.type(np.nan),
                                vals)
            if vals.dtype.itemsize == 8:
                u = vals.view(np.uint64)
                lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                hc = _np_fmix(_np_mix(_np_mix(h, lo), (u >> np.uint64(
                    32)).astype(np.uint32)), 8)
            elif vals.dtype.kind == "f":
                hc = _np_fmix(_np_mix(h, vals.view(np.uint32)), 4)
            else:
                hc = _np_fmix(_np_mix(h, vals.astype(np.int64).astype(
                    np.uint32)), 4)
            h = hc if valid is None else np.where(valid, hc, h)
    return h.view(np.int32)


def _spark_mm3_bytes(h, data: bytes):
    """Spark ``hashUnsafeBytes`` of one row from the running hash ``h``
    (uint32): 4-byte little-endian blocks, then each tail byte
    sign-extended as a block of its own."""
    nb = len(data) // 4
    with np.errstate(over="ignore"):
        for i in range(nb):
            h = _np_mix(h, np.frombuffer(data[4 * i:4 * i + 4], "<u4")[0])
        for t in data[4 * nb:]:
            h = _np_mix(h, np.int64(t - 256 if t >= 128 else t).astype(
                np.uint32))
        return _np_fmix(h, len(data))


def _edge_table(rng, dtypes, n, null_rate):
    sample, cols = [], []
    for dt in dtypes:
        vals = _edge_values(rng, dt, n)
        valid = None if null_rate is None else rng.random(n) >= null_rate
        sample.append((vals, valid))
        cols.append(Column.from_numpy(vals, dt, valid))
    return tuple(cols), sample


@pytest.mark.parametrize("null_rate", [None, 0.0, 0.01, 1.0],
                         ids=["no_validity", "nulls0", "nulls1pct",
                              "nulls100"])
def test_murmur3_dense_edge_values(x64_both, null_rate):
    """The kernel reading the columns as stored equals the XLA chain and
    the plain Spark hash over every cycle dtype and both floats, with
    -0.0, NaNs, INT_MIN and all-ones values, at every null rate; the
    rows are bucket-padded (1,000 -> 1,024), short of a whole chunk."""
    rng = np.random.default_rng(900)
    cols, sample = _edge_table(rng, CYCLE_DTYPES, 1000, null_rate)
    b = shapes.bucket_rows(1000)
    pcols = tuple(shapes.pad_column(c, b) for c in cols)
    if null_rate is None:      # the bucket's rows, still no validity
        pcols = tuple(Column(c.dtype, c.data) for c in pcols)
    want = np.asarray(H._murmur3_chain(pcols, 42, 0))
    got = np.asarray(pk.murmur3_cols(pcols, 42, interpret=True))
    np.testing.assert_array_equal(want, got)
    np.testing.assert_array_equal(got[:1000], _spark_mm3(sample))


@pytest.mark.parametrize("n", [1, 8191, 8192, 9000, 20000])
def test_murmur3_dense_row_counts(n):
    """Row counts on and off the kernel's 8,192-row chunk, unpadded:
    the kernel pads to whole chunks itself and returns ``n`` rows."""
    rng = np.random.default_rng(910 + n)
    cols, sample = _edge_table(rng, CYCLE_DTYPES, n, 0.01)
    got = np.asarray(pk.murmur3_cols(cols, 42, interpret=True))
    assert got.shape == (n,)
    np.testing.assert_array_equal(np.asarray(H._murmur3_chain(cols, 42, 0)),
                                  got)
    np.testing.assert_array_equal(got, _spark_mm3(sample))


@pytest.mark.parametrize("valid", [False, True], ids=["plain", "nullable"])
def test_murmur3_dense_single_int32_key(monkeypatch, valid):
    """One INT32 key through the public entry, the exchange's shape
    (``runtime/plan.py``): one kernel call, equal to the XLA engine and
    the plain Spark hash."""
    rng = np.random.default_rng(920)
    vals = rng.integers(-2**31, 2**31, 5000, dtype=np.int64).astype(
        np.int32)
    vals[:3] = [-2**31, -1, 0]
    ok = rng.random(5000) > 0.2 if valid else None
    col = Column.from_numpy(vals, INT32, ok)
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    got = np.asarray(H.murmur3_hash([col]))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
    np.testing.assert_array_equal(np.asarray(H.murmur3_hash([col])), got)
    np.testing.assert_array_equal(got, _spark_mm3([(vals, ok)]))
    assert len(pk._mm3_runs((pk._mm3_kind(col, 0),))) == 1


def test_murmur3_dense_mixed_strings(monkeypatch):
    """Fixed-width columns around two dense-padded string columns, over
    several chained kernel calls: each string column takes one prep
    program, the fixed ones none, and the hash equals the XLA chain and
    a plain Spark reference row by row."""
    monkeypatch.setattr(pk, "_MM3_INTERPRET_COLS", 3)
    rng = np.random.default_rng(930)
    n = 300
    fixed, sample = _edge_table(rng, [INT64, INT8, FLOAT32, UINT16], n,
                                0.1)
    strs = [_string_rows(rng, n), _string_rows(rng, n)]
    scols = [Column.strings_padded(s) for s in strs]
    cols = (fixed[0], scols[0], fixed[1], fixed[2], scols[1], fixed[3])
    W = max(c.chars2d.shape[1] for c in scols)
    b, Wb = shapes.bucket_rows(n), shapes.bucket_width(W)
    pcols = tuple(shapes.pad_column(c, b, width=Wb) for c in cols)
    desc = tuple(pk._mm3_kind(c, pk._string_window(Wb, "mm3"))
                 for c in pcols)
    assert [k for k, *_ in desc].count("str") == 2
    assert len(pk._mm3_runs(desc, True)) == 2
    got = np.asarray(pk.murmur3_cols(pcols, 42, W=Wb, interpret=True))
    np.testing.assert_array_equal(
        np.asarray(H._murmur3_jit(pcols, 42, Wb)), got)
    order = [("f", 0), ("s", 0), ("f", 1), ("f", 2), ("s", 1), ("f", 3)]
    want = []
    for r in range(n):
        h = np.uint32(42)
        for kind, i in order:
            if kind == "s":
                if strs[i][r] is not None:
                    h = _spark_mm3_bytes(h, strs[i][r].encode())
                continue
            vals, ok = sample[i]
            if ok[r]:
                h = _spark_mm3([(vals[r:r + 1], None)], seed=h)[0].view(
                    np.uint32)
        want.append(h)
    np.testing.assert_array_equal(
        got[:n], np.array(want, np.uint32).view(np.int32))


@pytest.mark.parametrize("n", [n for n in EDGE_ROWS if n > 0])
@pytest.mark.parametrize("pattern", ["most", "none", "plain"])
def test_xxhash64_pallas_byte_identity(n, pattern):
    rng = np.random.default_rng(300 + n)
    cols = _make_cols(rng, HASH_DTYPES, n, pattern)
    b = shapes.bucket_rows(n)
    pcols = tuple(shapes.pad_column(c, b) for c in cols)
    want = np.asarray(H._xx64_jit(pcols, 7, 0))
    got = np.asarray(pk.xxhash64_fixed(pcols, 7, interpret=True))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("mode", ["mm3", "xx64"])
def test_hash_runs_share_compiles(monkeypatch, mode):
    """A wide schema hashes in chained runs, bit-exact with the XLA
    chain.  murmur3 runs a bounded number of columns a kernel call,
    compiled per run layout: a block of columns repeated four times
    compiles three kernels (the first run seeds the hash, the last
    finishes it, the two middle runs share one), and hashing it again
    compiles none.  xxhash64 runs at most ``_HASH_WORDS`` word rows; its
    XLA word prep compiles per dtype and the kernel per word layout, so
    a second order of the same dtypes adds neither (every fixed column
    is one 8-byte block)."""
    rng = np.random.default_rng(77)
    cols = tuple(_make_cols(rng, HASH_DTYPES * 4, 40))
    if mode == "mm3":
        monkeypatch.setattr(pk, "_MM3_INTERPRET_COLS", len(HASH_DTYPES))
        runs = pk._mm3_runs(tuple(pk._mm3_kind(c, 0) for c in cols), True)
        assert runs == [(a, a + len(HASH_DTYPES))
                        for a in range(0, len(cols), len(HASH_DTYPES))]
        pk._mm3_run.clear_cache()
        for _ in range(2):
            np.testing.assert_array_equal(
                np.asarray(H._murmur3_jit(cols, 42, 0)),
                np.asarray(pk.murmur3_cols(cols, 42, interpret=True)))
            assert pk._mm3_run._cache_size() == 3
        return
    shuffled = tuple(cols[i] for i in rng.permutation(len(cols)))
    runs = pk._hash_runs(cols, 0, mode)
    assert len(runs) > 1
    assert all(a is b for a, b in zip([c for r in runs for c in r], cols))
    assert all(pk._desc_rows(pk._hash_desc(r, 0, mode)) <= pk._HASH_WORDS
               for r in runs)
    sizes = []
    for cs in (cols, shuffled):
        np.testing.assert_array_equal(
            np.asarray(H._xx64_jit(cs, 42, 0)),
            np.asarray(pk.xxhash64_cols(cs, 42, interpret=True)))
        sizes.append((pk._hash_prep._cache_size(),
                      pk._xx64_call._cache_size()))
    assert sizes[1] == sizes[0]


@pytest.mark.parametrize("op", ["murmur3_hash", "xxhash64"])
@pytest.mark.parametrize("n", [1, 33, 257])
def test_hash_knob_equivalence(monkeypatch, op, n):
    """Public hash entries return identical values whichever engine the
    knob selects — including the ``SRJ_TPU_PALLAS=0`` kill switch."""
    rng = np.random.default_rng(400 + n)
    fn = getattr(H, op)
    cols = _make_cols(rng, [INT32, INT64, INT16], n)
    monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
    auto = np.asarray(fn(cols, 99))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    pallas = np.asarray(fn(cols, 99))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
    xla = np.asarray(fn(cols, 99))
    np.testing.assert_array_equal(auto, pallas)
    np.testing.assert_array_equal(auto, xla)


def _string_rows(rng, n):
    """String mix exercising the codec edges: empty strings, rows at the
    padded max width, non-aligned tails (1..3 bytes past a word), and
    nulls."""
    alpha = "abcdefghijklmnopqrstuvwxyz0123456789-_."
    out = []
    for i in range(n):
        if i % 11 == 0:
            out.append("")                       # empty string
        elif i % 7 == 0:
            out.append(None)                     # null row
        elif i % 5 == 0:
            out.append(alpha)                    # max-len row (39 = 4k+3)
        else:
            ln = int(rng.integers(1, len(alpha) + 1))
            out.append("".join(
                alpha[int(j)] for j in rng.integers(0, len(alpha), ln)))
    return out


def _padded_hash_cols(rng, n, with_fixed=True):
    scol = Column.strings_padded(_string_rows(rng, n))
    cols = [scol]
    if with_fixed:
        cols += list(_make_cols(rng, [INT32, INT64], n))
    W = scol.chars2d.shape[1]
    b = shapes.bucket_rows(n)
    Wb = shapes.bucket_width(W)
    return tuple(shapes.pad_column(c, b, width=Wb or None)
                 for c in cols), Wb


@pytest.mark.parametrize("n", [1, 9, 33, 257])
def test_murmur3_string_pallas_byte_identity(n):
    """The variable-width murmur3 codec (tail masking + sign-extended
    bytes) matches the XLA chain bit-for-bit on mixed string +
    fixed-width columns."""
    rng = np.random.default_rng(700 + n)
    pcols, Wb = _padded_hash_cols(rng, n)
    want = np.asarray(H._murmur3_jit(pcols, 42, Wb))
    got = np.asarray(pk.murmur3_cols(pcols, 42, W=Wb, interpret=True))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("n", [1, 9, 33, 257])
def test_xxhash64_string_pallas_byte_identity(n):
    """The xxhash64 string codec (32-byte chunks, 8-byte stripes,
    clamped 4-byte block, 3-byte tail) matches the XLA chain."""
    rng = np.random.default_rng(800 + n)
    pcols, Wb = _padded_hash_cols(rng, n)
    want = np.asarray(H._xx64_jit(pcols, 7, Wb))
    got = np.asarray(pk.xxhash64_cols(pcols, 7, W=Wb, interpret=True))
    np.testing.assert_array_equal(want, got)


def test_hash_string_all_empty():
    """A column whose every string is empty lowers to a zero-word codec
    (lens row only) and still matches XLA."""
    scol = Column.strings_padded(["", "", None, "", ""])
    b = shapes.bucket_rows(5)
    pcols = (shapes.pad_column(scol, b),)
    Wb = shapes.bucket_width(scol.chars2d.shape[1])
    want = np.asarray(H._murmur3_jit(pcols, 42, Wb))
    got = np.asarray(pk.murmur3_cols(pcols, 42, W=Wb, interpret=True))
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("op", ["murmur3_hash", "xxhash64"])
def test_hash_string_knob_equivalence(monkeypatch, op):
    """Public hash entries over padded string columns return identical
    values whichever engine the knob selects."""
    rng = np.random.default_rng(47)
    fn = getattr(H, op)
    scol = Column.strings_padded(_string_rows(rng, 70))
    icol = Column.from_numpy(np.arange(70, dtype=np.int32), INT32)
    monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
    auto = np.asarray(fn([scol, icol], 99))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    pallas = np.asarray(fn([scol, icol], 99))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
    xla = np.asarray(fn([scol, icol], 99))
    np.testing.assert_array_equal(auto, pallas)
    np.testing.assert_array_equal(auto, xla)


def test_hash_pallas_skips_arrow_strings(monkeypatch):
    """Arrow-layout (offsets+chars) string columns are ineligible — the
    per-row dynamic-start gather their window extraction needs is the
    TPU-illegal pattern — so they fall to XLA even with the knob forced
    on, and the result is unchanged.  Dense-padded strings ride Pallas
    (covered by the knob-equivalence test above)."""
    docs = Column.strings(["a", "bc", "", "longer-value", "x"] * 7)
    icol = Column.from_numpy(np.arange(35, dtype=np.int32), INT32)
    assert not pk.hash_cols_eligible((docs, icol))
    monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
    want = np.asarray(H.murmur3_hash([icol, docs]))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    got = np.asarray(H.murmur3_hash([icol, docs]))
    np.testing.assert_array_equal(want, got)


def test_murmur3_wide_window_takes_xla(monkeypatch):
    """A string window wider than the murmur3 kernel's VMEM allows goes
    to the XLA chain (stamped ineligible); xxhash64's kernel, which
    tiles by lanes, still takes it."""
    wide = Column.strings_padded(["a" * (pk._MM3_MAX_WINDOW + 1), None])
    icol = Column.from_numpy(np.arange(2, dtype=np.int32), INT32)
    ok = Column.strings_padded(["a" * pk._MM3_MAX_WINDOW, None])
    assert pk._mm3_cols_eligible((icol, ok))
    assert not pk._mm3_cols_eligible((icol, wide))
    assert pk.hash_cols_eligible((icol, wide))
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    assert pk.choose("murmur3_hash", "tpu", sig=(icol, wide)) == (
        "xla", False)
    assert pk.choose("xxhash64", "tpu", sig=(icol, wide)) == (
        "pallas", False)


def test_hash_string_lowering_is_tpu_legal():
    """The string-hash word builders (padded windows -> word rows: the
    murmur3 string prep, the xxhash64 mats) must lower without per-row
    dynamic-start gathers: padded ``chars_window`` is a static slice, so
    only tiny sorted lane-index gathers from byte packing may appear."""
    rng = np.random.default_rng(53)
    pcols, Wb = _padded_hash_cols(rng, 33)
    Wp = pk._string_window(Wb, "mm3")
    for build in (
            lambda cs: pk._mm3_string_prep(cs[0], Wp, pk._mm3_rows(33)),
            lambda cs: pk._hash_mats(cs, Wb, "xx64")[0]):
        low = jax.jit(build).lower(pcols).as_text()
        assert "stablehlo.dynamic_slice" not in low
        assert "dynamic_gather" not in low
        assert "stablehlo.scatter" not in low
        for line in low.splitlines():
            if '"stablehlo.gather"' not in line:
                continue
            assert "indices_are_sorted = true" in line, line


def test_scalar_oracle_survives_dispatch(monkeypatch):
    """Spark's pinned scalar vector still holds through the dispatcher:
    hash(1) == -559580957 (reference spark_hash test)."""
    col = Column.from_numpy(np.array([1], np.int32), INT32)
    for knob in ("0", "1"):
        monkeypatch.setenv("SRJ_TPU_PALLAS", knob)
        assert int(np.asarray(H.murmur3_hash([col], 42))[0]) == -559580957


# ---------------------------------------------------------------------------
# get_json scan
# ---------------------------------------------------------------------------

GJ_DOCS = [
    '{"a": [1, 2, 3], "b": {"c": [{"d": 4}, {"d": 5}, {"d": 6}]}}',
    '{"b": {"c": "str"}, "a": []}',
    '{"a": [true, null], "b": {"c": {"x": 1}}}',
    "",                                    # empty row
    '{"a": "unterminated',                 # malformed
    '{"b": {"c": "esc\\"aped"}}',          # escaped quote in capture
    '[1, 2]',                              # non-object top level
    '{"aa": 1, "a": [10, 20, 30, 40]}',    # key-prefix collision
]

GJ_PATHS = ["$.b.c", "$.a[1]", "$.a", "$.b.c[2].d"]

_GJ_FIELDS = ("start", "end", "found", "capturing", "bad", "deep")


def _gj_window(docs, pad=0):
    bs = [d.encode() for d in docs]
    W = max((len(b) for b in bs), default=1) + pad
    ch = np.zeros((len(bs), max(W, 1)), np.uint8)
    for i, b in enumerate(bs):
        ch[i, : len(b)] = np.frombuffer(b, np.uint8)
    return jnp.asarray(ch)


@pytest.mark.parametrize("path", GJ_PATHS)
def test_get_json_scan_pallas_identity(path):
    """The Pallas grid scan lands the same per-row automaton state
    (capture window, found/bad/deep flags) as the ``lax.scan`` chain,
    across object keys, array subscripts, and malformed rows."""
    from spark_rapids_jni_tpu.ops import get_json as GJ
    segs = tuple(GJ._parse_path(path))
    mkl = max((len(s) for s in segs if isinstance(s, bytes)), default=1)
    ch = _gj_window(GJ_DOCS)
    want = GJ._scan_automaton(ch, segs, mkl)
    got = pk.get_json_scan(ch, segs, mkl, interpret=True)
    for f in _GJ_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(want[f]), np.asarray(got[f]), err_msg=f)


@pytest.mark.parametrize("tile", [1, 4, 8])
def test_get_json_scan_tile_sizes(tile):
    """Identity holds for row tiles that do not divide the row count."""
    from spark_rapids_jni_tpu.ops import get_json as GJ
    segs = tuple(GJ._parse_path("$.b.c"))
    ch = _gj_window(GJ_DOCS * 3, pad=5)
    want = GJ._scan_automaton(ch, segs, 1)
    got = pk.get_json_scan(ch, segs, 1, interpret=True, tile_rows=tile)
    for f in _GJ_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(want[f]), np.asarray(got[f]), err_msg=f)


@pytest.mark.parametrize("path", GJ_PATHS)
def test_get_json_knob_equivalence(monkeypatch, path):
    """Public ``get_json_object`` returns the same extracted values
    under knob=1 (Pallas scan), knob=0, and auto — nulls included."""
    from spark_rapids_jni_tpu.ops import get_json as GJ
    col = Column.strings(GJ_DOCS + [None])

    def run(knob):
        if knob is None:
            monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
        else:
            monkeypatch.setenv("SRJ_TPU_PALLAS", knob)
        return GJ.get_json_object(col, path).to_pylist()

    auto, pallas, xla = run(None), run("1"), run("0")
    assert auto == pallas == xla


def test_get_json_step_is_gather_free():
    """The automaton step the Pallas kernel replays per char column must
    stay select/compare-only — a gather or scatter in the step would be
    Mosaic-illegal inside the kernel body."""
    from spark_rapids_jni_tpu.ops.get_json import _automaton_pieces
    make_carry0, step = _automaton_pieces((b"ab", 1, b"c"), 4)
    jaxpr = str(jax.make_jaxpr(
        lambda c, ch: step(c, (jnp.int32(3), ch))[0]
    )(make_carry0(8), jnp.zeros((8,), jnp.uint8)))
    assert "gather" not in jaxpr
    assert "scatter" not in jaxpr


# ---------------------------------------------------------------------------
# bloom probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 9, 256, 1023])
def test_bloom_device_probe_matches_host(monkeypatch, n):
    rng = np.random.default_rng(500 + n)
    bf = SB.SparkBloomFilter.optimal(4096, 0.03)
    ins = rng.integers(-(1 << 62), 1 << 62, 2048, dtype=np.int64)
    bf.put(Column.from_numpy(ins, INT64, None))
    probe = np.concatenate([
        ins[: n // 2 + 1],
        rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)])[:n]
    valid = rng.random(n) > 0.2
    col = Column.from_numpy(probe, INT64, valid)
    want = bf.might_contain(col)
    for knob in ("0", "1"):
        monkeypatch.setenv("SRJ_TPU_PALLAS", knob)
        got = np.asarray(SB.might_contain_device(bf, col))
        np.testing.assert_array_equal(want, got)


def test_bloom_device_all_null(monkeypatch):
    bf = SB.SparkBloomFilter.optimal(128, 0.03)
    vals = np.arange(64, dtype=np.int64)
    bf.put(Column.from_numpy(vals, INT64, None))
    col = Column.from_numpy(vals, INT64, np.zeros(64, dtype=bool))
    for knob in ("0", "1"):
        monkeypatch.setenv("SRJ_TPU_PALLAS", knob)
        got = np.asarray(SB.might_contain_device(bf, col))
        assert not got.any()


def test_bloom_device_narrow_int_cast(monkeypatch):
    """byte/short/int probes cast to long exactly as the host path
    (negative values sign-extend)."""
    bf = SB.SparkBloomFilter.optimal(512, 0.03)
    bf.put(Column.from_numpy(
        np.arange(-200, 200, dtype=np.int64), INT64, None))
    for np_dt, dt in ((np.int8, INT8), (np.int16, INT16),
                     (np.int32, INT32)):
        probe = np.arange(-120, 120, dtype=np_dt)
        col = Column.from_numpy(probe, dt, None)
        want = bf.might_contain(col)
        monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
        np.testing.assert_array_equal(
            want, np.asarray(SB.might_contain_device(bf, col)))


def test_bloom_device_rejects_strings():
    bf = SB.SparkBloomFilter.optimal(16, 0.03)
    with pytest.raises(ValueError, match="long-castable"):
        SB.might_contain_device(bf, Column.strings(["a", "b"]))


# ---------------------------------------------------------------------------
# knob / selection plumbing
# ---------------------------------------------------------------------------

def test_choose_contract(monkeypatch):
    monkeypatch.delenv("SRJ_TPU_PALLAS", raising=False)
    # auto off-TPU: generic XLA (tier-1 default behavior unchanged)
    assert pk.choose("convert_from_rows", "cpu") == ("xla", False)
    assert pk.choose("convert_from_rows", "tpu") == ("pallas", False)
    monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
    assert pk.choose("xxhash64", "tpu") == ("xla", False)
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    # forced on off-TPU runs in interpret mode
    assert pk.choose("xxhash64", "cpu") == ("pallas", True)
    assert pk.choose("xxhash64", "tpu") == ("pallas", False)
    # unsupported ops never route to pallas
    assert pk.choose("get_json", "tpu") == ("xla", False)


def test_choose_eligibility_hooks(monkeypatch):
    """Per-op ``eligible(sig)`` hooks veto signatures the kernels cannot
    serve; ineligible sigs fall to XLA even on TPU with the knob on."""
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
    # hash ops: sig is the padded column tuple — Arrow-layout strings
    # (per-row gather window) are out, dense-padded strings ride
    arrow = (Column.strings(["a", "bc"]),)
    padded = (Column.strings_padded(["a", "bc"]),)
    assert pk.choose("murmur3_hash", "tpu", sig=arrow) == ("xla", False)
    assert pk.choose("murmur3_hash", "tpu", sig=padded) == \
        ("pallas", False)
    assert pk.choose("xxhash64", "tpu", sig=arrow) == ("xla", False)
    # get_json: sig is (num_segs, window_width) — zero-width and
    # oversized windows stay on the scan chain
    assert pk.choose("get_json_object", "tpu", sig=(1, 64)) == \
        ("pallas", False)
    assert pk.choose("get_json_object", "tpu", sig=(1, 0)) == \
        ("xla", False)
    assert pk.choose("get_json_object", "tpu", sig=(1, 1 << 20)) == \
        ("xla", False)
    # row ops: sig is (num_columns, fixed_row_size) — rs is 8-aligned
    # for every real layout, so only degenerate sigs are vetoed
    assert pk.choose("convert_to_rows", "tpu", sig=(3, 48)) == \
        ("pallas", False)
    assert pk.choose("convert_to_rows", "tpu", sig=(0, 0)) == \
        ("xla", False)
    # sig=None (caller has no signature) never vetoes
    assert pk.choose("murmur3_hash", "tpu") == ("pallas", False)
    # a kernel Mosaic cannot lower is ineligible on TPU only
    assert pk.choose("bloom_might_contain", "tpu") == ("xla", False)
    assert pk.choose("bloom_might_contain", "cpu") == ("pallas", True)


def test_vmem_tile_pow2():
    """Tile negotiation returns pow-2 row tiles inside [floor, cap] so
    pow-2 row buckets divide evenly (no tile-tail padding on top of
    bucket padding)."""
    for bpr in (1, 3, 17, 64, 513, 4096):
        t = shapes.vmem_tile(bpr)
        assert t & (t - 1) == 0
        assert 32 <= t <= 4096
    assert shapes.vmem_tile(1 << 30) == 32        # floor
    assert shapes.vmem_tile(1) == 4096            # cap


def test_span_impl_attribution(monkeypatch, tmp_path):
    """The decode span carries ``impl=pallas`` under knob=1 and
    ``impl=xla`` under knob=0 — the attribute the costmodel ledger and
    ``obs profile`` split on."""
    import json
    from spark_rapids_jni_tpu import obs

    events = tmp_path / "events.jsonl"
    rng = np.random.default_rng(1)
    t = Table(_make_cols(rng, [INT32, INT64], 40))
    batch = rc.convert_to_rows(t)[0]
    obs.enable(sink=str(events))
    try:
        monkeypatch.setenv("SRJ_TPU_PALLAS", "1")
        rc.convert_from_rows(batch, [INT32, INT64])
        monkeypatch.setenv("SRJ_TPU_PALLAS", "0")
        rc.convert_from_rows(batch, [INT32, INT64])
        obs.flush()
    finally:
        obs.disable()
    impls = [e.get("impl") for line in events.read_text().splitlines()
             for e in [json.loads(line)]
             if e.get("kind") == "span"
             and e.get("name") == "convert_from_rows"]
    assert impls == ["pallas", "xla"], impls


def test_costmodel_splits_cells_per_impl():
    from spark_rapids_jni_tpu.obs import costmodel

    led = costmodel.Ledger()
    for impl in ("pallas", "xla"):
        led.observe({"kind": "span", "name": "convert_from_rows",
                     "bucket": 1024, "impl": impl, "wall_s": 0.5,
                     "device_s": 0.5, "bytes": 1 << 20, "rows": 1024})
    rows = led.profile(ceiling=100.0)
    assert {r["impl"] for r in rows} == {"pallas", "xla"}
    assert all(r["op"] == "convert_from_rows" for r in rows)
    # rendering tolerates baselines dumped before the impl split
    legacy = [{k: v for k, v in r.items() if k != "impl"} for r in rows]
    text = costmodel.render_profile(rows, baseline=legacy)
    assert "[pallas]" in text and "[xla]" in text
