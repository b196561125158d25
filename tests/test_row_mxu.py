"""MXU permutation-matmul engine tests: cross-check against the oracle
gather path and the XLA concatenate path (the dual-implementation strategy
of the reference test suite, ``src/main/cpp/tests/row_conversion.cpp``)."""

import numpy as np
import pytest

from spark_rapids_jni_tpu import (
    BOOL8, FLOAT32, FLOAT64, INT16, INT32, INT64, INT8, UINT8, UINT16,
    UINT32, UINT64,
)
from spark_rapids_jni_tpu.table import (
    Column, Table, assert_tables_equivalent, decimal32, decimal64,
)
from spark_rapids_jni_tpu.ops import (
    compute_row_layout, convert_from_rows, convert_to_rows,
    convert_to_rows_fixed_width_optimized,
)
from spark_rapids_jni_tpu.utils import DataProfile, create_random_table, \
    cycle_dtypes

ALL_FIXED = [INT64, FLOAT64, UINT64, INT32, UINT32, FLOAT32, INT16, UINT16,
             INT8, UINT8, BOOL8, decimal32(2), decimal64(-1)]


def _random_table(rng, dtypes, n, null_mode="some"):
    cols = []
    for i, dt in enumerate(dtypes):
        if null_mode == "none":
            valid = None
        elif null_mode == "all":
            valid = np.ones(n, bool)
        elif null_mode == "zero":
            valid = np.zeros(n, bool)
        else:
            valid = rng.random(n) > 0.25
        info_kind = dt.np_dtype.kind
        if info_kind == "f":
            vals = rng.standard_normal(n)
        elif dt.kind == "bool8":
            vals = rng.integers(0, 2, n)
        else:
            info = np.iinfo(dt.np_dtype)
            vals = rng.integers(info.min, info.max, n, endpoint=True,
                                dtype=dt.np_dtype)
        cols.append(Column.from_numpy(vals, dt, valid))
    return Table(tuple(cols))


@pytest.mark.parametrize("n", [1, 6, 31, 4096, 6 * 1024 + 557])
def test_mxu_matches_oracle_all_types(rng, n):
    t = _random_table(rng, ALL_FIXED, n)
    got = convert_to_rows(t, impl="mxu")
    want = convert_to_rows_fixed_width_optimized(t)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.data), np.asarray(w.data))
        np.testing.assert_array_equal(np.asarray(g.offsets),
                                      np.asarray(w.offsets))


@pytest.mark.parametrize("null_mode", ["none", "all", "zero", "some"])
def test_mxu_roundtrip_null_patterns(rng, null_mode):
    t = _random_table(rng, ALL_FIXED, 777, null_mode)
    batches = convert_to_rows(t, impl="mxu")
    assert len(batches) == 1
    got = convert_from_rows(batches[0], t.dtypes, impl="mxu")
    assert_tables_equivalent(t, got)


def test_mxu_wide_cycled_schema(rng):
    dtypes = cycle_dtypes([INT64, FLOAT64, INT32, FLOAT32, INT16, INT8,
                           BOOL8], 212)
    t = create_random_table(dtypes, 2048, seed=3)
    a = convert_to_rows(t, impl="mxu")[0]
    b = convert_to_rows(t, impl="xla")[0]
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
    got = convert_from_rows(a, dtypes, impl="mxu")
    assert_tables_equivalent(t, got)


def test_mxu_single_column_each_width(rng):
    for dt in [INT64, INT32, INT16, INT8]:
        t = _random_table(rng, [dt], 100)
        rt = convert_from_rows(convert_to_rows(t, impl="mxu")[0], t.dtypes,
                               impl="mxu")
        assert_tables_equivalent(t, rt)


def test_mxu_cross_impl_decode(rng):
    """Rows encoded by one engine must decode identically by the others."""
    t = _random_table(rng, ALL_FIXED, 513)
    rows = convert_to_rows(t, impl="xla")[0]
    assert_tables_equivalent(t, convert_from_rows(rows, t.dtypes, impl="mxu"))
    rows = convert_to_rows(t, impl="mxu")[0]
    assert_tables_equivalent(t, convert_from_rows(rows, t.dtypes, impl="xla"))


def test_mxu_no_x64_pair_representation(rng):
    """64-bit columns as uint32 pairs (TPU/no-x64 mode) survive the MXU
    engine bit-exactly."""
    import jax
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        vals = np.array([0, -1, 2 ** 63 - 1, -2 ** 63, 123456789123456789],
                        dtype=np.int64)
        c = Column.from_numpy(vals, INT64, np.array([1, 1, 0, 1, 1], bool))
        t = Table((c,))
        rt = convert_from_rows(convert_to_rows(t, impl="mxu")[0], t.dtypes,
                               impl="mxu")
        assert rt.columns[0].data.ndim == 2
        assert t.columns[0].to_pylist() == rt.columns[0].to_pylist()
    finally:
        jax.config.update("jax_enable_x64", prev)


def test_multi_batch_roundtrip_with_nulls(rng):
    """Equal-batch encode with traced-start slicing must preserve values
    and validity across batch boundaries (incl. a non-multiple-of-8 tail
    batch)."""
    dtypes = [INT64, INT32, INT16, INT8, BOOL8]
    t = _random_table(rng, dtypes, 2003)
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    layout = compute_row_layout(t.dtypes)
    limit = layout.fixed_row_size * 512  # force ~4 batches, 32-aligned
    batches = convert_to_rows(t, impl="mxu", size_limit=limit)
    assert len(batches) > 1
    assert sum(b.num_rows for b in batches) == 2003
    parts = [convert_from_rows(b, t.dtypes, impl="mxu") for b in batches]
    got_cols = []
    for i in range(t.num_columns):
        vals = sum((p.columns[i].to_pylist() for p in parts), [])
        got_cols.append(vals)
    for i, c in enumerate(t.columns):
        assert c.to_pylist() == got_cols[i], f"column {i}"


def test_pallas_pack_matches_xla_pack(rng):
    """The Pallas single-pass plane packer must produce byte-identical
    rows to the XLA piece-wise packer (interpret mode on CPU)."""
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    for dts, n in [
        (ALL_FIXED, 1000),
        (cycle_dtypes([INT64, FLOAT64, INT32, FLOAT32, INT16, INT8, BOOL8],
                      212), 2048 + 77),
        ([INT8], 33),           # single 1-byte column
        ([INT64, INT64], 50),   # only 8-byte columns
        ([INT16, INT16, INT16], 257),  # odd 2-byte count
    ]:
        t = _random_table(rng, dts, n)
        layout = compute_row_layout(t.dtypes)
        a = row_mxu.to_rows_fixed(t, layout, pack="pallas_interpret")
        b = row_mxu.to_rows_fixed(t, layout, pack="xla")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"schema {dts[:4]}... n={n}")


def test_grouped_decode_matches_standard(rng):
    """The dtype-major grouped decode must produce the same columns and
    validity as the per-column decode."""
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.table import assert_tables_equivalent, Table
    from tests.test_row_conversion import make_table
    dtypes = [INT64, FLOAT64, INT32, FLOAT32, INT16, INT8, BOOL8] * 3
    t = make_table(rng, dtypes, 777, "most")
    layout = compute_row_layout(t.dtypes)
    blob = row_mxu.to_rows_fixed(t, layout)
    g = row_mxu.from_rows_fixed_grouped(blob, layout)
    std = Table(tuple(row_mxu.from_rows_fixed(blob, layout)))
    assert_tables_equivalent(std, g.to_table())
    assert_tables_equivalent(t, g.to_table())
    # single-column materialization agrees too
    np.testing.assert_array_equal(np.asarray(g.column(4).data),
                                  np.asarray(std.columns[4].data))


def test_fused_encoder_matches_xla(rng, x64_both):
    """The fused single-pass pack+dot encoder (interpret mode on CPU)
    must produce byte-identical rows to the XLA path, including batch
    encodes at tile-aligned offsets and partial tail tiles."""
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    T = row_mxu._FUSE_TILE
    for dts, n in [
        (ALL_FIXED, 2 * T + 313),
        ([INT8], T + 33),
        ([INT64, INT64], T + 50),
        ([INT16, INT16, INT16], T + 257),
    ]:
        t = _random_table(rng, dts, n)
        layout = compute_row_layout(t.dtypes)
        want = np.asarray(
            row_mxu.to_rows_fixed(t, layout, pack="xla")).reshape(n, -1)
        enc = row_mxu.FixedEncoder(t, layout, interpret=True)
        got = np.asarray(enc.encode(0, n)).reshape(n, -1)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"schema {dts[:3]} n={n}")
        if n >= 2 * T:
            got_b = np.asarray(enc.encode(T, T)).reshape(T, -1)
            np.testing.assert_array_equal(got_b, want[T:2 * T])
        tail = n - n // T * T
        got_t = np.asarray(
            enc.encode(n // T * T, tail)).reshape(tail, -1)
        np.testing.assert_array_equal(got_t, want[n // T * T:])


def test_fused_encoder_rejects_unaligned_start(rng):
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    t = _random_table(rng, [INT32], row_mxu._FUSE_TILE * 2)
    layout = compute_row_layout(t.dtypes)
    enc = row_mxu.FixedEncoder(t, layout, interpret=True)
    with pytest.raises(ValueError, match="aligned"):
        enc.encode(7, 100)


def test_fused_decode_planes_matches_xla(rng, x64_both):
    """The fused decode-to-planes kernel must reproduce the XLA
    dot+recombine path for both the per-column and grouped decoders."""
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    dtypes = cycle_dtypes(ALL_FIXED, 29)
    n = row_mxu._FUSE_TILE + 409
    t = _random_table(rng, dtypes, n)
    layout = compute_row_layout(t.dtypes)
    blob = row_mxu.to_rows_fixed(t, layout, pack="xla")
    cols_x = row_mxu.from_rows_fixed(blob, layout, mode="xla")
    cols_p = row_mxu.from_rows_fixed(blob, layout,
                                     mode="pallas_interpret")
    for a, b in zip(cols_x, cols_p):
        np.testing.assert_array_equal(np.asarray(a.data),
                                      np.asarray(b.data))
        np.testing.assert_array_equal(np.asarray(a.validity),
                                      np.asarray(b.validity))
    g_x = row_mxu.from_rows_fixed_grouped(blob, layout, mode="xla")
    g_p = row_mxu.from_rows_fixed_grouped(blob, layout,
                                          mode="pallas_interpret")
    for a, b in zip(g_x.tree_flatten()[0], g_p.tree_flatten()[0]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_transpose_engine_matches_dot_engine(rng):
    """The no-MXU transpose encoder (contiguous-run block copies +
    arithmetic validity bytes) must produce byte-identical JCUDF blobs
    to the permutation-dot kernel across schema shapes."""
    from bench import FIXED_DTYPES, cycle_dtypes
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    from spark_rapids_jni_tpu.table import (INT8, INT16, INT32, FLOAT64,
                                            BOOL8)
    for dtypes, n in ((cycle_dtypes(FIXED_DTYPES, 212), 2048),
                      ([INT32, INT8, INT16, BOOL8, FLOAT64], 1001),
                      ([INT8] * 3 + [INT16] * 5 + [INT32], 2048)):
        t = _random_table(rng, dtypes, n)
        layout = compute_row_layout(t.dtypes)
        gc = row_mxu.table_to_grouped(t, layout)
        a = np.asarray(row_mxu.to_rows_fixed_grouped(gc, interpret=True))
        b = np.asarray(row_mxu.to_rows_fixed_grouped_transpose(gc))
        np.testing.assert_array_equal(a, b)


def test_grouped_encode_is_not_written_to_the_compile_cache(rng, tmp_path):
    """The grouped encode forces its blob row-major.  JAX reads such an
    executable back from the persistent compile cache labelling the blob
    with the default layout, and every program that then reads the blob
    is refused at launch, so the encode is compiled in each process and
    never written there; the pack before it is cached as usual."""
    import os
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from spark_rapids_jni_tpu.ops import row_mxu
    from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
    # a schema no other test encodes, so both programs compile here
    t = _random_table(rng, [INT16, UINT8, INT64, BOOL8, INT16, UINT8, INT8],
                      row_mxu._FUSE_TILE)
    layout = compute_row_layout(t.dtypes)
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    try:
        cc.reset_cache()
        for k, v in zip(keys, (str(tmp_path), 0.0, -1)):
            jax.config.update(k, v)
        enc = row_mxu.FixedEncoder(t, layout, interpret=True)
        blob = jax.block_until_ready(enc.encode(0, t.num_rows))
        written = os.listdir(tmp_path)
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert blob.shape == (t.num_rows, layout.fixed_row_size)
    assert any(f.startswith("jit__pack_grouped_jit-") for f in written)
    assert not any("grouped_encode" in f for f in written), written
