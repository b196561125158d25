"""The plain references against the layout rules and the scalar
definitions they restate, and the byte counts against hand counts."""

import numpy as np
import pytest

from benchmarks.harness import work
from benchmarks.references import jcudf, spark_hash

# INT8 INT32 INT16 INT64 INT32 BOOL8 UINT16 UINT8 UINT64: the reference's
# cycle in both row-conversion benchmarks
CYCLE = [1, 4, 2, 8, 4, 1, 2, 1, 8]
NP = [np.int8, np.int32, np.int16, np.int64, np.int32, np.bool_, np.uint16,
      np.uint8, np.uint64]


def _sizes(ncols):
    return [CYCLE[i % 9] for i in range(ncols)]


def test_212_column_layout_hand_count():
    starts, voff, vbytes, rs = jcudf.layout(_sizes(212))
    # one cycle of nine columns ends at 48 (int64 and uint64 each padded
    # to 8), so the next cycle starts aligned; 23 cycles end at 1,104,
    # then int8, int32, int16, int64 (padded to 1,120) and int32
    assert starts[:10] == [0, 4, 8, 16, 24, 28, 30, 32, 40, 48]
    assert starts[207:] == [1104, 1108, 1112, 1120, 1128]
    assert (voff, vbytes, rs) == (1132, 27, 1160)


def test_155_column_layout_hand_count():
    starts, voff, vbytes, rs = jcudf.layout(_sizes(155))
    # 17 cycles end at 816, then int8 and int32; 20 validity bytes
    assert starts[153:] == [816, 820]
    assert (voff, vbytes, rs) == (824, 20, 848)


def test_byte_counts_hand_count():
    n = 4 << 20
    # 31 data bytes a cycle: 23 cycles and 19 bytes; 212 validity bits
    assert work.column_bytes(_sizes(212), 1) == 732 + 26.5 == 758.5
    # 17 cycles and 5 bytes; 155 validity bits
    assert work.column_bytes(_sizes(155), 1) == 532 + 19.375 == 551.375
    assert work.row_bytes(_sizes(155), n) == 848 * n
    assert work.rows_convert_bytes(_sizes(155), n) == (551.375 + 848) * n
    assert work.hash_bytes(_sizes(212), n, 4) == (758.5 + 4) * n


@pytest.mark.parametrize("name", ["jcudf_fixed212", "jcudf_fixed155"])
def test_config_sizes_follow_the_layout(name):
    import json
    import os
    from benchmarks.harness import spec
    cfg = json.load(open(os.path.join(spec.BENCH_DIR, "configs",
                                      name + ".json")))
    sizes = [np.dtype(t if t != "bool8" else "uint8").itemsize
             for t in cfg["dtype_cycle"]]
    sizes = [sizes[i % len(sizes)] for i in range(cfg["num_columns"])]
    assert sizes == _sizes(cfg["num_columns"])
    assert jcudf.layout(sizes)[3] == cfg["row_bytes"]
    assert work.column_bytes(sizes, 1) == cfg["column_bytes_per_row"]


def _sample(rng, k, ncols, nulls=0.2):
    vals, valid = [], []
    for c in range(ncols):
        dt = np.dtype(NP[c % len(NP)])
        if dt.kind == "f":
            v = rng.standard_normal(k).astype(dt)
            v[:3] = [-0.0, np.nan, 0.0]
        elif dt == np.bool_:
            v = rng.integers(0, 2, k).astype(np.uint8)
        else:
            info = np.iinfo(dt)
            v = rng.integers(info.min, info.max, k, dtype=dt,
                             endpoint=True)
        vals.append(v)
        valid.append(rng.random(k) > nulls)
    return vals, valid


def test_jcudf_encode_follows_the_layout_rules():
    rng = np.random.default_rng(0)
    vals, valid = _sample(rng, 64, 10)
    rows = jcudf.encode(vals, valid)
    starts, voff, vbytes, rs = jcudf.layout([v.itemsize for v in vals])
    assert rows.shape == (64, rs)
    for c, (v, s) in enumerate(zip(vals, starts)):
        # null slots keep the column's bytes
        assert np.array_equal(rows[:, s:s + v.itemsize],
                              v.view(np.uint8).reshape(64, -1))
        bit = (rows[:, voff + c // 8] >> (c % 8)) & 1
        assert np.array_equal(bit == 1, valid[c])
    used = np.zeros(rs, bool)
    for v, s in zip(vals, starts):
        used[s:s + v.itemsize] = True
    used[voff:voff + vbytes] = True
    assert not rows[:, ~used].any()          # padding is zero
    assert not (rows[:, voff + vbytes - 1] >> (len(vals) % 8)).any()


def test_jcudf_round_trip_and_control():
    rng = np.random.default_rng(1)
    vals, valid = _sample(rng, 100, 23)
    rows = jcudf.encode(vals, valid)
    got, ok = jcudf.decode(rows, [v.dtype for v in vals])
    for a, b, c, d in zip(got, vals, ok, valid):
        assert np.array_equal(a.view(np.uint8), b.view(np.uint8))
        assert np.array_equal(c, d)
    bad = jcudf.encode(vals, valid, keep_nulls=False)
    assert np.count_nonzero(bad != rows) > 0


def test_jcudf_matches_the_program_on_the_cpu():
    """A second witness: the program's rows for the same table."""
    from spark_rapids_jni_tpu import ops
    from benchmarks.harness import datagen, tables
    cfg = {"dtype_cycle": ["int8", "int32", "int16", "int64", "int32",
                           "bool8", "uint16", "uint8", "uint64"],
           "num_columns": 21}
    dtypes = datagen.fixed_width_dtypes(cfg)
    table = datagen.fixed_width_table(3, dtypes, 256, 0.1)
    (batch,) = ops.convert_to_rows(table)
    idx = np.arange(256, dtype=np.int32)
    sample = tables.sample_table(table, idx)
    want = jcudf.encode([v for v, _ in sample], [ok for _, ok in sample])
    assert np.array_equal(
        tables.sample_rows(batch.data, idx, want.shape[1]), want)


NP_FLOATS = [np.float32, np.float64]


@pytest.mark.parametrize("ncols", [1, 9, 12])
@pytest.mark.parametrize("floats", [False, True], ids=["cycle", "floats"])
def test_vectorized_murmur3_equals_the_scalar_chain(ncols, floats):
    rng = np.random.default_rng(ncols)
    vals, valid = _sample(rng, 200, ncols)
    if floats:
        for c in range(ncols):
            v = rng.standard_normal(200).astype(NP_FLOATS[c % 2])
            v[:3] = [-0.0, np.nan, 0.0]
            vals[c] = v
    sample = list(zip(vals, valid))
    mm = spark_hash.murmur3(sample)
    assert np.array_equal(mm, spark_hash.scalar_murmur3(sample))
    ctrl = spark_hash.murmur3(sample, keep_nulls=False)
    assert np.count_nonzero(ctrl != mm) > 0


def test_spark_literals():
    # Spark: hash(1) = -559580957, hash(0) = 933211791 (seed 42)
    one = np.array([1, 0], np.int32)
    mm = spark_hash.murmur3([(one, np.ones(2, bool))])
    assert mm.tolist() == [-559580957, 933211791]
    # a byte, a short and a bool hash as the same int; an unsigned byte
    # zero-extends
    for dt in (np.int8, np.int16, np.uint8, np.uint16):
        got = spark_hash.murmur3([(one.astype(dt), np.ones(2, bool))])
        assert got.tolist() == mm.tolist()
    neg = spark_hash.murmur3([(np.array([-1], np.int8), np.ones(1, bool))])
    assert neg.tolist() == spark_hash.murmur3(
        [(np.array([-1], np.int32), np.ones(1, bool))]).tolist()
    assert spark_hash.murmur3([(np.array([255], np.uint8), np.ones(1, bool))]
                              ).tolist() == spark_hash.murmur3(
        [(np.array([255], np.int32), np.ones(1, bool))]).tolist()
    assert spark_hash.pmod(np.array([-7, 7], np.int32), 5).tolist() == [3, 2]
