"""End-to-end tour: every framework layer in one runnable script.

Covers the path a Spark executor would drive: parquet footer pruning ->
generated columnar data -> kernels (hash, cast, zorder, json, decimal,
membership) -> JCUDF row conversion -> distributed shuffle + q72-shaped
aggregate on an 8-device mesh -> operator metrics.

Run:  python examples/end_to_end.py      (CPU mesh; works anywhere)
"""

import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# compile cache: the caller's JAX_COMPILATION_CACHE_DIR, else the fixed
# checkout dir (settled before jax is imported)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402

jax.config.update("jax_default_device", jax.devices("cpu")[0])

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from spark_rapids_jni_tpu import (  # noqa: E402
    Column, INT32, STRING, Table, obs,
)
from spark_rapids_jni_tpu.ops import (  # noqa: E402
    convert_from_rows, convert_to_rows, get_json_object, interleave_bits,
    membership, murmur3_hash,
)
from spark_rapids_jni_tpu.parquet import (  # noqa: E402
    StructElement, ValueElement, read_and_filter,
)
from spark_rapids_jni_tpu.models import distributed_q72_step  # noqa: E402
from spark_rapids_jni_tpu.parallel import make_mesh  # noqa: E402
from spark_rapids_jni_tpu.utils.datagen import (  # noqa: E402
    DataProfile, create_random_table,
)


def main():
    obs.enable()   # spans record, and feed the obs metrics registry
    rng = np.random.default_rng(7)

    # 1. parquet footer: parse + prune a footer to the columns we read
    #    (synthetic footer via the test helpers; in production this buffer
    #    comes from the tail of a parquet file)
    from spark_rapids_jni_tpu.parquet.testing import flat_footer
    from spark_rapids_jni_tpu.parquet.thrift_dom import write_struct
    raw = write_struct(flat_footer(["item", "week", "qty", "extra"],
                                   rows_per_group=(1000, 1000)))
    sel = (StructElement.builder()
           .add_child("item", ValueElement())
           .add_child("week", ValueElement())
           .add_child("qty", ValueElement()).build())
    with read_and_filter(raw, 0, 1 << 40, sel) as footer:
        print(f"footer: engine={footer.engine} rows={footer.num_rows()} "
              f"cols={footer.num_columns()} (pruned from 4)")

    # 2. generate a table shaped like the pruned read
    n = 8 * 256
    t = create_random_table(
        [INT32, INT32, INT32, STRING], n,
        DataProfile(int_lower=0, int_upper=23, string_len_max=16), seed=7)
    print(f"table: {t.num_rows} rows x {t.num_columns} cols "
          f"(strings dense-padded: {t.columns[3].is_padded})")

    # 3. kernels
    h = murmur3_hash([t.columns[0], t.columns[3]])
    z = interleave_bits([t.columns[0], t.columns[1]])
    docs = Column.strings_padded(
        ['{"sku": {"id": %d}}' % i for i in range(8)])
    ids = get_json_object(docs, "$.sku.id").to_pylist()
    filt = membership.build([t.columns[0]])
    hit = membership.might_contain(
        filt, [Column.from_numpy(np.arange(30, dtype=np.int32), INT32)])
    print(f"kernels: hash[0]={int(np.asarray(h)[0])} "
          f"zorder[0]={int(np.asarray(z)[0, 0]):#x} json={ids[:3]} "
          f"membership hits={int(np.asarray(hit).sum())}/30")

    # 4. JCUDF row conversion roundtrip (strings ride the padded engine)
    batches = convert_to_rows(t)
    back = convert_from_rows(batches[0], t.dtypes)
    assert back.columns[3].to_pylist() == t.columns[3].to_pylist()
    print(f"rows: {len(batches)} batch(es), row_size="
          f"{batches[0].row_size}B, roundtrip OK")

    # 5. distributed q72 shape on the 8-device mesh
    mesh = make_mesh(jax.devices("cpu")[:8])
    b_item = rng.integers(0, 24, 64).astype(np.int32)
    b_inv = rng.integers(0, 6, 64).astype(np.int32)
    step = jax.jit(distributed_q72_step(mesh))
    gi, gw, cnt, qs, have, ng, ovf = step(
        t.columns[0].data, t.columns[1].data, t.columns[2].data,
        jnp.asarray(b_item), jnp.asarray(b_inv))
    assert not np.asarray(ovf).any()
    groups = int(np.asarray(have).sum())
    total = int(np.asarray(cnt).reshape(-1)[
        np.asarray(have).reshape(-1)].sum())
    print(f"q72: {groups} groups, {total} joined rows across 8 devices")

    # 6. q95 shape: exchange by order key -> left-semi vs returned
    # orders -> count/sum/min/max by ship date
    from spark_rapids_jni_tpu.models import distributed_q95_step
    order = rng.integers(0, 100, n).astype(np.int32)
    net = rng.integers(1, 500, n).astype(np.int32)
    returned = np.unique(rng.integers(0, 100, 30).astype(np.int32))
    q95 = jax.jit(distributed_q95_step(mesh))
    gd, c95, s95, mn95, mx95, have95, _, ovf95 = q95(
        jnp.asarray(order), t.columns[0].data, jnp.asarray(net),
        jnp.asarray(returned))
    assert not np.asarray(ovf95).any()
    print(f"q95: {int(np.asarray(have95).sum())} partial groups, "
          f"sum(net)={int(np.asarray(s95).reshape(-1)[np.asarray(have95).reshape(-1)].sum())}")

    # 7. Spark CAST kernels: float / decimal / date / timestamp
    from spark_rapids_jni_tpu.ops import (
        cast_string_to_float, cast_string_to_decimal128,
        cast_string_to_date, cast_string_to_timestamp,
        decimal128_from_ints, div_decimal128, decimal128_to_strings)
    from spark_rapids_jni_tpu import FLOAT64
    sc = Column.strings(["1.5e2", "-inf", "123.456", "2023-06-01",
                         "2023-06-01 12:30:00+05:30"])
    fv, _ = cast_string_to_float(sc, FLOAT64)
    dv, _ = cast_string_to_decimal128(sc, 2)
    dt_, _ = cast_string_to_date(sc)
    tsv, _ = cast_string_to_timestamp(sc)
    q, _ = div_decimal128(decimal128_from_ints([355], 2),
                          decimal128_from_ints([113], 0), 6)
    print(f"casts: float={fv.to_pylist()[0]} date={dt_.to_pylist()[3]} "
          f"ts={tsv.to_pylist()[4]} 3.55/113={decimal128_to_strings(q)[0]}")

    # 8. skew-safe strings: a 2KB outlier in a 16B column stays off the
    # device matrix (width cap + host tail), roundtripping exactly
    from spark_rapids_jni_tpu.table import string_tail
    vals = ["x%d" % i for i in range(256)]
    vals[17] = "Z" * 2048
    capped = Column.strings_padded(vals, width_cap="auto")
    tt = Table((Column.from_numpy(np.arange(256, dtype=np.int32), INT32),
                capped))
    rb = convert_to_rows(tt)
    rt = convert_from_rows(rb[0], tt.dtypes)
    assert rt.columns[1].to_pylist() == vals
    print(f"skew: capped width={capped.chars2d.shape[1]}B, "
          f"{len(string_tail(capped))} outlier in host tail, roundtrip OK")

    # 9. Table-level q95 with Spark null semantics: validity rides the
    # exchange, the semi join drops null order keys, and the aggregate
    # sums an INT64 net column exactly on device (multi-word limb sums)
    from spark_rapids_jni_tpu.models import distributed_q95_table_step
    from spark_rapids_jni_tpu import INT64
    from spark_rapids_jni_tpu.parallel import shard_table
    ov = rng.random(n) > 0.1
    tship = shard_table(Table((
        Column.from_numpy(order, INT32, valid=ov),
        Column.from_numpy(np.asarray(t.columns[0].data), INT32,
                          valid=np.ones(n, bool)),
        Column.from_numpy(net, INT32, valid=rng.random(n) > 0.2))), mesh)
    tret = Table((Column.from_numpy(returned, INT32,
                                    valid=np.ones(len(returned), bool)),))
    t95res, t95have, _, t95ovf = jax.jit(
        distributed_q95_table_step(mesh))(tship, tret)
    assert not np.asarray(t95ovf).any()
    print(f"q95 tables: {int(np.asarray(t95have).sum())} partial groups "
          "with null-aware COUNT/SUM/MIN/MAX")

    # 10. int64 measures aggregate exactly without x64 (uint32-pair
    # columns through the chunked limb kernels)
    from spark_rapids_jni_tpu.models import hash_aggregate_table
    big = Table((Column.from_numpy(rng.integers(0, 4, 1000)
                                   .astype(np.int32), INT32),
                 Column.from_numpy(rng.integers(-2 ** 40, 2 ** 40, 1000)
                                   .astype(np.int64), INT64)))
    bres, bhave, _ = hash_aggregate_table(
        big, key_idxs=[0], measures=[(1, "sum"), (1, "min"), (1, "max")],
        max_groups=8)
    print("int64 SUM/MIN/MAX groups:",
          int(np.asarray(bhave).sum()))

    # 11. the memory tier (RMM analogue): pooled host staging + device
    # buffer accounting
    from spark_rapids_jni_tpu import memory
    arena = memory.default_arena()
    tr = memory.DeviceBufferTracker()
    blob = tr.track(rb[0].data, tag="jcudf-batch")
    st = arena.stats()
    print(f"memory: arena reuse {st['reuse_count']}/{st['alloc_count']} "
          f"allocs, tracker live {tr.stats()['current_bytes']} bytes; "
          f"spill+restore", end=" ")
    host_img = tr.spill(blob)            # device buffer freed eagerly
    restored = jax.device_put(host_img)
    print("OK" if restored.shape == host_img.shape else "FAIL")

    # 12. round-5 query surface: int64 join keys past 2^31 (dense-id
    # composite probe), decimal128 AVG (exact limb SUM / COUNT with
    # HALF_UP), and adaptive dense aggregation for int32 date keys
    from spark_rapids_jni_tpu.models.pipeline import join_inner_table
    from spark_rapids_jni_tpu.ops.decimal import (decimal128_from_ints,
                                                  decimal128_to_ints)
    base = np.int64(3 << 32)
    build = Table((Column.from_numpy(
        np.array([base + 1, base + 2, base + 2], np.int64), INT64),
        Column.from_numpy(np.array([10, 20, 21], np.int32), INT32)))
    probe = Table((Column.from_numpy(
        np.array([base + 2, base + 9], np.int64), INT64),))
    _, pay, _, jvalid, _, _ = join_inner_table(build, 0, 1, probe, 0, 8)
    print("int64-key join payloads:",
          sorted(np.asarray(pay)[np.asarray(jvalid)].tolist()))

    davg = Table((Column.from_numpy(np.array([1, 1, 2], np.int32),
                                    INT32),
                  decimal128_from_ints([250, 251, -100], scale=2)))
    dres, dhave, _ = hash_aggregate_table(
        davg, key_idxs=[0], measures=[(1, "avg")], max_groups=4)
    print("decimal128 AVG (scale 6):",
          [decimal128_to_ints(dres.columns[1])[j]
           for j in np.nonzero(np.asarray(dhave))[0]])

    dates = Table((Column.from_numpy(
        rng.integers(2_415_022, 2_488_070, 4096).astype(np.int32),
        INT32),
        Column.from_numpy(rng.integers(0, 9, 4096).astype(np.int32),
                          INT32)))
    _, ahave, ang = hash_aggregate_table(
        dates, key_idxs=[0], measures=[(None, "count"), (1, "sum")],
        max_groups=8192)
    print(f"adaptive date-key group-by: {int(np.asarray(ang))} groups "
          "(dense-slot branch at runtime)")

    # 13. JSON path extraction on device (trailing + mid-path wildcards)
    jcol = Column.strings_padded(
        ['{"a":[{"b":1},{"c":9},{"b":2}]}', '{"a":[]}'])
    print("$.a[*].b ->", get_json_object(jcol, "$.a[*].b").to_pylist())

    # 14. operator metrics: the span families of the obs registry
    snap = obs.metrics.registry().snapshot()
    for family in ("srj_tpu_span_calls_total", "srj_tpu_span_rows_total",
                   "srj_tpu_span_bytes_total"):
        print(family + ":", dict(sorted(
            snap.get(family, {}).get("values", {}).items())))


if __name__ == "__main__":
    main()
