"""Table/GroupedColumns operator layer: Spark null semantics
cross-checked against a pure-numpy oracle (the reference inherits these
semantics from Spark above it — SURVEY.md §1 layer map)."""

import numpy as np
import pytest

from spark_rapids_jni_tpu import Column, Table, INT32, INT64, FLOAT32
from spark_rapids_jni_tpu.models.pipeline import (
    hash_aggregate_table, join_inner_table, join_semi_mask_table,
)


def _oracle_groupby(keys, key_valid, measures, live=None):
    """dict: composite key tuple (None for null) -> per-measure value."""
    n = len(keys[0])
    live = np.ones(n, bool) if live is None else live
    groups = {}
    for r in range(n):
        if not live[r]:
            continue
        kt = tuple(None if not kv[r] else int(k[r])
                   for k, kv in zip(keys, key_valid))
        g = groups.setdefault(kt, [])
        g.append(r)
    out = {}
    for kt, rows in groups.items():
        vals = []
        for vcol, vvalid, op in measures:
            if op == "count_star":
                vals.append(len(rows))
                continue
            nn = [vcol[r] for r in rows if vvalid[r]]
            if op == "count":
                vals.append(len(nn))
            elif not nn:
                vals.append(None)          # SUM/MIN/MAX/AVG of empty
            elif op == "sum":
                vals.append(sum(int(x) for x in nn))
            elif op == "min":
                vals.append(min(nn))
            elif op == "max":
                vals.append(max(nn))
            elif op == "avg":
                vals.append(float(sum(float(x) for x in nn) / len(nn)))
        out[kt] = vals
    return out


def test_aggregate_null_semantics_vs_oracle(rng):
    n = 500
    keys = rng.integers(0, 12, n).astype(np.int32)
    kvalid = rng.random(n) > 0.2            # ~20% null keys
    vals = rng.integers(-100, 100, n).astype(np.int32)
    vvalid = rng.random(n) > 0.3
    t = Table((Column.from_numpy(keys, INT32, valid=kvalid),
               Column.from_numpy(vals, INT32, valid=vvalid)))
    res, have, num_groups = hash_aggregate_table(
        t, key_idxs=[0],
        measures=[(None, "count"), (1, "count"), (1, "sum"),
                  (1, "min"), (1, "max"), (1, "avg")],
        max_groups=64)
    oracle = _oracle_groupby(
        [keys], [kvalid],
        [(vals, vvalid, "count_star"), (vals, vvalid, "count"),
         (vals, vvalid, "sum"), (vals, vvalid, "min"),
         (vals, vvalid, "max"), (vals, vvalid, "avg")])
    assert int(np.asarray(num_groups)) == len(oracle)
    hv = np.asarray(have)
    gk = res.columns[0].to_pylist()
    cols = [res.columns[i].to_pylist() for i in range(1, 7)]
    got = {}
    for j in np.nonzero(hv)[0]:
        key = (gk[j],)                       # None for the null group
        got[key] = [c[j] for c in cols]
    for kt, exp in oracle.items():
        g = got[kt]
        for gi, (gv, ev) in enumerate(zip(g, exp)):
            if gi == 5 and ev is not None:   # avg: float compare
                assert gv == pytest.approx(ev), (kt, gi)
            else:
                assert gv == ev, (kt, gi, g, exp)
    assert set(got) == set(oracle)


def test_aggregate_multi_key_null_safe_grouping(rng):
    n = 300
    k1 = rng.integers(0, 4, n).astype(np.int32)
    v1 = rng.random(n) > 0.3
    k2 = rng.integers(0, 3, n).astype(np.int32)
    v2 = rng.random(n) > 0.3
    vals = rng.integers(0, 50, n).astype(np.int32)
    t = Table((Column.from_numpy(k1, INT32, valid=v1),
               Column.from_numpy(k2, INT32, valid=v2),
               Column.from_numpy(vals, INT32)))
    res, have, num_groups = hash_aggregate_table(
        t, key_idxs=[0, 1], measures=[(2, "sum"), (None, "count")],
        max_groups=64)
    ones = np.ones(n, bool)
    oracle = _oracle_groupby([k1, k2], [v1, v2],
                             [(vals, ones, "sum"),
                              (vals, ones, "count_star")])
    assert int(np.asarray(num_groups)) == len(oracle)
    hv = np.asarray(have)
    g1 = res.columns[0].to_pylist()
    g2 = res.columns[1].to_pylist()
    sums = res.columns[2].to_pylist()
    counts = res.columns[3].to_pylist()
    got = {(g1[j], g2[j]): [sums[j], counts[j]]
           for j in np.nonzero(hv)[0]}
    assert got == oracle


def test_aggregate_int64_keys_no_x64():
    """64-bit keys group via their (hi, lo) plane pair expansion."""
    import jax
    with jax.enable_x64(False):
        keys = np.array([2**40, -1, 2**40, -1, 7, 2**40], np.int64)
        vals = np.array([1, 2, 3, 4, 5, 6], np.int32)
        t = Table((Column.from_numpy(keys, INT64),
                   Column.from_numpy(vals, INT32)))
        res, have, num_groups = hash_aggregate_table(
            t, key_idxs=[0], measures=[(1, "sum")], max_groups=8)
        hv = np.asarray(have)
        gk = res.columns[0].to_pylist()
        sums = res.columns[1].to_pylist()
        got = {gk[j]: sums[j] for j in np.nonzero(hv)[0]}
        assert got == {2**40: 10, -1: 6, 7: 5}


def test_aggregate_from_grouped_backing(rng):
    """A GroupedColumns source aggregates identically to its Table —
    lazy plane extraction, no per-column materialization step."""
    from spark_rapids_jni_tpu.ops.row_mxu import table_to_grouped
    n = 400
    keys = rng.integers(0, 9, n).astype(np.int32)
    kvalid = rng.random(n) > 0.15
    vals = rng.integers(0, 100, n).astype(np.int32)
    vvalid = rng.random(n) > 0.25
    t = Table((Column.from_numpy(keys, INT32, valid=kvalid),
               Column.from_numpy(vals, INT32, valid=vvalid)))
    gc = table_to_grouped(t)
    import jax
    agg = jax.jit(lambda g: hash_aggregate_table(
        g, key_idxs=[0], measures=[(1, "sum"), (1, "count")],
        max_groups=32))
    res_g, have_g, ng_g = agg(gc)
    res_t, have_t, ng_t = hash_aggregate_table(
        t, key_idxs=[0], measures=[(1, "sum"), (1, "count")],
        max_groups=32)
    assert int(np.asarray(ng_g)) == int(np.asarray(ng_t))
    for cg, ct in zip(res_g.columns, res_t.columns):
        assert cg.to_pylist() == ct.to_pylist()


def test_aggregate_int64_measure_from_grouped_backing(rng, x64_both):
    """An int64 measure aggregates identically from the plane-major
    GroupedColumns backing and the Table — the pair column comes out of
    the planes as the same [2, n] representation the words kernels eat
    (or native int64 under x64)."""
    from spark_rapids_jni_tpu.ops.row_mxu import table_to_grouped
    n = 512
    keys = rng.integers(0, 6, n).astype(np.int32)
    vals = rng.integers(-(2 ** 50), 2 ** 50, n, dtype=np.int64)
    vv = rng.random(n) > 0.2
    t = Table((Column.from_numpy(keys, INT32),
               Column.from_numpy(vals, INT64, valid=vv)))
    gc = table_to_grouped(t)
    m = [(1, "sum"), (1, "min"), (1, "max")]
    res_g, have_g, _ = hash_aggregate_table(gc, key_idxs=[0],
                                            measures=m, max_groups=16)
    res_t, have_t, _ = hash_aggregate_table(t, key_idxs=[0],
                                            measures=m, max_groups=16)
    for cg, ct in zip(res_g.columns, res_t.columns):
        assert cg.to_pylist() == ct.to_pylist()
    # and against Python ints
    exp = {}
    for r in range(n):
        if not vv[r]:
            continue
        k, v = int(keys[r]), int(vals[r])
        s, lo, hi = exp.get(k, (0, None, None))
        exp[k] = (s + v, v if lo is None else min(lo, v),
                  v if hi is None else max(hi, v))
    hv = np.asarray(have_t)
    gk = res_t.columns[0].to_pylist()
    sm = res_t.columns[1].to_pylist()
    mn = res_t.columns[2].to_pylist()
    mx = res_t.columns[3].to_pylist()
    live = list(np.nonzero(hv)[0])
    # every key with live rows must come back, and no others (keys
    # whose every measure is null still group — count them too)
    all_keys = {int(k) for k in keys}
    assert {gk[j] for j in live} == all_keys
    for j in live:
        if gk[j] in exp:
            assert (sm[j], mn[j], mx[j]) == exp[gk[j]]
        else:                      # all-null-measure group: null outputs
            assert (sm[j], mn[j], mx[j]) == (None, None, None)


def test_aggregate_string_keys_vs_oracle(rng):
    """GROUP BY a dense-padded string column: duplicates, shared
    prefixes, nulls, empty strings, embedded NULs (length tiebreak),
    multi-byte UTF-8 — counts and sums vs a Python-dict oracle, with
    the key column rebuilt from the sorted subkeys."""
    pool = ["apple", "app", "apple\x00", "", "b", "béta", "béta!",
            "apple", "z" * 9, None]
    vals_s = [pool[i] for i in rng.integers(0, len(pool), 300)]
    col = Column.strings_padded(vals_s)
    meas = rng.integers(0, 50, 300).astype(np.int32)
    mv = rng.random(300) > 0.2
    t = Table((col, Column.from_numpy(meas, INT32, valid=mv)))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0], measures=[(None, "count"), (1, "sum")],
        max_groups=32)
    hv = np.asarray(have)
    gk = res.columns[0].to_pylist()
    cnt = res.columns[1].to_pylist()
    sm = res.columns[2].to_pylist()
    got = {gk[j]: (cnt[j], sm[j]) for j in np.nonzero(hv)[0]}

    exp = {}
    for s, m, v in zip(vals_s, meas, mv):
        c, t_ = exp.get(s, (0, None))
        exp[s] = (c + 1, (0 if t_ is None else t_) + int(m)
                  if v else t_)
    assert got == exp, (got, exp)
    assert int(np.asarray(ng)) == len(exp)


def test_aggregate_string_key_zero_width():
    """An all-empty/all-null string key column has a [n, 0] chars2d:
    grouping must not crash and still separates empty from null."""
    t = Table((Column.strings_padded([None, "", None, ""]),
               Column.from_numpy(np.arange(4, dtype=np.int32), INT32)))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0], measures=[(1, "sum")], max_groups=4)
    hv = np.asarray(have)
    got = {res.columns[0].to_pylist()[j]: res.columns[1].to_pylist()[j]
           for j in np.nonzero(hv)[0]}
    assert got == {None: 2, "": 4}    # sums of rows {0,2} and {1,3}
    assert int(np.asarray(ng)) == 2


def test_aggregate_string_key_capped_refused():
    vals = ["x" * 50, "y"]
    col = Column.strings_padded(vals, width_cap=8)
    t = Table((col, Column.from_numpy(np.array([1, 2], np.int32),
                                      INT32)))
    with pytest.raises(ValueError, match="width-capped"):
        hash_aggregate_table(t, key_idxs=[0],
                             measures=[(None, "count")], max_groups=4)


def test_string_join_vs_oracle(rng):
    """String equi-join (two-sort forward-fill, no gathers): payload
    lookup, unmatched/null probes, null build keys, prefix/NUL
    near-collisions, and different padded widths on the two sides."""
    from spark_rapids_jni_tpu.models.pipeline import (
        sort_merge_join_strings, join_semi_mask_strings)
    build_keys = ["alpha", "beta", "b", "b\x00", "", "zzz-long-key",
                  None]
    build_pay = np.array([10, 20, 30, 40, 50, 60, 70], np.int32)
    bcol = Column.strings_padded(build_keys)
    pool = build_keys[:-1] + ["missing", "alph", "alphaa", None, "bet"]
    probe_keys = [pool[i] for i in rng.integers(0, len(pool), 200)]
    pcol = Column.strings_padded(probe_keys)

    pays, matched, ambiguous = sort_merge_join_strings(
        bcol, [build_pay], pcol)
    assert not bool(ambiguous)
    got_m = np.asarray(matched)
    got_p = np.asarray(pays[0])
    lut = {k: int(v) for k, v in zip(build_keys, build_pay)
           if k is not None}
    for r, k in enumerate(probe_keys):
        want = lut.get(k) if k is not None else None
        if want is None:
            assert not got_m[r], (r, k)
        else:
            assert got_m[r] and got_p[r] == want, (r, k, got_p[r])

    semi = np.asarray(join_semi_mask_strings(bcol, pcol))
    assert (semi == got_m).all()


def test_string_join_duplicate_build_flags_ambiguous():
    from spark_rapids_jni_tpu.models.pipeline import (
        sort_merge_join_strings, join_semi_mask_strings)
    bcol = Column.strings_padded(["x", "y", "x"])
    pcol = Column.strings_padded(["x", "z"])
    pays, matched, ambiguous = sort_merge_join_strings(
        bcol, [np.array([1, 2, 3], np.int32)], pcol)
    assert bool(ambiguous)
    # a DUPLICATE NULL build key is not ambiguous (nulls never match)
    bcol2 = Column.strings_padded(["x", None, None])
    _, m2, amb2 = sort_merge_join_strings(
        bcol2, [np.array([1, 2, 3], np.int32)], pcol)
    assert not bool(amb2)
    assert list(np.asarray(m2)) == [True, False]
    # semi joins tolerate duplicates
    semi = np.asarray(join_semi_mask_strings(bcol, pcol))
    assert list(semi) == [True, False]


def test_join_null_keys_never_match(rng):
    bkeys = np.array([1, 2, 2, 3, 0], np.int32)
    bvalid = np.array([1, 1, 0, 1, 0], bool)     # one null dup of key 2
    bpay = np.array([10, 20, 21, 30, 99], np.int32)
    pkeys = np.array([2, 3, 0, 5, 2], np.int32)
    pvalid = np.array([1, 1, 0, 1, 1], bool)     # probe row 2 is null
    build = Table((Column.from_numpy(bkeys, INT32, valid=bvalid),
                   Column.from_numpy(bpay, INT32)))
    probe = Table((Column.from_numpy(pkeys, INT32, valid=pvalid),))
    pidx, pay, pay_valid, valid, total, overflow = join_inner_table(
        build, 0, 1, probe, 0, capacity=16)
    assert not bool(np.asarray(overflow))
    got = sorted(zip(np.asarray(pidx)[np.asarray(valid)].tolist(),
                     np.asarray(pay)[np.asarray(valid)].tolist()))
    # probe 0 (key 2) matches only the NON-null build row 1; probe 4 too
    assert got == [(0, 20), (1, 30), (4, 20)]
    sm = np.asarray(join_semi_mask_table(build, 0, probe, 0))
    assert sm.tolist() == [True, True, False, False, True]


def test_join_sentinel_key_with_null_build(rng):
    """A live probe key equal to int32 max must not false-match the
    null build rows parked at the sentinel."""
    big = np.iinfo(np.int32).max
    build = Table((Column.from_numpy(np.array([1, big], np.int32),
                                     valid=np.array([1, 0], bool),
                                     dtype=INT32),
                   Column.from_numpy(np.array([5, 6], np.int32), INT32)))
    probe = Table((Column.from_numpy(np.array([big, 1], np.int32),
                                     INT32),))
    sm = np.asarray(join_semi_mask_table(build, 0, probe, 0))
    assert sm.tolist() == [False, True]
    pidx, pay, pay_valid, valid, total, _ = join_inner_table(
        build, 0, 1, probe, 0, capacity=8)
    got = sorted(zip(np.asarray(pidx)[np.asarray(valid)].tolist(),
                     np.asarray(pay)[np.asarray(valid)].tolist()))
    assert got == [(1, 5)]


def test_join_int64_keys_beyond_int32(rng, x64_both):
    """int64 join keys spanning >2^31 (TPC-DS SF3000 surrogate/ticket
    keys): the dense-id composite probe must join exactly, with
    duplicate build keys, null keys on both sides, and key values whose
    low AND high words collide across distinct keys."""
    base = 3 << 32
    bkeys = np.array([base + 1, base + 2, base + 2, -(base + 2),
                      (7 << 32) + 2, 5], np.int64)
    bvalid = np.array([1, 1, 1, 1, 1, 0], bool)
    bpay = np.array([10, 20, 21, 30, 40, 99], np.int32)
    pkeys = np.array([base + 2, -(base + 2), (7 << 32) + 2, base + 1,
                      5, base + 9], np.int64)
    pvalid = np.array([1, 1, 1, 1, 0, 1], bool)
    build = Table((Column.from_numpy(bkeys, INT64, valid=bvalid),
                   Column.from_numpy(bpay, INT32)))
    probe = Table((Column.from_numpy(pkeys, INT64, valid=pvalid),))
    pidx, pay, pay_valid, valid, total, overflow = join_inner_table(
        build, 0, 1, probe, 0, capacity=16)
    assert not bool(np.asarray(overflow))
    got = sorted(zip(np.asarray(pidx)[np.asarray(valid)].tolist(),
                     np.asarray(pay)[np.asarray(valid)].tolist()))
    # probe 0 (base+2) hits both non-null dups; 1 hits the negative twin;
    # 2 hits the hi-word-differing key; 3 hits base+1; null probe 4 and
    # unmatched 5 emit nothing
    assert got == [(0, 20), (0, 21), (1, 30), (2, 40), (3, 10)]
    sm = np.asarray(join_semi_mask_table(build, 0, probe, 0))
    assert sm.tolist() == [True, True, True, True, False, False]


def test_join_int64_key_representation_mismatch():
    from spark_rapids_jni_tpu.models.pipeline import _join_keys_pair
    build = Table((Column.from_numpy(
        np.array([1], np.int64), INT64),))
    probe = Table((Column.from_numpy(np.array([1], np.int32), INT32),))
    if build.columns[0].data.ndim != 2:
        pytest.skip("x64 on: both sides 1-D, no mismatch to detect")
    with pytest.raises(ValueError, match="mismatch"):
        _join_keys_pair(build, 0, probe, 0)


def test_distributed_q72_table_step_nulls(rng, cpu_devices):
    """The Table-level q72 step: validity rides the exchange, null keys
    never join, null quantities/inventories drop at the filter; totals
    match a numpy oracle computed from the nullable inputs."""
    import jax
    from spark_rapids_jni_tpu.parallel import make_mesh, shard_table
    from spark_rapids_jni_tpu.models.pipeline import (
        distributed_q72_table_step)
    mesh = make_mesh(cpu_devices[:8])
    n = 8 * 64
    item = rng.integers(0, 10, n).astype(np.int32)
    iv = rng.random(n) > 0.15
    week = rng.integers(0, 3, n).astype(np.int32)
    wv = rng.random(n) > 0.1
    qty = rng.integers(1, 6, n).astype(np.int32)
    qv = rng.random(n) > 0.2
    bi = rng.integers(0, 12, 40).astype(np.int32)
    biv = rng.random(40) > 0.1
    binv = rng.integers(0, 5, 40).astype(np.int32)
    binvv = rng.random(40) > 0.1

    t = shard_table(Table((
        Column.from_numpy(item, INT32, valid=iv),
        Column.from_numpy(week, INT32, valid=wv),
        Column.from_numpy(qty, INT32, valid=qv))), mesh)
    build = Table((Column.from_numpy(bi, INT32, valid=biv),
                   Column.from_numpy(binv, INT32, valid=binvv)))
    step = jax.jit(distributed_q72_table_step(mesh))
    res, have, ng, ovf = step(t, build)
    assert not np.asarray(ovf).any()

    # numpy oracle over the nullable inputs
    exp = {}
    for r in range(n):
        if not (iv[r] and qv[r]):
            continue
        for b in range(40):
            if not (biv[b] and binvv[b]) or bi[b] != item[r]:
                continue
            if binv[b] < qty[r]:
                key = (int(item[r]), int(week[r]) if wv[r] else None)
                c, s = exp.get(key, (0, 0))
                exp[key] = (c + 1, s + int(qty[r]))
    hv = np.asarray(have).reshape(-1)
    gitem = res.columns[0].to_pylist()
    gweek = res.columns[1].to_pylist()
    counts = res.columns[2].to_pylist()
    sums = res.columns[3].to_pylist()
    got = {}
    for j in np.nonzero(hv)[0]:
        key = (gitem[j], gweek[j])
        c, s = got.get(key, (0, 0))
        got[key] = (c + counts[j], s + (sums[j] or 0))
    assert got == exp


def test_aggregate_int64_measures_exact(rng, x64_both):
    """SUM/MIN/MAX over int64 measure columns run exactly on device via
    the multi-word limb kernels: values crossing int32 range, negatives,
    nulls, and more rows than one 32768-row limb chunk; sums compare
    against Python-int arithmetic mod 2^64 (Spark's non-ANSI long
    overflow wraps).  Both x64 modes: no-x64 (the TPU representation)
    takes the pair path, x64 the native-int64 path."""
    n = 70_001                      # 3 limb chunks, ragged tail
    keys = rng.integers(0, 7, n).astype(np.int32)
    kv = rng.random(n) > 0.1
    vals = rng.integers(-(2 ** 62), 2 ** 62, n, dtype=np.int64)
    vv = rng.random(n) > 0.2
    t = Table((Column.from_numpy(keys, INT32, valid=kv),
               Column.from_numpy(vals, INT64, valid=vv)))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0],
        measures=[(1, "sum"), (1, "min"), (1, "max"), (1, "count")],
        max_groups=32)
    hv = np.asarray(have)
    got = {}
    gk = res.columns[0].to_pylist()
    sm = res.columns[1].to_pylist()
    mn = res.columns[2].to_pylist()
    mx = res.columns[3].to_pylist()
    ct = res.columns[4].to_pylist()
    for j in np.nonzero(hv)[0]:
        got[gk[j]] = (sm[j], mn[j], mx[j], ct[j])

    exp = {}
    for r in range(n):
        key = int(keys[r]) if kv[r] else None
        s, lo, hi, c = exp.get(key, (0, None, None, 0))
        if vv[r]:
            v = int(vals[r])
            s += v
            lo = v if lo is None else min(lo, v)
            hi = v if hi is None else max(hi, v)
            c += 1
        exp.setdefault(key, None)
        exp[key] = (s, lo, hi, c)
    for key, (s, lo, hi, c) in exp.items():
        s_wrap = ((s + (1 << 63)) % (1 << 64)) - (1 << 63)
        want = (s_wrap if c else None, lo, hi, c)
        assert got[key] == want, (key, got[key], want)


def test_aggregate_int64_avg_and_empty_groups(rng, x64_both):
    """AVG(int64) as float32 — exact for small negative sums, where a
    naive hi*2^32+lo float32 reconstruction cancels to 0.0; a group
    whose every measure is null gets null SUM/MIN/MAX/AVG but still
    COUNT(*) rows."""
    keys = np.array([1, 1, 2, 2, 3], np.int32)
    vals = np.array([10, 20, 7, -9, 999], np.int64)
    vv = np.array([True, True, True, True, False])
    t = Table((Column.from_numpy(keys, INT32),
               Column.from_numpy(vals, INT64, valid=vv)))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0],
        measures=[(1, "avg"), (1, "sum"), (None, "count")], max_groups=8)
    hv = np.asarray(have)
    gk = res.columns[0].to_pylist()
    av = res.columns[1].to_pylist()
    sm = res.columns[2].to_pylist()
    ct = res.columns[3].to_pylist()
    out = {gk[j]: (av[j], sm[j], ct[j]) for j in np.nonzero(hv)[0]}
    assert out[1] == (15.0, 30, 2)
    assert out[2] == (-1.0, -2, 2)
    assert out[3] == (None, None, 1)    # all-null measures, COUNT(*)=1


def test_aggregate_float64_measure_refused(rng, x64_both):
    """FLOAT64 pair columns must refuse the integer limb kernels
    (IEEE bit patterns do not add), not silently return NaN."""
    import jax
    from spark_rapids_jni_tpu import FLOAT64
    t = Table((Column.from_numpy(np.array([1, 1], np.int32), INT32),
               Column.from_numpy(np.array([1.5, 2.5]), FLOAT64)))
    if jax.config.jax_enable_x64:
        # native [n] float64: the scalar path sums it fine
        res, have, _ = hash_aggregate_table(
            t, key_idxs=[0], measures=[(1, "sum")], max_groups=4)
        j = int(np.nonzero(np.asarray(have))[0][0])
        assert res.columns[1].to_pylist()[j] == 4.0
    else:
        with pytest.raises(NotImplementedError):
            hash_aggregate_table(t, key_idxs=[0],
                                 measures=[(1, "sum")], max_groups=4)


def test_aggregate_decimal128_sum_minmax(rng):
    """Decimal128 measures: 4-limb SUM (mod 2^128) and lexicographic
    MIN/MAX with a signed top limb, vs Python-int arithmetic."""
    from spark_rapids_jni_tpu.ops.decimal import (
        decimal128_from_ints, decimal128_to_ints)
    n = 40_000                      # 2 limb chunks
    keys = rng.integers(0, 5, n).astype(np.int32)
    mags = [int(x) for x in rng.integers(0, 1 << 62, n)]
    shifts = rng.integers(0, 64, n)
    signs = rng.integers(0, 2, n)
    vals = [(m << int(sh)) * (1 if sg else -1)
            for m, sh, sg in zip(mags, shifts, signs)]
    vv = rng.random(n) > 0.15
    dcol = decimal128_from_ints(vals, scale=2, valid=np.asarray(vv))
    t = Table((Column.from_numpy(keys, INT32), dcol))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0],
        measures=[(1, "sum"), (1, "min"), (1, "max")], max_groups=16)
    hv = np.asarray(have)
    gk = res.columns[0].to_pylist()
    sums = decimal128_to_ints(res.columns[1])
    mins = decimal128_to_ints(res.columns[2])
    maxs = decimal128_to_ints(res.columns[3])
    sv = np.asarray(res.columns[1].valid_bools())

    exp = {}
    for r in range(n):
        key = int(keys[r])
        s, lo, hi = exp.get(key, (0, None, None))
        if vv[r]:
            v = vals[r]
            s += v
            lo = v if lo is None else min(lo, v)
            hi = v if hi is None else max(hi, v)
        exp[key] = (s, lo, hi)
    for j in np.nonzero(hv)[0]:
        key = gk[j]
        s, lo, hi = exp[key]
        if lo is None:
            assert not sv[j]
            continue
        s_wrap = ((s + (1 << 127)) % (1 << 128)) - (1 << 127)
        assert sums[j] == s_wrap, (key, sums[j], s_wrap)
        assert mins[j] == lo and maxs[j] == hi, key


def test_aggregate_decimal128_avg(rng):
    """AVG over decimal128: exact limb SUM / COUNT with HALF_UP at
    scale+4 (Spark's avg widening), vs Python Fraction arithmetic;
    all-null groups stay null."""
    from fractions import Fraction
    from spark_rapids_jni_tpu.ops.decimal import (
        decimal128_from_ints, decimal128_to_ints)
    n = 5_000
    keys = rng.integers(0, 6, n).astype(np.int32)
    vals = [int(x) for x in rng.integers(-(1 << 40), 1 << 40, n)]
    vv = rng.random(n) > 0.15
    vv[keys == 5] = False                 # one all-null group
    dcol = decimal128_from_ints(vals, scale=2, valid=np.asarray(vv))
    t = Table((Column.from_numpy(keys, INT32), dcol))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0], measures=[(1, "avg"), (None, "count")],
        max_groups=16)
    hv = np.asarray(have)
    gk = res.columns[0].to_pylist()
    assert res.columns[1].dtype.kind == "decimal128"
    assert res.columns[1].dtype.scale == 6
    avgs = decimal128_to_ints(res.columns[1])
    av = np.asarray(res.columns[1].valid_bools())
    exp = {}
    for r in range(n):
        if not vv[r]:
            continue
        s, c = exp.get(int(keys[r]), (0, 0))
        exp[int(keys[r])] = (s + vals[r], c + 1)
    for j in np.nonzero(hv)[0]:
        if gk[j] not in exp:
            assert not av[j], gk[j]       # all-null group: null AVG
            continue
        s, c = exp[gk[j]]
        # HALF_UP on the magnitude at result scale 6 (input scale 2)
        q = Fraction(abs(s) * 10_000, c)
        r_int = q.numerator // q.denominator
        if Fraction(q.numerator % q.denominator, q.denominator) \
                >= Fraction(1, 2):
            r_int += 1
        if s < 0:
            r_int = -r_int
        assert avgs[j] == r_int, (gk[j], avgs[j], r_int)
        assert av[j]


def test_distributed_q95_table_step_nulls(rng, cpu_devices):
    """The Table-level q95 step: validity rides the exchange, the semi
    join drops null order keys on both sides, null ship dates form a
    null-key group, null nets drop from SUM/MIN/MAX but still COUNT;
    totals match a numpy oracle computed from the nullable inputs."""
    import jax
    from spark_rapids_jni_tpu.parallel import make_mesh, shard_table
    from spark_rapids_jni_tpu.models.pipeline import (
        distributed_q95_table_step)
    mesh = make_mesh(cpu_devices[:8])
    n = 8 * 64
    order = rng.integers(0, 60, n).astype(np.int32)
    ov = rng.random(n) > 0.15
    date = rng.integers(0, 4, n).astype(np.int32)
    dv = rng.random(n) > 0.1
    net = rng.integers(-40, 40, n).astype(np.int32)
    nv = rng.random(n) > 0.2
    ret = rng.integers(0, 60, 48).astype(np.int32)
    rv = rng.random(48) > 0.1

    t = shard_table(Table((
        Column.from_numpy(order, INT32, valid=ov),
        Column.from_numpy(date, INT32, valid=dv),
        Column.from_numpy(net, INT32, valid=nv))), mesh)
    returned = Table((Column.from_numpy(ret, INT32, valid=rv),))
    step = jax.jit(distributed_q95_table_step(mesh))
    res, have, ng, ovf = step(t, returned)
    assert not np.asarray(ovf).any()

    # numpy oracle over the nullable inputs
    ret_set = {int(k) for k, v in zip(ret, rv) if v}
    exp = {}
    for r in range(n):
        if not ov[r] or int(order[r]) not in ret_set:
            continue
        key = int(date[r]) if dv[r] else None
        c, s, lo, hi = exp.get(key, (0, 0, None, None))
        c += 1
        if nv[r]:
            v = int(net[r])
            s += v
            lo = v if lo is None else min(lo, v)
            hi = v if hi is None else max(hi, v)
        exp[key] = (c, s, lo, hi)

    hv = np.asarray(have).reshape(-1)
    gdate = res.columns[0].to_pylist()
    counts = res.columns[1].to_pylist()
    sums = res.columns[2].to_pylist()
    mins = res.columns[3].to_pylist()
    maxs = res.columns[4].to_pylist()
    got = {}
    for j in np.nonzero(hv)[0]:
        key = gdate[j]
        c, s, lo, hi = got.get(key, (0, 0, None, None))
        c += counts[j]
        s += sums[j] or 0
        if mins[j] is not None:
            lo = mins[j] if lo is None else min(lo, mins[j])
        if maxs[j] is not None:
            hi = maxs[j] if hi is None else max(hi, maxs[j])
        got[key] = (c, s, lo, hi)
    # a group whose every net is null merges as sum 0 with the oracle's 0
    assert got == exp


def test_distributed_q6_table_step_nulls(rng, cpu_devices):
    """The Table-level q6/flagship step: exchange by sold date, join
    replicated items, integral price filter, revenue aggregate — merged
    across devices with merge_aggregate_table_partials and checked
    against a numpy oracle over the nullable inputs."""
    import jax
    from spark_rapids_jni_tpu.parallel import make_mesh, shard_table
    from spark_rapids_jni_tpu.models.pipeline import (
        distributed_q6_table_step, merge_aggregate_table_partials)
    mesh = make_mesh(cpu_devices[:8])
    n = 8 * 64
    date = rng.integers(0, 5, n).astype(np.int32)
    dv = rng.random(n) > 0.1
    item = rng.integers(0, 20, n).astype(np.int32)
    iv = rng.random(n) > 0.1
    qty = rng.integers(1, 6, n).astype(np.int32)
    qv = rng.random(n) > 0.15
    price = rng.integers(50, 500, n).astype(np.int32)
    pv = rng.random(n) > 0.15
    bi = np.arange(20, dtype=np.int32)
    bp = rng.integers(40, 400, 20).astype(np.int32)
    bpv = rng.random(20) > 0.1

    sales = shard_table(Table((
        Column.from_numpy(date, INT32, valid=dv),
        Column.from_numpy(item, INT32, valid=iv),
        Column.from_numpy(qty, INT32, valid=qv),
        Column.from_numpy(price, INT32, valid=pv))), mesh)
    items = Table((Column.from_numpy(bi, INT32,
                                     valid=np.ones(20, bool)),
                   Column.from_numpy(bp, INT32, valid=bpv)))
    step = jax.jit(distributed_q6_table_step(mesh))
    res, have, ng, ovf = step(sales, items)
    assert not np.asarray(ovf).any()
    got = merge_aggregate_table_partials([(res, have)], num_keys=1,
                                         ops=["count", "sum"])

    exp = {}
    for r in range(n):
        if not (iv[r] and pv[r] and qv[r] and bpv[item[r]]):
            continue
        if not price[r] * 10 > bp[item[r]] * 12:
            continue
        key = (int(date[r]) if dv[r] else None,)
        c, s = exp.get(key, (0, 0))
        exp[key] = (c + 1, s + int(price[r]) * int(qty[r]))
    assert {k: tuple(v) for k, v in got.items()} == exp


def test_distributed_string_groupby_via_shuffle(rng, cpu_devices):
    """GROUP BY a STRING key across the mesh: the JCUDF string shuffle
    moves whole groups to owner devices (murmur3 on the key bytes),
    each device aggregates with string keys, and the host merge
    combines result tables — totals vs a Python oracle."""
    import jax
    from spark_rapids_jni_tpu.parallel import make_mesh, shard_table
    from spark_rapids_jni_tpu.parallel.shuffle import (
        shuffle_table_sharded, decode_shuffle_result)
    from spark_rapids_jni_tpu.models.pipeline import (
        merge_aggregate_table_partials)
    mesh = make_mesh(cpu_devices[:8])
    n = 8 * 64
    pool = ["web", "store", "catalog", "übermart", "", None]
    keys = [pool[i] for i in rng.integers(0, len(pool), n)]
    vals = rng.integers(0, 50, n).astype(np.int32)
    vv = rng.random(n) > 0.2
    t = shard_table(Table((
        Column.strings_padded(keys),
        Column.from_numpy(vals, INT32, valid=vv))), mesh)

    res = shuffle_table_sharded(t, key_cols=[0], mesh=mesh)
    assert not np.asarray(res.overflow).any()
    # decode per-device receive buffers and aggregate per device
    # (group ownership is total after the exchange, so per-device
    # results merge without cross-device group splits except nulls,
    # which the None-key merge handles anyway)
    import jax.numpy as jnp
    parts = []
    num_parts = 8
    dev_mesh = make_mesh(cpu_devices[:1])
    rows = np.asarray(res.rows)
    valid = np.asarray(res.row_valid).reshape(num_parts, -1)
    per = rows.shape[0] // num_parts
    for d in range(num_parts):
        sub_res = type(res)(jnp.asarray(rows[d * per:(d + 1) * per]),
                            jnp.asarray(valid[d].reshape(-1)),
                            res.num_valid, res.overflow,
                            res.str_widths)
        sub = decode_shuffle_result(sub_res, t.dtypes, dev_mesh)
        r, have, ng = hash_aggregate_table(
            sub, key_idxs=[0], measures=[(None, "count"), (1, "sum")],
            max_groups=32, mask=jnp.asarray(valid[d].reshape(-1)))
        parts.append((r, have))
    got = merge_aggregate_table_partials(parts, num_keys=1,
                                         ops=["count", "sum"])

    exp = {}
    for k, v, mv in zip(keys, vals, vv):
        c, s = exp.get((k,), (0, None))
        exp[(k,)] = (c + 1,
                     ((0 if s is None else s) + int(v)) if mv else s)
    assert {k: tuple(v) for k, v in got.items()} == exp


def test_grouped_survives_shuffle_roundtrip(rng, cpu_devices):
    """The plane-major backing crosses a mesh shuffle: per-device lazy
    extraction feeds the row encode, rows exchange, and the receive side
    decodes straight back to planes — content preserved."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.parallel.shuffle import bucket_exchange
    from spark_rapids_jni_tpu.ops.row_mxu import (
        GroupedColumns, table_to_grouped, _planes_and_vmask)
    from spark_rapids_jni_tpu.ops.row_conversion import (
        _assemble_fixed_rows, compute_row_layout)
    from spark_rapids_jni_tpu.ops.hashing import hash_partition_ids
    mesh = make_mesh(cpu_devices[:8])
    n = 8 * 64
    keys = (1 + rng.integers(0, (1 << 20) - 1, n)).astype(np.int32)
    pay = rng.integers(-50, 50, n).astype(np.int32)
    pv = rng.random(n) > 0.2
    t = Table((Column.from_numpy(keys, INT32),
               Column.from_numpy(pay, INT32, valid=pv)))
    layout = compute_row_layout(t.dtypes)
    gc = table_to_grouped(t)
    # shard the plane-major backing itself: rows on the planes' axis 1
    pspec = NamedSharding(mesh, P(None, "data"))
    gc_sh = GroupedColumns(jax.device_put(gc.planes, pspec),
                           jax.device_put(gc.vmask, pspec), gc.layout)

    import functools

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(None, "data"), P(None, "data")),
        out_specs=(P(None, "data"), P(None, "data")),
        check_vma=False)
    def roundtrip(planes, vmask):
        local = GroupedColumns(planes, vmask, gc.layout)
        tbl = local.to_table()           # lazy extraction, fuses in-jit
        rows2d = _assemble_fixed_rows(tbl, layout)
        pids = hash_partition_ids([tbl.columns[0]], 8)
        exchange = bucket_exchange(8, capacity=128, axis_name="data")
        recv, slot_valid, _, overflow = exchange(rows2d, pids)
        # dead exchange slots are all-zero rows: their JCUDF validity
        # bits are zero, so they decode as all-null rows naturally
        planes2, vmask2 = _planes_and_vmask(recv, layout, "xla")
        return planes2, vmask2

    planes2, vmask2 = jax.jit(roundtrip)(gc_sh.planes, gc_sh.vmask)
    out = GroupedColumns(planes2, vmask2, gc.layout).to_table()
    # every (key, payload) pair survives exactly once; dead slots decode
    # as all-null rows (key None) and are dropped
    got = [(k, p) for k, p in zip(out.columns[0].to_pylist(),
                                  out.columns[1].to_pylist())
           if k is not None]
    exp = [(int(k), int(p) if v else None)
           for k, p, v in zip(keys, pay, pv)]
    assert sorted(got, key=str) == sorted(exp, key=str)


def test_aggregate_narrow_key_packed_path(rng):
    """int8/int16/bool keys ride the packed single-sort path and agree
    with the oracle, nulls included."""
    from spark_rapids_jni_tpu import INT8, INT16, BOOL8
    n = 400
    for dt, lo, hi in [(INT8, -128, 128), (INT16, -3000, 3000),
                       (BOOL8, 0, 2)]:
        keys = rng.integers(lo, hi, n).astype(dt.np_dtype)
        kvalid = rng.random(n) > 0.2
        vals = rng.integers(0, 50, n).astype(np.int32)
        t = Table((Column.from_numpy(keys, dt, valid=kvalid),
                   Column.from_numpy(vals, INT32)))
        res, have, ng = hash_aggregate_table(
            t, key_idxs=[0], measures=[(1, "sum"), (None, "count")],
            max_groups=512)
        ones = np.ones(n, bool)
        oracle = _oracle_groupby([keys], [kvalid],
                                 [(vals, ones, "sum"),
                                  (vals, ones, "count_star")])
        assert int(np.asarray(ng)) == len(oracle), dt
        hv = np.asarray(have)
        gk = res.columns[0].to_pylist()
        sums = res.columns[1].to_pylist()
        cnts = res.columns[2].to_pylist()
        got = {(None if gk[j] is None else int(gk[j]),):
               [sums[j], cnts[j]] for j in np.nonzero(hv)[0]}
        ok = {(None if k[0] is None else int(k[0]),): v
              for k, v in oracle.items()}
        assert got == ok, dt


def test_aggregate_domain_direct_matches_sort_path(rng, monkeypatch):
    """The domain-direct aggregate (narrow packed keys scatter straight
    into per-key slots) must produce slot-for-slot identical results to
    the variadic-sort path across every op, with masks, null keys and
    null measures, single and composite narrow keys."""
    from spark_rapids_jni_tpu import INT8, INT16, BOOL8
    from spark_rapids_jni_tpu.models import pipeline as pl
    n = 600
    k16 = rng.integers(-3000, 3000, n).astype(np.int16)
    k8 = rng.integers(-128, 128, n).astype(np.int8)
    kb = (rng.random(n) > 0.5)
    kv16 = rng.random(n) > 0.15
    kv8 = rng.random(n) > 0.15
    vals = rng.integers(-50, 50, n).astype(np.int32)
    fvals = rng.random(n).astype(np.float32)
    vvalid = rng.random(n) > 0.2
    import jax.numpy as jnp
    mask = jnp.asarray(rng.random(n) > 0.3)
    measures = [(2, "sum"), (2, "min"), (2, "max"), (2, "avg"),
                (2, "count"), (None, "count"), (3, "sum")]
    real_domain = pl._hash_aggregate_domain
    for key_idxs in ([0], [1], [0, 1], [0, 4], [1, 4]):
        t = Table((Column.from_numpy(k16, INT16, valid=kv16),
                   Column.from_numpy(k8, INT8, valid=kv8),
                   Column.from_numpy(vals, INT32, valid=vvalid),
                   Column.from_numpy(fvals, FLOAT32),
                   Column.from_numpy(kb.astype(np.uint8), BOOL8)))
        # widen the domain cap so even the 2^25 int16+int8 composite
        # rides the direct path, and assert it actually did
        took = []
        monkeypatch.setattr(pl, "_DOMAIN_DIRECT_MAX", 1 << 26)
        monkeypatch.setattr(
            pl, "_hash_aggregate_domain",
            lambda *a, **k: took.append(1) or real_domain(*a, **k))
        fast = hash_aggregate_table(t, key_idxs=key_idxs,
                                    measures=measures, max_groups=1024,
                                    mask=mask)
        assert took, key_idxs
        monkeypatch.setattr(pl, "_DOMAIN_DIRECT_MAX", 0)
        monkeypatch.setattr(pl, "_ADAPTIVE_AGG_ON", False)
        slow = hash_aggregate_table(t, key_idxs=key_idxs,
                                    measures=measures, max_groups=1024,
                                    mask=mask)
        monkeypatch.undo()
        assert int(np.asarray(fast[2])) == int(np.asarray(slow[2]))
        np.testing.assert_array_equal(np.asarray(fast[1]),
                                      np.asarray(slow[1]))
        for cf, cs in zip(fast[0].columns, slow[0].columns):
            np.testing.assert_array_equal(np.asarray(cf.valid_bools()),
                                          np.asarray(cs.valid_bools()))
            hv = np.asarray(fast[1])
            np.testing.assert_array_equal(np.asarray(cf.data)[hv],
                                          np.asarray(cs.data)[hv])


def test_aggregate_adaptive_int32_keys(rng, monkeypatch):
    """int32 keys that are dense BY VALUE (date-key shape) ride the
    runtime range dispatch; huge-range keys fall back to the sort at
    RUNTIME through the same cond — results identical to the
    sort-only path either way, nulls and masks included."""
    import jax.numpy as jnp
    from spark_rapids_jni_tpu.models import pipeline as pl
    n = 4000
    measures = [(1, "sum"), (1, "min"), (1, "avg"), (None, "count")]
    mask = np.asarray(rng.random(n) > 0.3)
    for tag, lo, hi in (("dense", 2_415_022, 2_488_070),
                        ("huge", -(1 << 30), 1 << 30)):
        keys = rng.integers(lo, hi, n).astype(np.int32)
        kv = rng.random(n) > 0.15
        vals = rng.integers(-50, 50, n).astype(np.int32)
        t = Table((Column.from_numpy(keys, INT32, valid=kv),
                   Column.from_numpy(vals, INT32)))
        took = []
        real = pl._hash_aggregate_adaptive
        monkeypatch.setattr(
            pl, "_hash_aggregate_adaptive",
            lambda *a, **k: took.append(1) or real(*a, **k))
        fast = hash_aggregate_table(t, key_idxs=[0], measures=measures,
                                    max_groups=8192,
                                    mask=jnp.asarray(mask))
        assert took, tag             # the adaptive dispatch engaged
        monkeypatch.setattr(pl, "_ADAPTIVE_AGG_ON", False)
        slow = hash_aggregate_table(t, key_idxs=[0], measures=measures,
                                    max_groups=8192,
                                    mask=jnp.asarray(mask))
        monkeypatch.undo()
        assert int(np.asarray(fast[2])) == int(np.asarray(slow[2])), tag
        np.testing.assert_array_equal(np.asarray(fast[1]),
                                      np.asarray(slow[1]))
        hv = np.asarray(fast[1])
        for cf, cs in zip(fast[0].columns, slow[0].columns):
            np.testing.assert_array_equal(
                np.asarray(cf.valid_bools())[hv],
                np.asarray(cs.valid_bools())[hv])
            np.testing.assert_array_equal(np.asarray(cf.data)[hv],
                                          np.asarray(cs.data)[hv])


def test_aggregate_adaptive_composite_packed_plus_plain(rng, monkeypatch):
    """Multi-key adaptive coverage: a packed int16 key (null-free,
    small values — packed range 51) combined with a nullable int32 key
    (value range ~102, +2 slots) gives radix product ~5.3k < 2^18, so
    the runtime dispatch takes the DOMAIN branch with the multi-key
    mixed-radix chain and the packed decode — results must equal the
    sort-only path slot-for-slot."""
    import jax.numpy as jnp
    from spark_rapids_jni_tpu import INT16
    from spark_rapids_jni_tpu.models import pipeline as pl
    n = 3000
    k16 = rng.integers(0, 51, n).astype(np.int16)        # packed, no nulls
    k32 = rng.integers(1000, 1102, n).astype(np.int32)
    kv32 = rng.random(n) > 0.2
    vals = rng.integers(-9, 9, n).astype(np.int32)
    mask = jnp.asarray(rng.random(n) > 0.25)
    t = Table((Column.from_numpy(k16, INT16),
               Column.from_numpy(k32, INT32, valid=kv32),
               Column.from_numpy(vals, INT32)))
    measures = [(2, "sum"), (2, "max"), (None, "count")]
    fast = hash_aggregate_table(t, key_idxs=[0, 1], measures=measures,
                                max_groups=8192, mask=mask)
    monkeypatch.setattr(pl, "_ADAPTIVE_AGG_ON", False)
    slow = hash_aggregate_table(t, key_idxs=[0, 1], measures=measures,
                                max_groups=8192, mask=mask)
    monkeypatch.undo()
    assert int(np.asarray(fast[2])) == int(np.asarray(slow[2]))
    np.testing.assert_array_equal(np.asarray(fast[1]),
                                  np.asarray(slow[1]))
    hv = np.asarray(fast[1])
    for cf, cs in zip(fast[0].columns, slow[0].columns):
        np.testing.assert_array_equal(np.asarray(cf.valid_bools())[hv],
                                      np.asarray(cs.valid_bools())[hv])
        np.testing.assert_array_equal(np.asarray(cf.data)[hv],
                                      np.asarray(cs.data)[hv])


def test_join_sentinel_interleave_with_duplicates():
    """Null build rows parked at the sentinel must order strictly AFTER
    real rows whose key IS dtype max — the gather window may only cover
    real rows."""
    big = np.iinfo(np.int32).max
    build = Table((
        Column.from_numpy(np.array([big, 7, big], np.int32), INT32,
                          valid=np.array([1, 0, 1], bool)),
        Column.from_numpy(np.array([5, 99, 6], np.int32), INT32)))
    probe = Table((Column.from_numpy(np.array([big], np.int32), INT32),))
    pidx, pay, pay_valid, valid, total, _ = join_inner_table(
        build, 0, 1, probe, 0, capacity=8)
    got = sorted(np.asarray(pay)[np.asarray(valid)].tolist())
    assert got == [5, 6], got


def test_aggregate_empty_source():
    t = Table((Column.from_numpy(np.zeros(0, np.int32), INT32),
               Column.from_numpy(np.zeros(0, np.int32), INT32)))
    res, have, ng = hash_aggregate_table(
        t, key_idxs=[0], measures=[(1, "sum"), (None, "count")],
        max_groups=8)
    assert int(np.asarray(ng)) == 0
    assert not np.asarray(have).any()


@pytest.mark.parametrize("start,end", [(0, 77), (0, 80), (8, 77), (16, 16),
                                       (3, 61), (5, 77)])
def test_slice_table_validity_bits(start, end):
    """A row slice's packed validity is the slice of the bits, packed
    from bit 0 with every bit past it zero: cut as bytes from a byte
    boundary, repacked from any other start."""
    from spark_rapids_jni_tpu.table import slice_table
    rng = np.random.default_rng(start * 100 + end)
    valid = rng.random(77) > 0.3
    t = Table((Column.from_numpy(np.arange(77, dtype=np.int32), INT32,
                                 valid=valid),))
    c = slice_table(t, start, end).columns[0]
    want = np.packbits(valid[start:end], bitorder="little")
    np.testing.assert_array_equal(np.asarray(c.validity), want)
    np.testing.assert_array_equal(np.asarray(c.data),
                                  np.arange(77)[start:end])
