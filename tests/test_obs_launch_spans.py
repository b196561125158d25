"""Program spans on the profiler's clock.

Under a ``jax.profiler`` capture every span is an ``srj/<name>``
annotation on the host plane, and each call that hands a program to the
runtime on the hash and row paths sits in its own launch span whose
``program`` attribute names the XLA module the call runs.  On the CPU
each execution is an ``ExecuteHelper`` event on the host plane, run on
the caller's thread inside the launch, and its ops carry the module name
(``hlo_module``), which is what these tests read the captures against.
"""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import (
    BOOL8, Column, FLOAT64, INT8, INT16, INT32, INT64, Table, obs,
)
from spark_rapids_jni_tpu.obs import metrics, spans
from spark_rapids_jni_tpu.ops import (
    convert_from_rows, convert_to_rows, pallas_kernels,
)
from spark_rapids_jni_tpu.ops import row_conversion, row_mxu
from spark_rapids_jni_tpu.ops.hashing import (
    murmur3_hash, pmod, xxhash64, _murmur3_jit, _xx64_jit,
)
from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout

LAUNCHES = ("hash.prep", "hash.kernel", "hash.xla", "hash.seed",
            "hash.finish", "rows.pack", "rows.encode", "rows.offsets",
            "rows.decode")


def _table(dtypes, n, seed=0):
    rng = np.random.default_rng(seed)
    return Table(tuple(
        Column.from_numpy(rng.integers(-100, 100, n).astype(dt.np_dtype),
                          dt, valid=rng.random(n) > 0.1)
        for dt in dtypes))


def _capture(tmp_path, fn):
    """Run ``fn`` (warmed first, so nothing compiles in the capture)
    under the profiler; return the ``srj/`` spans as ``(name, start,
    end, metadata)`` and the program executions as ``(start, module)``."""
    fn()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(fn())
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    found, helpers, module_of = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                st = dict(ev.stats)
                s, d = int(ev.start_ns), int(ev.duration_ns)
                if ev.name.startswith(spans.TRACE_PREFIX):
                    found.append((ev.name[len(spans.TRACE_PREFIX):], s,
                                  s + d, st))
                elif ev.name.endswith("ExecuteHelper"):
                    helpers.append((s, st.get("run_id")))
                elif "hlo_module" in st and "run_id" in st:
                    module_of.setdefault(st["run_id"], st["hlo_module"])
    found.sort(key=lambda e: e[1])
    return found, sorted((s, module_of.get(r)) for s, r in helpers)


def _named(found, name):
    return [e for e in found if e[0] == name]


def _check_launches(found, execs, entry):
    """Inside each ``entry`` span every program execution lies in one
    launch span, each launch span holds exactly one execution, and the
    span's ``program`` is the module that ran."""
    entries = _named(found, entry)
    assert entries
    launches = [e for e in found if e[0] in LAUNCHES]
    for _, a, b, _ in entries:
        inside = [(t, m) for t, m in execs if a <= t < b]
        mine = [e for e in launches if a <= e[1] and e[2] <= b]
        assert inside and len(mine) == len(inside)
        for name, s, e, meta in mine:
            ran = [m for t, m in inside if s <= t < e]
            assert ran == [meta["program"]], (name, meta, ran)


@pytest.fixture
def pallas_on(monkeypatch):
    monkeypatch.setenv("SRJ_TPU_PALLAS", "1")


MIXED = [INT32, INT64, INT8, INT16, FLOAT64, BOOL8] * 6


def _mm3_runs(cols):
    # the kernel runs interpret-mode on the CPU
    return pallas_kernels._mm3_runs(
        tuple(pallas_kernels._mm3_kind(c, 0) for c in cols), True)


def test_murmur3_pallas_launch_spans(tmp_path, pallas_on):
    """Fixed-width columns go to the kernel as stored: a call launches
    one program per kernel run and nothing else, each ``hash.kernel``
    span naming its run and the columns it read from their own buffers
    (``native_cols``) or through a prep program (``prep_cols``)."""
    t = _table(MIXED, 1024)
    found, execs = _capture(tmp_path, lambda: murmur3_hash(t))
    _check_launches(found, execs, "murmur3_hash")
    [(_, a, b, meta)] = _named(found, "murmur3_hash")
    assert meta["rows"] == 1024
    assert all(a <= s and e <= b for n, s, e, _ in found if n in LAUNCHES)
    runs = _mm3_runs(t.columns)
    assert len(runs) > 1
    assert [n for n, *_ in found if n in LAUNCHES] == (
        ["hash.kernel"] * len(runs))
    kernels = _named(found, "hash.kernel")
    assert [(m["run"], m["native_cols"], m["prep_cols"])
            for *_, m in kernels] == [(i, e - s, 0)
                                      for i, (s, e) in enumerate(runs)]
    assert sum(m["native_cols"] for *_, m in kernels) == len(MIXED)
    assert {m["program"] for *_, m in kernels} == {"jit__mm3_run"}


def test_murmur3_string_launch_spans(tmp_path, pallas_on):
    """A dense-padded string column keeps one prep program, launched
    before the kernel run that reads it and counted in its
    ``prep_cols``."""
    t = _table([INT32, INT64], 256)
    scol = Column.strings_padded(["ab", None, "cde", ""] * 64)
    cols = (t.columns[0], scol, t.columns[1])
    found, execs = _capture(tmp_path, lambda: murmur3_hash(cols))
    _check_launches(found, execs, "murmur3_hash")
    assert [(n, m["program"]) for n, *_, m in found if n in LAUNCHES] == [
        ("hash.prep", "jit__mm3_string_prep"), ("hash.kernel", "jit__mm3_run")]
    [(*_, prep)] = _named(found, "hash.prep")
    [(*_, kernel)] = _named(found, "hash.kernel")
    assert prep["column"] == 1
    assert (kernel["native_cols"], kernel["prep_cols"]) == (2, 1)


def test_xxhash64_pallas_launch_spans(tmp_path, pallas_on):
    t = _table([INT32, INT64, INT8], 256)
    found, execs = _capture(tmp_path, lambda: xxhash64(t))
    _check_launches(found, execs, "xxhash64")
    assert len(_named(found, "hash.prep")) == 3
    [(*_, meta)] = _named(found, "hash.kernel")
    assert meta["program"] == "jit__xx64_call"
    assert [m["program"] for n, *_, m in found
            if n in ("hash.seed", "hash.finish")] == [
        "jit__xx64_seed", "jit__xx64_finish"]


def test_murmur3_xla_launch_span(tmp_path):
    t = _table(MIXED, 1024)
    found, execs = _capture(tmp_path, lambda: murmur3_hash(t))
    _check_launches(found, execs, "murmur3_hash")
    [(*_, meta)] = _named(found, "hash.xla")
    assert meta["program"] == "jit__murmur3_jit"
    assert not _named(found, "hash.prep")


def test_pmod_entry_span(tmp_path):
    h = murmur3_hash(_table([INT32], 64))
    found, _ = _capture(tmp_path, lambda: pmod(h, 7))
    [(*_, meta)] = _named(found, "pmod")
    assert meta["rows"] == 64


def test_grouped_rows_launch_spans(tmp_path):
    dtypes = [INT32, INT64, INT8, INT16]
    t = _table(dtypes, 2048)
    layout = compute_row_layout(t.dtypes)
    limit = 1024 * layout.fixed_row_size      # two batches of 1,024 rows

    def call():
        gc = row_mxu.table_to_grouped(t, layout)
        return row_conversion.convert_to_rows_grouped(gc, size_limit=limit)

    found, execs = _capture(tmp_path, call)
    [(*_, pack)] = _named(found, "rows.pack")
    assert pack["program"] == "jit__pack_grouped_jit"
    _check_launches(found, execs, "convert_to_rows_grouped")
    enc = _named(found, "rows.encode")
    assert [(m["batch"], m["rows"]) for *_, m in enc] == [(0, 1024),
                                                          (1, 1024)]
    assert {m["program"] for *_, m in enc} == {"jit__grouped_encode_impl"}
    offs = _named(found, "rows.offsets")
    assert [(m["batch"], m["program"]) for *_, m in offs] == [
        (b, p) for b in (0, 1) for p in ("jit__row_iota", "jit__row_scale")]


@pytest.mark.parametrize("impl,program", [
    ("xla", "jit__to_rows_fixed_jit"),
    ("mxu", "jit__to_rows_mxu_jit"),
    ("pallas", "jit__to_rows_planes_jit"),
])
def test_convert_to_rows_encode_span(tmp_path, impl, program):
    t = _table([INT32, INT64, INT8, INT16], 256)
    found, execs = _capture(tmp_path,
                            lambda: convert_to_rows(t, impl=impl))
    _check_launches(found, execs, "convert_to_rows")
    [(*_, meta)] = _named(found, "rows.encode")
    assert (meta["program"], meta["batch"], meta["rows"]) == (program, 0,
                                                              256)


def _module_name(jitted, *args):
    return jitted.lower(*args).compiler_ir().operation.attributes[
        "sym_name"].value


def test_program_names_match_lowerings():
    """``program`` is derived from the jitted callable; it must be the
    name of the module the callable lowers to."""
    t = _table([INT32, INT64], 256)
    col = t.columns[0]
    scol = Column.strings_padded(["ab", None, "cde"])
    layout = compute_row_layout(t.dtypes)
    gc = row_mxu.table_to_grouped(_table([INT32, INT64], 1024), layout)
    encode = row_mxu._grouped_encode_fn(jax.devices()[0])
    cases = [
        (pallas_kernels._hash_prep, (col, 0, "xx64")),
        (pallas_kernels._mm3_run, (((col.data, col.validity),), None,
                                   (pallas_kernels._mm3_kind(col, 0),),
                                   42, 256, True, True)),
        (pallas_kernels._mm3_string_prep, (scol, 4, 8192)),
        (pallas_kernels._xx64_seed, (256, np.uint32(42))),
        (pallas_kernels._xx64_finish, (jnp.zeros((2, 256), jnp.uint32),)),
        (_murmur3_jit, (t.columns, 42, 0)),
        (_xx64_jit, (t.columns, 42, 0)),
        (row_mxu._pack_grouped_jit, (t, layout)),
        (row_conversion._row_iota, (1025,)),
        (row_conversion._row_scale, (jnp.arange(1025, dtype=jnp.int32),
                                     layout.fixed_row_size)),
        (row_conversion._to_rows_fixed_jit, (t, layout, jnp.int32(0),
                                             None)),
    ]
    for fn, args in cases:
        assert _module_name(fn, *args) == spans.program_name(fn)
    assert _module_name(encode, gc.planes, gc.vmask, layout, 1024, True,
                        0) == spans.program_name(
        row_mxu._grouped_encode_impl)


def test_recorded_and_captured_span(tmp_path):
    """With obs on under a capture a span is both a ring event and an
    annotation, and attributes set late reach the annotation."""
    obs.clear()
    obs.enable()
    try:
        def call():
            with obs.span("both", rows=3) as sp:
                sp.set(batch=1)
            return jnp.zeros(1)
        found, _ = _capture(tmp_path, call)
        evs = [e for e in obs.events(kind="span") if e["name"] == "both"]
    finally:
        obs.disable()
        obs.clear()
    assert evs and evs[-1]["rows"] == 3 and evs[-1]["batch"] == 1
    [(*_, meta)] = _named(found, "both")
    assert meta == {"rows": 3, "batch": 1}


def test_recorded_hash_call_reads_memory_once(monkeypatch, pallas_on):
    """Only the entry span reads the allocator and resets its peak: the
    launch spans inside a recorded hash call read no memory."""
    reads, resets = [], []
    monkeypatch.setattr(spans, "_mem_snapshot",
                        lambda: (reads.append(1), {"bytes_in_use": 1,
                                                   "peak_bytes_in_use": 2})[1])
    monkeypatch.setattr(spans, "_reset_peak", lambda: resets.append(1))
    t = _table(MIXED, 256)
    murmur3_hash(t)
    obs.clear()
    obs.enable()
    try:
        murmur3_hash(t)
        evs = obs.events(kind="span")
    finally:
        obs.disable()
        obs.clear()
    assert len(reads) == 2 and len(resets) == 1   # entry start and end
    launches = [e for e in evs if e["name"] in LAUNCHES]
    assert len(launches) == len(_mm3_runs(t.columns))
    assert not any("mem" in e for e in launches)
    [entry] = [e for e in evs if e["name"] == "murmur3_hash"]
    assert entry["mem"]["peak_delta_bytes"] == 1


def test_no_capture_opens_no_annotation(monkeypatch):
    assert not spans.capturing()
    opened = []
    monkeypatch.setattr(spans, "_TraceAnnotation",
                        lambda *a, **k: opened.append(a))
    with spans.launch("hash.prep", pallas_kernels._hash_prep) as sp:
        sp.set(column=1)
        assert sp.fence(5) == 5
    with obs.span("x"):
        pass
    assert opened == []


def _string_table(n, fixed=(INT32, INT64, INT8), strings=2, seed=0):
    """Fixed-width columns, then dense-padded string columns of 0-12
    bytes (a 12-byte window)."""
    rng = np.random.default_rng(seed)
    t = _table(fixed, n, seed)
    scols = tuple(Column.strings_padded(
        [None if rng.random() < 0.1 else "x" * int(rng.integers(0, 13))
         for _ in range(n)], pad_to=12) for _ in range(strings))
    return Table(t.columns + scols)


def test_var_rows_encode_span(tmp_path):
    """The padded string window's encode is one launch a batch; the
    offsets are the two programs the fixed-width batches launch."""
    t = _string_table(256)
    found, execs = _capture(tmp_path, lambda: convert_to_rows(t))
    _check_launches(found, execs, "convert_to_rows")
    [(*_, meta)] = _named(found, "rows.encode")
    assert (meta["program"], meta["batch"], meta["rows"], meta["strings"],
            meta["str_width"]) == ("jit__to_rows_padded_jit", 0, 256, 2, 12)
    assert [m["program"] for *_, m in _named(found, "rows.offsets")] == [
        "jit__row_iota", "jit__row_scale"]


def _fixed_rows(impl):
    t = _table([INT32, INT64, INT8, INT16], 256)
    return convert_to_rows(t, impl=impl)[0], t.dtypes


def _padded_rows():
    t = _string_table(256)
    return convert_to_rows(t)[0], t.dtypes


@pytest.mark.parametrize("make,impl,program", [
    (lambda: _fixed_rows("xla"), "xla", "jit__from_rows_fixed_jit"),
    (lambda: _fixed_rows("mxu"), "mxu", "jit__from_rows_mxu_jit"),
    (_padded_rows, None, "jit__from_rows_padded_jit"),
], ids=["fixed_xla", "fixed_mxu", "var_padded"])
def test_convert_from_rows_decode_span(tmp_path, make, impl, program):
    """A batch on the bucket grid decodes in one launch span that runs
    the program it names, and runs nothing else."""
    rows, dtypes = make()
    found, execs = _capture(
        tmp_path, lambda: convert_from_rows(rows, dtypes, impl=impl))
    [(_, s, e, meta)] = _named(found, "rows.decode")
    assert (meta["program"], meta["batch"], meta["rows"]) == (program, 0,
                                                              256)
    assert [m for t, m in execs if s <= t < e] == [program]
    _check_launches(found, execs, "convert_from_rows")
    [(_, a, b, _)] = _named(found, "convert_from_rows")
    assert len([t for t, _ in execs if a <= t < b]) == 1


def test_compact_var_decode_spans(tmp_path):
    """Compact rows decode in three launches: the fixed sections, the
    char totals the host reads, the char gathers."""
    padded, dtypes = _padded_rows()
    rows = row_conversion.compact_rows_host(padded, dtypes)
    found, execs = _capture(tmp_path,
                            lambda: convert_from_rows(rows, dtypes))
    _check_launches(found, execs, "convert_from_rows")
    assert [(m["program"], m["rows"])
            for *_, m in _named(found, "rows.decode")] == [
        ("jit__extract_fixed_variable_jit", 256),
        ("jit__str_totals_jit", 256),
        ("jit__gather_all_strings_jit", 256)]


@pytest.mark.parametrize("n", [256, 200], ids=["on_grid", "off_grid"])
def test_padded_var_decode_programs_do_not_grow_with_columns(tmp_path, n):
    """The decode of a padded string batch launches the same few
    programs at 5 columns and at 60 -- never a program a column: on the
    bucket grid the decode alone; off it also the pad to the bucket (a
    zero scratch and the donated fill) and the cut back to the batch's
    rows."""
    counts = []
    for fixed, strings in (((INT32, INT8, INT64), 2),
                           ((INT32, INT8, INT64, INT16) * 10, 20)):
        t = _string_table(n, fixed, strings)
        [rows] = convert_to_rows(t)
        found, execs = _capture(
            tmp_path / str(strings),
            lambda: convert_from_rows(rows, t.dtypes))
        [(_, a, b, _)] = _named(found, "convert_from_rows")
        counts.append(len([t for t, _ in execs if a <= t < b]))
        assert len(_named(found, "rows.decode")) == 1
    assert counts[0] == counts[1] <= (1 if n == 256 else 5), counts


@pytest.mark.parametrize("n", [256, 200], ids=["on_grid", "off_grid"])
def test_fixed_decode_programs_do_not_grow_with_columns(tmp_path, n):
    """A fixed-width batch decodes in the same few programs at 4 columns
    and at 40: the decode, and off the bucket grid the pad and one cut
    back to the batch's rows (no eager slice a column)."""
    counts = []
    for dtypes in ((INT32, INT64, INT8, INT16),
                   (INT32, INT64, INT8, INT16) * 10):
        t = _table(dtypes, n)
        [rows] = convert_to_rows(t, impl="mxu")
        found, execs = _capture(
            tmp_path / str(len(dtypes)),
            lambda: convert_from_rows(rows, t.dtypes, impl="mxu"))
        [(_, a, b, _)] = _named(found, "convert_from_rows")
        counts.append(len([t for t, _ in execs if a <= t < b]))
        assert len(_named(found, "rows.decode")) == 1
    assert counts[0] == counts[1] <= (1 if n == 256 else 5), counts


def _string_bytes():
    vals = metrics.registry().snapshot().get(
        "srj_tpu_rows_string_bytes_total", {"values": {}})["values"]
    return {k: vals.get(f"direction={k}", 0) for k in ("encode", "decode")}


def test_string_bytes_counter():
    """Each encode and decode of a padded batch counts its string
    windows: rows x window width over the string columns."""
    t = _string_table(256)
    before = _string_bytes()
    [rows] = convert_to_rows(t)
    convert_from_rows(rows, t.dtypes)
    after = _string_bytes()
    assert after["encode"] - before["encode"] == 256 * 2 * 12
    assert after["decode"] - before["decode"] == 256 * 2 * 12


def test_decode_program_names_match_lowerings():
    t = _string_table(256)
    layout = compute_row_layout(t.dtypes)
    [rows] = convert_to_rows(t)
    starts, fe_pad, row_size = row_conversion.padded_variable_layout(
        layout, rows.str_widths)
    flayout = compute_row_layout([INT32, INT64])
    frows = jnp.zeros((256, flayout.fixed_row_size), jnp.uint8)
    cases = [
        (row_conversion._to_rows_padded_jit,
         (t, layout, starts, fe_pad, row_size, np.int32(0), None)),
        (row_conversion._from_rows_padded_jit,
         (rows.data, layout, rows.str_widths, "xla")),
        (row_conversion._from_rows_fixed_jit, (frows, flayout)),
        (row_mxu._from_rows_mxu_jit, (frows, flayout, "xla")),
    ]
    for fn, args in cases:
        assert _module_name(fn, *args) == spans.program_name(fn)
