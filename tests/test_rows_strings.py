"""String-bearing JCUDF rows against the plain variable-width reference.

The deployment is the reference's "Fixed or Variable Width" benchmark
with strings: 130 fixed-width columns cycling its nine types, then 25
string columns of 0-32 bytes, 1% nulls.  Each case runs the public entry
points (``convert_to_rows`` / ``convert_from_rows``, auto engine,
default bucketing) on a seeded table of that schema and compares with
``benchmarks/references/jcudf_var.py``, which shares no code with the
program: the program's rows read through their offsets and (offset,
length) pairs by the plain decoder, the program's decode against the
source, and the plain encoder's compact rows decoded by the program.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import jcudf_var
from spark_rapids_jni_tpu import (
    BOOL8, Column, INT8, INT16, INT32, INT64, STRING, Table, UINT8, UINT16,
    UINT64,
)
from spark_rapids_jni_tpu.ops import convert_from_rows, convert_to_rows
from spark_rapids_jni_tpu.ops.row_conversion import RowsColumn

CYCLE = [INT8, INT32, INT16, INT64, INT32, BOOL8, UINT16, UINT8, UINT64]
DTYPES = [CYCLE[i % 9] for i in range(130)] + [STRING] * 25
KINDS = [jcudf_var.STRING if dt.is_string else dt.itemsize for dt in DTYPES]
NP_DTYPES = [None if dt.is_string else dt.np_dtype for dt in DTYPES]
WIDTH = 32


def _strings(rng, n, nulls, lens):
    valid = rng.random(n) >= nulls
    lens = np.where(valid, lens, 0)
    return [bytes(rng.integers(32, 127, ln).astype(np.uint8))
            for ln in lens], valid


def source(variant, n=2048, seed=0):
    """``(values, valid)`` per column: fixed-width values as numpy,
    strings as ``bytes`` (a null string empty)."""
    rng = np.random.default_rng(seed)
    values, valid = [], []
    for c, dt in enumerate(DTYPES):
        if dt.is_string:
            lens = rng.integers(0, WIDTH + 1, n)
            nulls = 0.01
            if variant == "all_null_column" and c == len(DTYPES) - 3:
                nulls = 1.0
            elif variant == "empty_strings":
                lens = np.zeros(n, np.int64)
            elif variant == "full_strings":
                lens = np.full(n, WIDTH)
            v, ok = _strings(rng, n, nulls, lens)
        else:
            if dt == BOOL8:
                v = rng.integers(0, 2, n).astype(dt.np_dtype)
            else:
                v = rng.integers(0, 256, n * dt.itemsize,
                                 dtype=np.uint8).view(dt.np_dtype)
            ok = rng.random(n) >= 0.01
        values.append(v)
        valid.append(ok)
    return values, valid


def to_table(values, valid) -> Table:
    """The program's table: fixed-width columns from numpy, strings in
    the dense device form (a ``[n, 32]`` window, zero past each
    length)."""
    cols = []
    for dt, v, ok in zip(DTYPES, values, valid):
        if not dt.is_string:
            cols.append(Column.from_numpy(v, dt, valid=ok))
            continue
        n = len(v)
        lens = np.array([len(s) for s in v], np.int32)
        mat = np.zeros((n, WIDTH), np.uint8)
        for r, s in enumerate(v):
            mat[r, :len(s)] = np.frombuffer(s, np.uint8)
        offsets = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offsets[1:])
        cols.append(Column(STRING, jnp.zeros((0,), jnp.uint8),
                           jnp.asarray(np.packbits(ok, bitorder="little")),
                           jnp.asarray(offsets), None, jnp.asarray(mat)))
    return Table(tuple(cols))


def read_table(table: Table):
    """``(values, valid)`` of a program table on the host."""
    values, valid = [], []
    for c in table.columns:
        ok = np.asarray(c.valid_bools())
        if c.dtype.is_string:
            # the bytes as stored, null slots included
            offs = np.asarray(c.offsets).astype(np.int64)
            if c.chars2d is not None:
                mat = np.asarray(c.chars2d)
                values.append([mat[r, :ln].tobytes()
                               for r, ln in enumerate(np.diff(offs))])
            else:
                chars = np.asarray(c.chars)
                values.append([chars[a:e].tobytes()
                               for a, e in zip(offs[:-1], offs[1:])])
        else:
            d = np.asarray(c.data)
            if d.ndim == 2:          # [2, n] plane pairs
                d = np.ascontiguousarray(d.T).view(c.dtype.np_dtype)[:, 0]
            values.append(d.astype(c.dtype.np_dtype))
        valid.append(ok)
    return values, valid


def assert_same(got, want):
    for c, (gv, wv, gok, wok) in enumerate(zip(got[0], want[0], got[1],
                                               want[1])):
        np.testing.assert_array_equal(gok, wok, err_msg=f"column {c} valid")
        if isinstance(wv, list):
            assert gv == wv, f"column {c} strings"
        else:
            np.testing.assert_array_equal(
                np.asarray(gv).view(np.uint8), np.asarray(wv).view(np.uint8),
                err_msg=f"column {c} values")


def program_rows(table):
    """The program's batches as one compact-readable ``(blob, offsets)``:
    every row cut from its batch at its offsets."""
    rows = []
    for b in convert_to_rows(table):
        flat = np.asarray(b.data).reshape(-1)
        offs = np.asarray(b.offsets).astype(np.int64)
        assert offs[0] == 0 and np.all(offs % 8 == 0)
        assert np.all(np.diff(offs) > 0) and offs[-1] <= flat.size
        rows += [flat[a:e] for a, e in zip(offs[:-1], offs[1:])]
    offsets = np.cumsum([0] + [len(r) for r in rows])
    return np.concatenate(rows), offsets


def check_encode(values, valid):
    """The program's rows, read by the plain decoder, give the source;
    every string's pair lies inside its row past the fixed section, no
    two strings of a row overlapping."""
    blob, offs = program_rows(to_table(values, valid))
    assert len(offs) - 1 == len(valid[0])
    assert_same(jcudf_var.decode(blob, offs, KINDS, NP_DTYPES),
                (values, valid))
    fixed_end = jcudf_var.layout(KINDS)[3]
    for row in jcudf_var.rows_of(blob, offs):
        spans = sorted((o, o + n) for o, n in jcudf_var.pairs(row, KINDS)
                       if n)
        assert all(o >= fixed_end and e <= len(row) for o, e in spans)
        assert all(b0 >= a1 for (_, a1), (b0, _) in zip(spans, spans[1:]))


def check_decode(values, valid):
    """The program's own rows decode to the source."""
    parts = [read_table(convert_from_rows(b, DTYPES))
             for b in convert_to_rows(to_table(values, valid))]
    got_values = [sum(col, []) if isinstance(col[0], list)
                  else np.concatenate(col)
                  for col in zip(*(p[0] for p in parts))]
    got_valid = [np.concatenate(col) for col in zip(*(p[1] for p in parts))]
    assert_same((got_values, got_valid), (values, valid))


def check_reference_rows(values, valid):
    """The plain encoder's compact rows decode through the program."""
    blob, offs = jcudf_var.encode(values, valid)
    rows = RowsColumn(jnp.asarray(blob), jnp.asarray(offs.astype(np.int32)))
    assert_same(read_table(convert_from_rows(rows, DTYPES)), (values, valid))


CASES = {
    "encode_read_by_reference": ("random", 2048, [check_encode]),
    "decode_against_source": ("random", 2048, [check_decode]),
    "reference_rows_decoded": ("random", 2048, [check_reference_rows]),
    "all_null_string_column": ("all_null_column", 2048,
                               [check_encode, check_decode,
                                check_reference_rows]),
    "empty_strings": ("empty_strings", 2048,
                      [check_encode, check_decode, check_reference_rows]),
    "strings_32_bytes": ("full_strings", 2048,
                         [check_encode, check_decode, check_reference_rows]),
    "rows_off_the_bucket_grid": ("random", 2000,
                                 [check_encode, check_decode,
                                  check_reference_rows]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_string_rows_match_the_plain_reference(case):
    variant, n, checks = CASES[case]
    values, valid = source(variant, n, seed=len(case))
    for check in checks:
        check(values, valid)
