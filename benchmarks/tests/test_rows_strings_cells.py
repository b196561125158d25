"""The string-bearing row cell, and the row round trips, at tiny sizes:
a whole run checks correct, the control put in the program's place does
not, and the plain variable-width reference keeps to the layout rules.
The round trips are the cells' traffic with ``"decode": true``."""

import dataclasses

import numpy as np
import pytest

import cells
from benchmarks import control
from benchmarks.harness import var_work
from benchmarks.references import jcudf_var

FIXED = "jcudf_fixed155.to_rows"
VAR = "jcudf_var155.to_rows"
VAR_TINY = {"num_columns": 19, "num_fixed_columns": 14,
            "num_string_columns": 5, "num_rows": 2048}


def tiny(workload, **mix):
    cell = cells.tiny_cell(workload, **mix)
    if cell.config["name"] == "jcudf_var155":
        cell = dataclasses.replace(cell, config=dict(cell.config,
                                                     **VAR_TINY))
    return cell


CASES = [(FIXED, {"decode": True}), (VAR, {}), (VAR, {"decode": True})]
IDS = ["fixed_roundtrip", "var_to_rows", "var_roundtrip"]


@pytest.mark.parametrize("traced", [False, True], ids=["window", "traced"])
@pytest.mark.parametrize("workload,mix", CASES, ids=IDS)
def test_cell_runs_and_checks(workload, mix, traced):
    cell = tiny(workload, **mix)
    res = cells.run(cell, traced=traced)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == set(cell.mix["limits"])
    assert res["attempted"] >= 1 and res["failed"] == 0
    if traced:
        assert "host.dispatch_ms" in res["metrics"]
        assert not any(k.endswith("_roofline") for k in res["metrics"])
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("seed", [13, 2**31 + 11])
@pytest.mark.parametrize("workload,mix", CASES, ids=IDS)
def test_control_fails_where_the_program_passes(workload, mix, seed):
    cell = tiny(workload, **mix)
    limits = cell.mix["limits"]
    program, ctrl = control.readings(cell, seed, cells.devices(cell))
    assert set(program) == set(ctrl) == set(limits)
    assert all(v <= limits[k] for k, v in program.items()), program
    assert any(v > limits[k] for k, v in ctrl.items()), ctrl


def _sample(rng, k):
    values = [rng.integers(-9, 9, k).astype(np.int16),
              [bytes(rng.integers(32, 127, rng.integers(0, 33)).astype(
                  np.uint8)) for _ in range(k)],
              rng.integers(0, 2**40, k).astype(np.uint64),
              [b""] * k,
              rng.integers(-9, 9, k).astype(np.int8)]
    valid = [rng.random(k) > 0.2 for _ in values]
    for r in range(k):
        if not valid[1][r]:
            values[1][r] = b""
    return values, valid


def test_var_layout_hand_count():
    # int16 at 0, a string slot 4-aligned at 4, uint64 at 16, a second
    # slot at 24, int8 at 32; one validity byte at 33, strings from 34
    kinds = [2, jcudf_var.STRING, 8, jcudf_var.STRING, 1]
    assert jcudf_var.layout(kinds) == ([0, 4, 16, 24, 32], 33, 1, 34)
    cyc = [1, 4, 2, 8, 4, 1, 2, 1, 8]
    kinds = [cyc[i % 9] for i in range(130)] + [jcudf_var.STRING] * 25
    # 14 cycles end at 672, then int8, int32, int16, int64 end at 696;
    # 25 slots to 896, 20 validity bytes
    assert jcudf_var.layout(kinds)[1:] == (896, 20, 916)
    assert var_work.column_bytes(kinds, 1, 0) == 449 + 155 / 8 + 100


def test_var_reference_round_trips_and_writes_compact_rows():
    values, valid = _sample(np.random.default_rng(5), 64)
    kinds = [jcudf_var.kind_of(v) for v in values]
    blob, offs = jcudf_var.encode(values, valid)
    fixed_end = jcudf_var.layout(kinds)[3]
    lens = [len(a) + len(b) for a, b in zip(values[1], values[3])]
    assert np.all(np.diff(offs) == [-(-(fixed_end + n) // 8) * 8
                                    for n in lens])
    assert np.all(offs % 8 == 0)
    row = jcudf_var.rows_of(blob, offs)[0]
    assert jcudf_var.pairs(row, kinds) == [(fixed_end, len(values[1][0])),
                                           (fixed_end + len(values[1][0]),
                                            0)]
    np_dtypes = [getattr(v, "dtype", None) for v in values]
    got_values, got_valid = jcudf_var.decode(blob, offs, kinds, np_dtypes)
    for g, w in zip(got_values, values):
        assert (g == w) if isinstance(w, list) else np.array_equal(g, w)
    for g, w in zip(got_valid, valid):
        assert np.array_equal(g, w)
    assert var_work.row_bytes(kinds, lens) == int(offs[-1])


def test_var_decoder_reads_strings_wherever_the_pairs_point():
    """A row whose strings lie in another order, with slack between
    them, reads the same through its pairs."""
    values, valid = _sample(np.random.default_rng(6), 4)
    kinds = [jcudf_var.kind_of(v) for v in values]
    starts, _, _, fixed_end = jcudf_var.layout(kinds)
    blob, offs = jcudf_var.encode(values, valid)
    rows = []
    for r, row in enumerate(jcudf_var.rows_of(blob, offs)):
        row = bytearray(row[:fixed_end]) + bytes(5)
        for c in (3, 1):                       # last string first
            s = values[c][r]
            row[starts[c]:starts[c] + 8] = np.array(
                [len(row), len(s)], "<u4").tobytes()
            row += s + bytes(3)
        row += bytes(-len(row) % 8)
        rows.append(bytes(row))
    new_offs = np.cumsum([0] + [len(r) for r in rows])
    np_dtypes = [getattr(v, "dtype", None) for v in values]
    got, ok = jcudf_var.decode(np.frombuffer(b"".join(rows), np.uint8),
                               new_offs, kinds, np_dtypes)
    assert got[1] == values[1] and got[3] == values[3]
    assert all(np.array_equal(g, w) for g, w in zip(ok, valid))


def test_var_control_drops_a_byte_of_every_nonempty_string():
    values, valid = _sample(np.random.default_rng(7), 32)
    kinds = [jcudf_var.kind_of(v) for v in values]
    blob, offs = jcudf_var.encode(values, valid, drop_last_byte=True)
    np_dtypes = [getattr(v, "dtype", None) for v in values]
    got, _ = jcudf_var.decode(blob, offs, kinds, np_dtypes)
    assert got[1] == [s[:-1] for s in values[1]]
