"""A whole run, past the look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have -- half of the batch left out, and an answer altered where it is
produced."""

import jax
import jax.numpy as jnp
import pytest

import cells
from spark_rapids_jni_tpu import ops
from spark_rapids_jni_tpu.ops import hashing
from spark_rapids_jni_tpu.ops.row_conversion import RowsColumn
from spark_rapids_jni_tpu.table import slice_table

ROWS = "jcudf_fixed155.to_rows"
HASH = "jcudf_fixed212.murmur3_partition"


@pytest.fixture(autouse=True)
def _fresh_programs():
    """Faults are planted below the jit: drop every cached program."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def _wrong(workload):
    res = cells.run(cells.tiny_cell(workload))
    assert res["correct"] is False, res["checks"]
    return {k for k, c in res["checks"].items() if c["value"] > c["limit"]}


def test_rows_half_left_out(monkeypatch):
    orig = ops.convert_to_rows
    monkeypatch.setattr(ops, "convert_to_rows", lambda t: orig(
        slice_table(t, 0, t.num_rows // 2)))
    assert "rows_missing" in _wrong(ROWS)


def test_rows_byte_altered(monkeypatch):
    orig = ops.convert_to_rows

    def altered(t):
        return [RowsColumn(b.data.at[:, 0].add(jnp.uint8(1)), b.offsets,
                           b.row_size, b.str_widths) for b in orig(t)]
    monkeypatch.setattr(ops, "convert_to_rows", altered)
    assert {"row_bytes_wrong", "decoded_wrong"} <= _wrong(ROWS)


def test_hash_half_left_out(monkeypatch):
    orig = hashing.murmur3_hash

    def half(t):
        h = orig(slice_table(t, 0, t.num_rows // 2))
        return jnp.concatenate([h, jnp.zeros_like(h)])
    monkeypatch.setattr(hashing, "murmur3_hash", half)
    assert {"murmur3_wrong", "pid_wrong"} <= _wrong(HASH)


def test_hash_altered(monkeypatch):
    orig = hashing.murmur3_hash
    monkeypatch.setattr(hashing, "murmur3_hash", lambda t: orig(t) ^ 1)
    assert {"murmur3_wrong", "pid_wrong"} <= _wrong(HASH)


def test_partition_altered(monkeypatch):
    orig = hashing.pmod
    monkeypatch.setattr(hashing, "pmod", lambda h, n: (orig(h, n) + 1) % n)
    assert _wrong(HASH) == {"pid_wrong"}
