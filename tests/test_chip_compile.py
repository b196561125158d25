"""Compile the main path's kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed here compiles each program for
a chip that is described, not attached, and refuses what the chip would
refuse (VMEM over budget, unaligned slices, a program larger than HBM).
Interpret-mode tests cannot catch that class of fault.  Shapes are the
chip smoke's: 212 fixed-width columns at 1M and at one batch of 4M rows.
The hash kernels compile at a 64K-row bucket: their tile, and so their
Mosaic body, is the same at 4M, where the compile takes ten times as
long.  The fused flagship plan compiles at the SF10 item bucket and a
1M-row fact bucket: the same program as SF10's, a shorter axis.  The
topology is described inside a fixture, never at import:
only one process may load libtpu.
"""


import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from spark_rapids_jni_tpu.ops import pallas_kernels, row_mxu
from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
from spark_rapids_jni_tpu.table import STRING, Column, Table
from spark_rapids_jni_tpu.utils import FIXED_WIDTH_DTYPES, cycle_dtypes

HBM_BYTES = 16 * 1024 ** 3        # one v5e chip
DTYPES = tuple(cycle_dtypes(FIXED_WIDTH_DTYPES, 212))


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile cannot be read back without the chip:
    # keep these compiles out of the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with jax.enable_x64(False):      # the chip's representation
        yield topo.devices[0]
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, dev):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=SingleDeviceSharding(dev))


def _table(n, dev):
    cols = []
    for dt in DTYPES:
        shape = (2, n) if dt.itemsize == 8 else (n,)
        np_dt = jnp.uint32 if dt.itemsize == 8 else dt.np_dtype
        cols.append(Column(dt, _sds(shape, np_dt, dev),
                           _sds(((n + 7) // 8,), jnp.uint8, dev)))
    return Table(tuple(cols))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total} bytes do not fit one chip's HBM"
    return compiled.as_text()


@pytest.mark.parametrize("rows,size", [(1 << 20, 1 << 20),
                                       (1 << 22, 1 << 21)],
                         ids=["1M", "4M-batch"])
def test_mxu_grouped_encode(one_chip, rows, size):
    """The fused MXU encode of one <=2 GB batch from the plane-major
    backing of a 1M- or 4M-row table."""
    layout = compute_row_layout(DTYPES)
    planes, vmask = jax.eval_shape(
        lambda t: row_mxu._pack_grouped_jit(t, layout),
        _table(rows, one_chip))
    hlo = _compile(
        lambda p, v, s: row_mxu._grouped_encode_impl(
            p, v, layout, size, False, s),
        _sds(planes.shape, planes.dtype, one_chip),
        _sds(vmask.shape, vmask.dtype, one_chip),
        _sds((), jnp.int32, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("grouped", [False, True],
                         ids=["columns", "grouped"])
def test_mxu_decode(one_chip, grouped):
    """The fused decode of one 2M-row batch, to columns and to the
    grouped backing."""
    layout = compute_row_layout(DTYPES)
    fn = row_mxu._from_rows_grouped_jit if grouped \
        else row_mxu._from_rows_mxu_jit
    hlo = _compile(lambda r: fn(r, layout, "pallas"),
                   _sds((1 << 21, layout.fixed_row_size), jnp.uint8,
                        one_chip))
    assert "tpu_custom_call" in hlo


def _string_table(n, dev, width=32):
    """The strings deployment's schema: 130 fixed-width columns of the
    reference's cycle, then 25 dense-padded string columns."""
    fixed = _table(n, dev).columns[:130]
    strings = tuple(
        Column(STRING, _sds((0,), jnp.uint8, dev),
               _sds(((n + 7) // 8,), jnp.uint8, dev),
               _sds((n + 1,), jnp.int32, dev), None,
               _sds((n, width), jnp.uint8, dev)) for _ in range(25))
    return Table(fixed + strings)


@pytest.mark.parametrize("direction", ["encode", "decode"])
def test_string_window_rows(one_chip, direction):
    """The padded string window's encode of the 1M-row table and its
    decode (the fused planes kernel, and the columns built in the same
    program) at the strings deployment's widths."""
    from spark_rapids_jni_tpu.ops import row_conversion as rc
    t = _string_table(1 << 20, one_chip)
    layout = compute_row_layout(t.dtypes)
    starts, fe_pad, rs = rc.padded_variable_layout(layout, (32,) * 25)
    if direction == "encode":
        _compile(lambda tb: rc._to_rows_padded_jit(
            tb, layout, starts, fe_pad, rs, 0, None), t)
        return
    hlo = _compile(lambda r: rc._from_rows_padded_jit(
        r, layout, (32,) * 25, "pallas"),
        _sds((1 << 20, rs), jnp.uint8, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("direction", ["pack", "decode"])
def test_word_plane_row_kernels(one_chip, direction):
    """The word-plane Pallas pack and decode over the 1,008-byte row:
    their strided word access must lower (32-bit data over a 128-lane
    scratch) and their VMEM must fit the scoped limit."""
    layout = compute_row_layout(DTYPES)
    n = 1 << 16
    if direction == "pack":
        hlo = _compile(lambda t: pallas_kernels.to_rows_fixed(
            t, layout, interpret=False), _table(n, one_chip))
    else:
        hlo = _compile(lambda r: pallas_kernels.from_rows_fixed(
            r, layout, interpret=False),
            _sds((n, layout.fixed_row_size), jnp.uint8, one_chip))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("kernel", ["murmur3_cols", "xxhash64_cols"])
def test_hash_kernel(one_chip, kernel):
    """Spark murmur3 / xxhash64 over 14 columns of the table: one
    murmur3 kernel call; xxhash64 calls of at most
    ``pallas_kernels._HASH_WORDS`` word rows."""
    fn = getattr(pallas_kernels, kernel)
    hlo = _compile(lambda t: fn(t.columns[:14], 42, interpret=False),
                   _table(1 << 16, one_chip))
    assert "tpu_custom_call" in hlo


_ENTRY_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (.+?) ([a-z][a-z\-]*)\(")


def test_murmur3_reads_columns_as_stored(one_chip):
    """murmur3 over all 212 columns (every kernel run) at the 64K-row
    bucket: between the resident column buffers and the kernels lie
    only bitcasts.  A copy or fusion there would be a relayout of a
    column, the per-column word prep the kernel exists to avoid.  The
    compiler may still prefetch a small operand into another memory
    space (``copy-start``), which moves it without changing its
    layout."""
    hlo = _compile(lambda t: pallas_kernels.murmur3_cols(t.columns, 42),
                   _table(1 << 16, one_chip))
    entry = hlo[hlo.index("\nENTRY ") + 1:]
    entry = entry[:entry.index("\n}\n")]
    ops = {}
    for line in entry.splitlines()[1:]:
        m = _ENTRY_OP.match(line)
        assert m, line[:200]
        shape, op = m.groups()
        ops[op] = ops.get(op, 0) + 1
        if op == "copy-start":
            dst, src = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", shape)[:2]
            assert re.sub(r"S\(\d+\)", "", dst) == src, line[:200]
    runs = pallas_kernels._mm3_runs(tuple(
        pallas_kernels._mm3_kind(c, 0) for c in _table(8, one_chip).columns))
    assert ops.pop("custom-call") == len(runs) > 1
    assert set(ops) <= {"parameter", "bitcast", "copy-start", "copy-done",
                        "tuple", "get-tuple-element"}, ops
    assert "tpu_custom_call" in hlo


def test_get_json_scan_kernel(one_chip):
    """The get_json path automaton over the widest eligible char window:
    its carries and chars ride 32-bit lanes (a v5e has no 8-bit vector
    arithmetic) and the char block must fit the scoped VMEM."""
    from spark_rapids_jni_tpu.ops import get_json
    segs = tuple(get_json._parse_path("$.b.c[2].d"))
    hlo = _compile(lambda ch: pallas_kernels.get_json_scan(
        ch, segs, 1, interpret=False),
        _sds((1 << 16, pallas_kernels._GJO_MAX_WINDOW), jnp.uint8,
             one_chip))
    assert "tpu_custom_call" in hlo


def test_flagship_plan(one_chip):
    """The fused flagship stage (join -> filter -> project -> group-by)
    with the SF10 item bucket (131,072) and a 1,048,576-row store_sales
    bucket: the same program as SF10's 33,554,432-row bucket (the same
    sorts, gathers and segment sums, a longer axis), in about 40 s
    instead of over a minute."""
    from spark_rapids_jni_tpu.models import pipeline
    from spark_rapids_jni_tpu.runtime import plan
    n, m = 1 << 20, 1 << 17
    names = ("sold_date", "item_key", "quantity", "price",
             "build_item_key", "build_item_price")
    shapes = [(n, jnp.int32), (n, jnp.int32), (n, jnp.int32),
              (n, jnp.float32), (m, jnp.int32), (m, jnp.float32)]
    _compile(lambda *a: plan.execute(pipeline.flagship_plan(),
                                     dict(zip(names, a))),
             *[_sds((s,), d, one_chip) for s, d in shapes])
