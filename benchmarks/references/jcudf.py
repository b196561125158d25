"""Plain numpy JCUDF row encoder and decoder for fixed-width schemas.

The layout rules, as the reference states them (``RowConversion.java``
javadoc, ``compute_column_information`` in ``row_conversion.cu``):

- columns in caller order, C-struct style: each aligned to its own size;
- validity bytes right after the last column, no alignment: bit
  ``c % 8`` of byte ``c // 8`` is column ``c``, 1 = valid, unused bits 0;
- the row is rounded up to 8 bytes; padding bytes are 0;
- a null slot's data bytes are the column's bytes as they are.

Shares no code with the program.
"""

from __future__ import annotations

import numpy as np

ROW_ALIGNMENT = 8


def layout(itemsizes):
    """``(starts, validity_offset, validity_bytes, row_size)``."""
    starts, pos = [], 0
    for size in itemsizes:
        pos = -(-pos // size) * size
        starts.append(pos)
        pos += size
    vbytes = -(-len(itemsizes) // 8)
    row_size = -(-(pos + vbytes) // ROW_ALIGNMENT) * ROW_ALIGNMENT
    return starts, pos, vbytes, row_size


def encode(values, valid, *, keep_nulls: bool = True):
    """Rows of ``k`` records: ``values`` is one numpy array of ``k``
    native-dtype values per column, ``valid`` one bool array each.
    ``keep_nulls=False`` writes every validity bit as 1: the control
    that breaks the null guarantee."""
    sizes = [v.dtype.itemsize for v in values]
    starts, voff, vbytes, rs = layout(sizes)
    k = len(values[0])
    rows = np.zeros((k, rs), np.uint8)
    for v, s, size in zip(values, starts, sizes):
        rows[:, s:s + size] = np.ascontiguousarray(v).view(
            np.uint8).reshape(k, size)
    for c, ok in enumerate(valid):
        bit = np.ones(k, bool) if not keep_nulls else ok
        rows[:, voff + c // 8] |= bit.astype(np.uint8) << (c % 8)
    return rows


def decode(rows, np_dtypes):
    """Inverse of :func:`encode`: ``(values, valid)`` per column."""
    np_dtypes = [np.dtype(d) for d in np_dtypes]
    starts, voff, _, rs = layout([d.itemsize for d in np_dtypes])
    if rows.shape[1] != rs:
        raise ValueError(f"rows are {rows.shape[1]} bytes, layout {rs}")
    values, valid = [], []
    for c, (d, s) in enumerate(zip(np_dtypes, starts)):
        values.append(np.ascontiguousarray(
            rows[:, s:s + d.itemsize]).view(d).reshape(-1))
        valid.append(((rows[:, voff + c // 8] >> (c % 8)) & 1) == 1)
    return values, valid
