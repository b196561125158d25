"""Plain numpy JCUDF row encoder and decoder for schemas with strings.

The layout rules, as the reference states them (``RowConversion.java``
javadoc; ``compute_column_information``, ``build_string_row_offsets``
and ``copy_strings_to_rows`` in ``row_conversion.cu``):

- columns in caller order, C-struct style: a fixed-width column aligned
  to its own size; a string column an 8-byte slot, 4-byte aligned,
  holding two little-endian uint32: the offset from the row's start to
  the string's bytes, then their length;
- validity bytes right after the last slot, no alignment: bit ``c % 8``
  of byte ``c // 8`` is column ``c``, 1 = valid;
- the encoder writes the row's strings after the validity bytes, back
  to back in column order, and rounds the row up to 8 bytes with zeros;
- the batch is one byte buffer with int32 row offsets, each row
  starting at a multiple of 8.

A reader sees a row only through its start and its slots, so
:func:`decode` reads any row whose pairs point inside it, wherever its
strings lie.  A string column's values are ``bytes``; a null string is
``b""`` with its validity bit 0.

Shares no code with the program.
"""

from __future__ import annotations

import numpy as np

ROW_ALIGNMENT = 8
STRING = "string"
SLOT = 8


def kind_of(values):
    """``STRING`` for a list of ``bytes``, else the numpy itemsize."""
    return STRING if isinstance(values, list) else values.dtype.itemsize


def layout(kinds):
    """``(starts, validity_offset, validity_bytes, fixed_end)``: each
    column's first byte, the validity bytes, and the end of the fixed
    section (where an encoder writes the first string)."""
    starts, pos = [], 0
    for k in kinds:
        size, align = (SLOT, 4) if k == STRING else (k, k)
        pos = -(-pos // align) * align
        starts.append(pos)
        pos += size
    vbytes = -(-len(kinds) // 8)
    return starts, pos, vbytes, pos + vbytes


def encode(values, valid, *, drop_last_byte: bool = False):
    """Compact rows of ``k`` records as ``(blob, offsets)``: ``values``
    holds per column ``k`` native-dtype values or ``k`` ``bytes``,
    ``valid`` one bool array each.  ``drop_last_byte`` writes every
    non-empty string one byte short: the control that breaks the string
    guarantee."""
    kinds = [kind_of(v) for v in values]
    starts, voff, _, fixed_end = layout(kinds)
    k = len(valid[0])
    fixed = np.zeros((k, fixed_end), np.uint8)
    for v, s, kind in zip(values, starts, kinds):
        if kind != STRING:
            fixed[:, s:s + kind] = np.ascontiguousarray(v).view(
                np.uint8).reshape(k, kind)
    for c, ok in enumerate(valid):
        fixed[:, voff + c // 8] |= np.asarray(ok, np.uint8) << (c % 8)
    strs = [(s, v) for v, s, kind in zip(values, starts, kinds)
            if kind == STRING]
    rows, offsets = [], [0]
    for r in range(k):
        row = bytearray(fixed[r].tobytes())
        for s, v in strs:
            b = v[r][:-1] if drop_last_byte and v[r] else v[r]
            row[s:s + SLOT] = np.array([len(row), len(b)],
                                       "<u4").tobytes()
            row += b
        row += bytes(-len(row) % ROW_ALIGNMENT)
        rows.append(bytes(row))
        offsets.append(offsets[-1] + len(row))
    blob = np.frombuffer(b"".join(rows), np.uint8)
    return blob, np.asarray(offsets, np.int64)


def rows_of(blob, offsets):
    """Each row's bytes, cut from the blob at its offsets."""
    blob = bytes(np.asarray(blob, np.uint8))
    offsets = [int(o) for o in offsets]
    return [blob[a:b] for a, b in zip(offsets[:-1], offsets[1:])]


def pairs(row: bytes, kinds):
    """``(offset, length)`` of each string slot of one row."""
    starts = layout(kinds)[0]
    return [tuple(int(x) for x in np.frombuffer(row[s:s + SLOT], "<u4"))
            if len(row) >= s + SLOT else (0, 0)
            for s, k in zip(starts, kinds) if k == STRING]


def decode(blob, offsets, kinds, np_dtypes):
    """Inverse of :func:`encode`, reading each row through its offsets
    and each string through its slot's pair: ``(values, valid)`` per
    column.  ``np_dtypes`` gives each fixed-width column's dtype (None
    for a string column).  Bytes a pair points past its row read as
    missing, so a broken row decodes short instead of raising."""
    starts, voff, _, fixed_end = layout(kinds)
    rows = rows_of(blob, offsets)
    k = len(rows)
    fixed = np.zeros((k, fixed_end), np.uint8)
    for r, row in enumerate(rows):
        head = np.frombuffer(row[:fixed_end], np.uint8)
        fixed[r, :len(head)] = head
    values, valid = [], []
    for c, (kind, s, dt) in enumerate(zip(kinds, starts, np_dtypes)):
        if kind == STRING:
            col = []
            for r, row in enumerate(rows):
                off, ln = (int(x) for x in fixed[r, s:s + SLOT].copy().view(
                    "<u4"))
                col.append(row[off:off + ln])
            values.append(col)
        else:
            values.append(np.ascontiguousarray(
                fixed[:, s:s + kind]).view(np.dtype(dt)).reshape(-1))
        valid.append(((fixed[:, voff + c // 8] >> (c % 8)) & 1) == 1)
    return values, valid
