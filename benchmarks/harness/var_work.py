"""The bytes a variable-width JCUDF conversion must move, from the
shapes and the strings' lengths.  The rows are charged at their compact
size, the least the JCUDF contract allows (each row's fixed section and
its string bytes, rounded up to 8), so a program that writes wider rows
reads a lower share of its roofline."""

from __future__ import annotations

import numpy as np

from benchmarks.references import jcudf_var


def column_bytes(kinds, rows: int, chars: int) -> float:
    """Fixed-width data, one validity bit a value, an int32 offset a
    string value, and the strings' bytes."""
    fixed = sum(k for k in kinds if k != jcudf_var.STRING)
    nstr = sum(k == jcudf_var.STRING for k in kinds)
    return rows * (fixed + len(kinds) / 8 + 4 * nstr) + chars


def row_bytes(kinds, row_chars) -> int:
    """Compact rows: each row's fixed section and string bytes, rounded
    up to 8."""
    row_chars = np.asarray(row_chars, np.int64)
    return int(np.sum(-(-(jcudf_var.layout(kinds)[3] + row_chars) // 8) * 8))


def rows_convert_bytes(kinds, row_chars) -> float:
    """to_rows reads the columns and writes the rows; from_rows the
    reverse: the same bytes either way.  ``row_chars`` holds each row's
    string bytes."""
    row_chars = np.asarray(row_chars, np.int64)
    return (column_bytes(kinds, len(row_chars), int(row_chars.sum()))
            + row_bytes(kinds, row_chars))
