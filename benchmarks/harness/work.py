"""The bytes each entry call's algorithm must move, from the shapes
alone.  A roofline share is the least time these bytes take at the
chip's peak HBM bandwidth over the device time the call took, so it
reads the same work whichever engine the program picks."""

from __future__ import annotations

from benchmarks.references import jcudf


def column_bytes(itemsizes, rows: int) -> float:
    """Column data plus packed validity (one bit per value)."""
    return rows * (sum(itemsizes) + len(itemsizes) / 8)


def row_bytes(itemsizes, rows: int) -> int:
    """JCUDF rows: the fixed-width row size times the rows."""
    return rows * jcudf.layout(itemsizes)[3]


def rows_convert_bytes(itemsizes, rows: int) -> float:
    """to_rows reads the columns and writes the rows; from_rows the
    reverse: the same bytes either way."""
    return column_bytes(itemsizes, rows) + row_bytes(itemsizes, rows)


def hash_bytes(itemsizes, rows: int, out_bytes: int) -> float:
    """A row hash reads every column and writes ``out_bytes`` a row."""
    return column_bytes(itemsizes, rows) + rows * out_bytes
