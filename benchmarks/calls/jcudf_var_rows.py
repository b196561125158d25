"""Call kind ``jcudf_var_rows``: a stage whose batch holds string
columns exits to JCUDF variable-width rows, and, where the mix says
``"decode": true``, the next stage reads them back.

One call is ``ops.convert_to_rows(table)`` over the configuration's
whole table (the auto engine), and with ``decode`` then
``ops.convert_from_rows`` on every batch it returned.  Each entry is
charged the bytes of ``harness/var_work.py`` for the rows it was
handed.

Checked, against ``references/jcudf_var.py``: the row count; every
row's offset (monotone, 8-aligned, each row at least its fixed section
and its string bytes); and, on rows drawn from the seed in every batch
(its first and last row among them), every fixed-width slot and
validity byte against the plain encoder, every string slot's (offset,
length) pair, the plain decoder's reading of the program's rows against
the source table, and with ``decode`` the program's decoded values,
validity bits and string bytes against the source table.  Rows are read
through their offsets and pairs only, so any layout the contract allows
passes.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import string_table, tables, var_work
from benchmarks.references import jcudf_var


class JcudfVarRows:
    labels = ("var_to_rows", "var_from_rows")

    def __init__(self, cell, seed):
        cfg = cell.config
        self.mix = cell.mix
        self.decode = bool(cell.mix.get("decode", False))
        self.dtypes = string_table.dtypes(cfg)
        self.kinds = [jcudf_var.STRING if dt.is_string else dt.itemsize
                      for dt in self.dtypes]
        self.np_dtypes = [None if dt.is_string else dt.np_dtype
                          for dt in self.dtypes]
        self.fixed_end = jcudf_var.layout(self.kinds)[3]
        self.n = int(cfg["num_rows"])
        self.rows_per_call = self.n
        self.table, self.row_chars = string_table.string_table(seed, cfg)
        self.nbytes = var_work.rows_convert_bytes(self.kinds, self.row_chars)
        self._batch_bytes = {}

    def call(self, entry):
        from spark_rapids_jni_tpu import ops
        batches = entry("var_to_rows", self.nbytes, ops.convert_to_rows,
                        self.table)
        if not self.decode:
            return batches, []
        decoded, row0 = [], 0
        for b in batches:
            decoded.append(entry("var_from_rows",
                                 self.batch_bytes(row0, b.num_rows),
                                 ops.convert_from_rows, b, self.dtypes))
            row0 += b.num_rows
        return batches, decoded

    def batch_bytes(self, row0: int, rows: int) -> float:
        """What decoding rows ``[row0, row0 + rows)`` must move (worked
        out once, not in the timed calls)."""
        key = (row0, rows)
        if key not in self._batch_bytes:
            self._batch_bytes[key] = var_work.rows_convert_bytes(
                self.kinds, self.row_chars[row0:row0 + rows])
        return self._batch_bytes[key]

    def _sample(self, table, idx):
        """``(values, valid)`` of ``table`` at rows ``idx``, columns in
        order: fixed-width values as numpy, strings as ``bytes``."""
        from spark_rapids_jni_tpu.table import Table
        cols = table.columns
        fixed = [i for i, dt in enumerate(self.dtypes) if not dt.is_string]
        strs = [i for i, dt in enumerate(self.dtypes) if dt.is_string]
        got = dict(zip(fixed, tables.sample_table(
            Table(tuple(cols[i] for i in fixed)), idx)))
        got.update(zip(strs, string_table.sample_strings(
            [cols[i] for i in strs], idx)))
        return ([got[i][0] for i in range(len(cols))],
                [got[i][1] for i in range(len(cols))])

    def observe(self, out, rng):
        batches, decoded = out
        k = int(self.mix["sample_rows_per_batch"])
        rows, offs, got, src = [], [0], [], []
        offsets_wrong, row0 = 0, 0
        for i, b in enumerate(batches):
            nb = b.num_rows
            bo = np.asarray(b.offsets).astype(np.int64)
            flat = np.asarray(b.data).reshape(-1)
            least = self.fixed_end + self.row_chars[row0:row0 + nb]
            size = np.diff(bo)
            offsets_wrong += int(np.count_nonzero(
                (bo[:-1] % jcudf_var.ROW_ALIGNMENT != 0) | (size < least)))
            offsets_wrong += int(bo[0] != 0) + int(bo[-1] > flat.size)
            idx = tables.draw_rows(rng, nb, k)
            for r in idx:
                rows.append(flat[bo[r]:bo[r + 1]])
                offs.append(offs[-1] + len(rows[-1]))
            if decoded:
                got.append(self._sample(decoded[i], idx))
            src.append(self._sample(self.table, idx + row0))
            row0 += nb
        return {"total_rows": row0, "offsets_wrong": offsets_wrong,
                "rows": (np.concatenate(rows) if rows
                         else np.zeros(0, np.uint8),
                         np.asarray(offs, np.int64)),
                "decoded": _concat(got) if got else None,
                "source": _concat(src)}

    def release(self):
        self.table = None

    def compare(self, obs):
        values, valid = obs["source"]
        blob, offs = obs["rows"]
        want_blob, want_offs = jcudf_var.encode(values, valid)
        got_rows = jcudf_var.rows_of(blob, offs)
        want_rows = jcudf_var.rows_of(want_blob, want_offs)
        starts = jcudf_var.layout(self.kinds)[0]
        # the fixed section less the string slots: fixed-width values,
        # alignment padding and the validity bytes
        fixed_mask = np.ones(self.fixed_end, bool)
        for s, kind in zip(starts, self.kinds):
            if kind == jcudf_var.STRING:
                fixed_mask[s:s + jcudf_var.SLOT] = False
        fixed_bytes_wrong = pairs_wrong = 0
        strs = [c for c, kind in enumerate(self.kinds)
                if kind == jcudf_var.STRING]
        for r, (got, want) in enumerate(zip(got_rows, want_rows)):
            g = np.frombuffer(got[:self.fixed_end], np.uint8)
            w = np.frombuffer(want[:self.fixed_end], np.uint8)
            fixed_bytes_wrong += int(np.count_nonzero(
                (g != w[:len(g)]) & fixed_mask[:len(g)]))
            fixed_bytes_wrong += int(np.count_nonzero(
                fixed_mask[len(g):]))
            spans = []
            for c, (off, ln) in zip(strs, jcudf_var.pairs(got, self.kinds)):
                bad = (ln != len(values[c][r]) or off < self.fixed_end
                       or off + ln > len(got))
                pairs_wrong += int(bad)
                if ln:
                    spans.append((off, off + ln))
            spans.sort()
            pairs_wrong += sum(b0 < a1 for (_, a1), (b0, _)
                               in zip(spans[:-1], spans[1:]))
        ref = jcudf_var.decode(blob, offs, self.kinds, self.np_dtypes)
        decoded_wrong = _wrong(ref, obs["source"])
        if obs["decoded"] is not None:
            decoded_wrong += _wrong(obs["decoded"], obs["source"])
        return {"rows_missing": abs(self.n - obs["total_rows"]),
                "offsets_wrong": obs["offsets_wrong"],
                "fixed_bytes_wrong": fixed_bytes_wrong,
                "pairs_wrong": pairs_wrong,
                "decoded_wrong": decoded_wrong}

    def control(self, obs):
        """The plain encoder in the program's place, with every
        non-empty string written one byte short."""
        values, valid = obs["source"]
        rows = jcudf_var.encode(values, valid, drop_last_byte=True)
        decoded = None
        if obs["decoded"] is not None:
            decoded = jcudf_var.decode(*rows, self.kinds, self.np_dtypes)
        return {**obs, "rows": rows, "decoded": decoded}


def _concat(samples):
    """One ``(values, valid)`` over the samples of every batch."""
    values, valid = [], []
    for c in range(len(samples[0][0])):
        parts = [s[0][c] for s in samples]
        values.append(sum(parts, []) if isinstance(parts[0], list)
                      else np.concatenate(parts))
        valid.append(np.concatenate([s[1][c] for s in samples]))
    return values, valid


def _wrong(got, want):
    """Differing fixed-width value bytes, validity bits, and string
    bytes (a length difference counts each byte missing or extra)."""
    n = 0
    for gv, wv, gok, wok in zip(got[0], want[0], got[1], want[1]):
        n += int(np.count_nonzero(np.asarray(gok) != np.asarray(wok)))
        if isinstance(wv, list):
            for g, w in zip(gv, wv):
                m = min(len(g), len(w))
                n += int(np.count_nonzero(
                    np.frombuffer(g[:m], np.uint8)
                    != np.frombuffer(w[:m], np.uint8)))
                n += abs(len(g) - len(w))
        else:
            n += int(np.count_nonzero(
                np.ascontiguousarray(gv).view(np.uint8)
                != np.ascontiguousarray(wv).view(np.uint8)))
    return n


def build(cell, seed, devices):
    del devices
    return JcudfVarRows(cell, seed)
