"""Call kind ``jcudf_rows``: a stage exits to JCUDF rows, and, where
the mix says ``"decode": true``, the next stage reads them back.

One call is ``ops.convert_to_rows(table)`` over the configuration's
whole table (the auto engine), and with ``decode`` then
``ops.convert_from_rows`` on every batch it returned.  Each entry is
charged for the rows it was handed.

Checked: every batch's offsets, the total row count, and, on rows drawn
from the seed in every batch (its first and last row among them), every
byte of the row against the plain encoder, the plain decoder's reading
of the program's rows against the source table, and with ``decode`` the
program's decoded values and validity bits against the source table.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import datagen, tables, work
from benchmarks.references import jcudf


class JcudfRows:
    labels = ("to_rows", "from_rows")

    def __init__(self, cell, seed):
        cfg = cell.config
        self.mix = cell.mix
        self.decode = bool(cell.mix.get("decode", False))
        self.dtypes = datagen.fixed_width_dtypes(cfg)
        self.itemsizes = [dt.itemsize for dt in self.dtypes]
        self.n = int(cfg["num_rows"])
        self.rows_per_call = self.n
        self.row_size = jcudf.layout(self.itemsizes)[3]
        self.table = datagen.fixed_width_table(
            seed, self.dtypes, self.n, cfg["null_probability"])

    def call(self, entry):
        from spark_rapids_jni_tpu import ops
        batches = entry("to_rows",
                        work.rows_convert_bytes(self.itemsizes, self.n),
                        ops.convert_to_rows, self.table)
        if not self.decode:
            return batches, []
        decoded = [entry("from_rows",
                         work.rows_convert_bytes(self.itemsizes, b.num_rows),
                         ops.convert_from_rows, b, self.dtypes)
                   for b in batches]
        return batches, decoded

    def observe(self, out, rng):
        batches, decoded = out
        k = int(self.mix["sample_rows_per_batch"])
        rows, got, src, offsets_wrong, row0 = [], [], [], 0, 0
        for i, b in enumerate(batches):
            nb = b.num_rows
            want_off = np.arange(nb + 1, dtype=np.int64) * self.row_size
            offsets_wrong += int(np.count_nonzero(
                np.asarray(b.offsets).astype(np.int64) - want_off))
            idx = tables.draw_rows(rng, nb, k)
            rows.append(tables.sample_rows(b.data, idx, self.row_size))
            if decoded:
                got.append(tables.sample_table(decoded[i], idx))
            src.append(tables.sample_table(self.table, idx + row0))
            row0 += nb
        return {"total_rows": row0, "offsets_wrong": offsets_wrong,
                "rows": np.concatenate(rows),
                "decoded": _concat(got) if got else None,
                "source": _concat(src)}

    def release(self):
        self.table = None

    def compare(self, obs):
        values = [v for v, _ in obs["source"]]
        valid = [ok for _, ok in obs["source"]]
        want_rows = jcudf.encode(values, valid)
        ref_vals, ref_valid = jcudf.decode(obs["rows"],
                                           [v.dtype for v in values])
        decoded_wrong = 0
        for (gv, gok), v, ok in zip(obs["decoded"] or [], values, valid):
            decoded_wrong += int(np.count_nonzero(
                gv.view(np.uint8).reshape(len(gv), -1)
                != v.view(np.uint8).reshape(len(v), -1)))
            decoded_wrong += int(np.count_nonzero(gok != ok))
        # the program's rows, read back by the plain decoder, must also
        # give the source values: a second witness for the bytes
        for rv, rok, v, ok in zip(ref_vals, ref_valid, values, valid):
            decoded_wrong += int(np.count_nonzero(
                rv.view(np.uint8) != v.view(np.uint8)))
            decoded_wrong += int(np.count_nonzero(rok != ok))
        return {"rows_missing": abs(self.n - obs["total_rows"]),
                "offsets_wrong": obs["offsets_wrong"],
                "row_bytes_wrong": int(np.count_nonzero(
                    obs["rows"] != want_rows)),
                "decoded_wrong": decoded_wrong}

    def control(self, obs):
        """The plain encoder in the program's place, with every validity
        bit written as 1."""
        values = [v for v, _ in obs["source"]]
        rows = jcudf.encode(values, [ok for _, ok in obs["source"]],
                            keep_nulls=False)
        decoded = None
        if obs["decoded"] is not None:
            decoded = list(zip(*jcudf.decode(rows, [v.dtype for v in values])))
        return {**obs, "rows": rows, "decoded": decoded}


def _concat(samples):
    return [(np.concatenate([s[c][0] for s in samples]),
             np.concatenate([s[c][1] for s in samples]))
            for c in range(len(samples[0]))]


def build(cell, seed, devices):
    del devices
    return JcudfRows(cell, seed)
