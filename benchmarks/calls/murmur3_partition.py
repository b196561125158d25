"""Call kind ``murmur3_partition``: Spark's hash partitioning over a
resident table.

One call is ``murmur3_hash(table)`` and then ``pmod`` of it by the mix's
partition count (Spark's ``HashPartitioning``), over the configuration's
whole table.  Row conversion is not on this path.

Checked: the output lengths, and on rows drawn from the seed the hash
and the partition id against the plain Spark hash.
"""

from __future__ import annotations

import numpy as np

from benchmarks.harness import datagen, tables, work
from benchmarks.references import spark_hash


class Murmur3Partition:
    labels = ("murmur3", "pmod")

    def __init__(self, cell, seed):
        cfg = cell.config
        self.mix = cell.mix
        self.parts = int(cell.mix["num_partitions"])
        self.dtypes = datagen.fixed_width_dtypes(cfg)
        self.itemsizes = [dt.itemsize for dt in self.dtypes]
        self.n = int(cfg["num_rows"])
        self.rows_per_call = self.n
        self.table = datagen.fixed_width_table(
            seed, self.dtypes, self.n, cfg["null_probability"])

    def call(self, entry):
        from spark_rapids_jni_tpu.ops import hashing
        h32 = entry("murmur3", work.hash_bytes(self.itemsizes, self.n, 4),
                    hashing.murmur3_hash, self.table)
        pid = entry("pmod", None, hashing.pmod, h32, self.parts)
        return h32, pid

    def observe(self, out, rng):
        h32, pid = out
        idx = tables.draw_rows(rng, self.n, int(self.mix["sample_rows"]))
        return {"lengths": (h32.shape[0], pid.shape[0]),
                "murmur3": np.asarray(h32)[idx].astype(np.int32),
                "pid": np.asarray(pid)[idx].astype(np.int32),
                "source": tables.sample_table(self.table, idx)}

    def release(self):
        self.table = None

    def compare(self, obs):
        want = spark_hash.murmur3(obs["source"])
        return {"rows_missing": sum(abs(self.n - k) for k in obs["lengths"]),
                "murmur3_wrong": int(np.count_nonzero(obs["murmur3"] != want)),
                "pid_wrong": int(np.count_nonzero(
                    obs["pid"] != spark_hash.pmod(want, self.parts)))}

    def control(self, obs):
        """The plain hash in the program's place, hashing null fields
        as if they were valid."""
        h32 = spark_hash.murmur3(obs["source"], keep_nulls=False)
        return {**obs, "murmur3": h32, "pid": spark_hash.pmod(h32, self.parts)}


def build(cell, seed, devices):
    del devices
    return Murmur3Partition(cell, seed)
