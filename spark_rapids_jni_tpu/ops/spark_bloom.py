"""Spark wire-compatible Bloom filter (``BloomFilterImpl`` V1).

The reference lineage's ``bloom_filter`` kernels interoperate with
Spark's ``BloomFilterAggregate``/``BloomFilterMightContain``: the bloom
buffer Spark builds (or expects) is ``org.apache.spark.util.sketch.
BloomFilterImpl`` — k Murmur3_x86_32-derived bit probes over a long[]
bitset, serialized as V1 ``(int version, int numHashFunctions,
int numWords, big-endian long[] words)``.

This module is the WIRE-COMPAT boundary: byte-compatible build, probe,
merge, and (de)serialization, vectorized in numpy at the host boundary
(a bloom probe is k random bit gathers per row — the access pattern
measured ~100x slower than streaming work on TPU, which is why the
TPU-native hot path for join pruning is ``ops.membership``'s sorted
filter).  Use this when a Spark cluster hands over (or expects) real
bloom bytes; use ``membership`` inside the TPU plan.

Spark algorithm (BloomFilterImpl.putLong / mightContainLong):
  h1 = Murmur3_x86_32.hashLong(item, seed=0)
  h2 = Murmur3_x86_32.hashLong(item, seed=h1)
  for i in 1..k: bit = (h1 + i*h2); if bit < 0: bit = ~bit
                 set/test bit % numBits
"""

from __future__ import annotations

import dataclasses
import math
import struct
from typing import Optional, Sequence

import numpy as np

from spark_rapids_jni_tpu.table import Column

_VERSION_V1 = 1


def _mm3_mix_h1(h1, k1):
    k1 = (k1 * np.uint32(0xCC9E2D51)).astype(np.uint32)
    k1 = ((k1 << np.uint32(15)) | (k1 >> np.uint32(17))).astype(np.uint32)
    k1 = (k1 * np.uint32(0x1B873593)).astype(np.uint32)
    h1 = (h1 ^ k1).astype(np.uint32)
    h1 = ((h1 << np.uint32(13)) | (h1 >> np.uint32(19))).astype(np.uint32)
    return (h1 * np.uint32(5) + np.uint32(0xE6546B64)).astype(np.uint32)


def _mm3_fmix(h1, length):
    h1 = (h1 ^ np.uint32(length)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    h1 = (h1 * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h1 ^= h1 >> np.uint32(13)
    h1 = (h1 * np.uint32(0xC2B2AE35)).astype(np.uint32)
    return (h1 ^ (h1 >> np.uint32(16))).astype(np.uint32)


def _hash_long(values_u64: np.ndarray, seeds_u32: np.ndarray) -> np.ndarray:
    """Vectorized ``Murmur3_x86_32.hashLong`` (low word, then high)."""
    lo = (values_u64 & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (values_u64 >> np.uint64(32)).astype(np.uint32)
    h1 = _mm3_mix_h1(seeds_u32.astype(np.uint32), lo)
    h1 = _mm3_mix_h1(h1, hi)
    return _mm3_fmix(h1, 8)


def _bit_indexes(values_u64: np.ndarray, k: int,
                 num_bits: int) -> np.ndarray:
    """[n, k] bit positions per Spark's combined-hash scheme."""
    n = len(values_u64)
    h1 = _hash_long(values_u64, np.zeros(n, np.uint32))
    h2 = _hash_long(values_u64, h1)
    i = np.arange(1, k + 1, dtype=np.uint32)[None, :]
    combined = (h1[:, None] + i * h2[:, None]).astype(np.uint32) \
        .view(np.int32)
    combined = np.where(combined < 0, ~combined, combined)
    return combined.astype(np.int64) % num_bits


@dataclasses.dataclass
class SparkBloomFilter:
    """Spark ``BloomFilterImpl``-compatible filter state."""

    num_hash_functions: int
    words: np.ndarray          # uint64 [num_words] bitset

    @property
    def num_bits(self) -> int:
        return len(self.words) * 64

    @staticmethod
    def optimal(expected_items: int, fpp: float = 0.03
                ) -> "SparkBloomFilter":
        """Spark's sizing: optimalNumOfBits / optimalNumOfHashFunctions."""
        if not 0.0 < fpp < 1.0:
            raise ValueError(f"fpp must be in (0, 1), got {fpp}")
        n = max(1, expected_items)
        # k comes from the UN-rounded optimalNumOfBits, exactly as
        # Spark's create() computes it (rounding first would diverge
        # from Spark's k for small n, making partials unmergeable);
        # only the allocation rounds up to whole words
        num_bits = max(1, int(-n * math.log(fpp) / (math.log(2) ** 2)))
        k = max(1, round(num_bits / n * math.log(2)))
        num_words = (num_bits + 63) // 64
        return SparkBloomFilter(k, np.zeros(num_words, np.uint64))

    def put(self, col: Column) -> "SparkBloomFilter":
        """Insert a long column's non-null rows (Spark ``putLong``)."""
        vals, valid = _col_to_u64(col)
        idx = _bit_indexes(vals[valid], self.num_hash_functions,
                           self.num_bits).reshape(-1)
        np.bitwise_or.at(self.words, idx >> 6,
                         np.uint64(1) << (idx & 63).astype(np.uint64))
        return self

    def might_contain(self, col: Column) -> np.ndarray:
        """Per-row probe (Spark ``mightContainLong``); null rows False."""
        vals, valid = _col_to_u64(col)
        idx = _bit_indexes(vals, self.num_hash_functions, self.num_bits)
        bits = (self.words[idx >> 6]
                >> (idx & 63).astype(np.uint64)) & np.uint64(1)
        return np.all(bits == 1, axis=1) & valid

    def merge(self, other: "SparkBloomFilter") -> "SparkBloomFilter":
        """In-place union (Spark ``mergeInPlace``): shapes must match."""
        if (self.num_hash_functions != other.num_hash_functions
                or len(self.words) != len(other.words)):
            raise ValueError("cannot merge incompatible bloom filters")
        self.words |= other.words
        return self

    # -- Spark BloomFilterImpl stream format (V1) -------------------------

    def serialize(self) -> bytes:
        head = struct.pack(">iii", _VERSION_V1, self.num_hash_functions,
                           len(self.words))
        return head + self.words.astype(">u8").tobytes()

    @staticmethod
    def deserialize(data: bytes) -> "SparkBloomFilter":
        if len(data) < 12:
            raise ValueError(
                f"bloom buffer truncated: {len(data)} < 12 header bytes")
        version, k, num_words = struct.unpack_from(">iii", data, 0)
        if version != _VERSION_V1:
            raise ValueError(f"unsupported bloom version {version}")
        if k < 1 or num_words < 1:
            # a hostile header must fail, not yield a filter that
            # matches everything (k<=0) or misreads the buffer
            raise ValueError(
                f"invalid bloom header: numHashFunctions={k}, "
                f"numWords={num_words}")
        expect = 12 + num_words * 8
        if len(data) < expect:
            raise ValueError(
                f"bloom buffer truncated: {len(data)} < {expect} bytes")
        words = np.frombuffer(data, dtype=">u8", count=num_words,
                              offset=12).astype(np.uint64)
        return SparkBloomFilter(k, words)


_DEVICE_KINDS = frozenset(
    {"int8", "int16", "int32", "int64", "date32", "timestamp_us"})


def might_contain_device(bf: SparkBloomFilter, col: Column, *,
                         bucket="auto"):
    """Device-side per-row probe: hash fused with the bitset test so the
    uint32-viewed bitset stays VMEM-resident across a row tile
    (``SRJ_TPU_PALLAS`` selects the Pallas kernel vs one generic XLA
    program).  Long-castable integer columns only; returns bool [n]
    (null rows False), byte-identical to :meth:`SparkBloomFilter.
    might_contain`.  Filters at or above 2**31 bits (256 MiB) fall back
    to the host probe — the fused kernels index with int32."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.ops import hashing as H
    from spark_rapids_jni_tpu.ops import pallas_kernels
    from spark_rapids_jni_tpu.runtime import shapes
    from spark_rapids_jni_tpu.utils import tracing
    from spark_rapids_jni_tpu.obs import spans

    if col.dtype.kind not in _DEVICE_KINDS or col.children:
        raise ValueError(
            f"bloom device probe takes long-castable integer columns, "
            f"got {col.dtype!r}; use might_contain() for the host path")
    n = col.num_rows
    k = bf.num_hash_functions
    num_bits = bf.num_bits
    with spans.span("bloom_might_contain", rows=n,
                    bytes=n * col.dtype.itemsize) as sp:
        if num_bits >= 1 << 31:
            sp.set(impl="host")
            return jnp.asarray(bf.might_contain(col))
        impl, interp = pallas_kernels.choose("bloom_might_contain",
                                             jax.default_backend())
        pallas_kernels.stamp_impl("xla" if impl == "xla" else "pallas")
        hi, lo = H._col_u64_blocks(col)
        valid = col.valid_bools()
        f = shapes.resolve(bucket)
        b = shapes.bucket_rows(n, f) if f is not None else n
        shapes.note(n, b)
        with shapes.pad_span():
            plo = jnp.pad(lo, (0, b - n))
            phi = jnp.pad(hi, (0, b - n))
            pvalid = jnp.pad(valid, (0, b - n))
        bits32 = jnp.asarray(
            bf.words.astype("<u8", copy=False).view(np.uint32))
        sig = (str(col.dtype), k, len(bf.words))
        with tracing.op_scope("bloom_might_contain", b):
            # statics bound positionally — the jitted entries take
            # k/num_bits via static_argnums
            if impl == "pallas":
                fn = lambda b32, l, h, v: pallas_kernels.bloom_might_contain(
                    b32, l, h, v, k, num_bits, interpret=interp)
            else:
                fn = lambda b32, l, h, v: \
                    pallas_kernels.bloom_might_contain_xla(
                        b32, l, h, v, k, num_bits)
            pallas_kernels.register(
                "bloom_might_contain", sig, b, fn,
                (bits32, plo, phi, pvalid), impl=impl)
            out = fn(bits32, plo, phi, pvalid)
        with shapes.unpad_span():
            return shapes.unpad_array(out, n)


def _col_to_u64(col: Column):
    """A long-compatible column's values as uint64 bits + validity."""
    data = np.asarray(col.data)
    if data.ndim == 2:                       # no-x64 [2, n] plane pairs
        from spark_rapids_jni_tpu.table import pair_to_np64
        vals = pair_to_np64(data, np.uint64)
    elif data.dtype.itemsize == 8:
        vals = data.view(np.uint64)
    else:
        # Spark's BloomFilterAggregate casts byte/short/int to long
        vals = data.astype(np.int64).view(np.uint64)
    return vals, np.asarray(col.valid_bools())
