"""Plain Spark ``hash()`` (murmur3_x86_32, seed 42) over rows of
fixed-width columns, and ``pmod``.

Semantics, as Spark's ``Murmur3Hash`` states them: a null field leaves
the running hash as it is; bool, byte, short and int hash as one 4-byte
int (``hashInt``, sign-extended), long as 8 bytes (``hashLong``); float
and double hash their bit patterns with -0.0 folded onto 0.0 and every
NaN onto the canonical NaN.  Spark has no unsigned types: uint8 and
uint16 hash zero-extended to a 4-byte int and uint64 as a long, as
cudf's Spark murmur3 hashes them.

The scalar functions are the chip smoke's references, one row at a
time; the vectorized ones compute the same over numpy arrays and are
what a run uses.  Shares no code with the program.
"""

from __future__ import annotations

import numpy as np

SEED = 42
M32, M64 = 0xFFFFFFFF, 0xFFFFFFFFFFFFFFFF
_C1, _C2 = 0xCC9E2D51, 0x1B873593


# -- scalar, one row at a time ----------------------------------------------

def _rotl(x, r, bits):
    return ((x << r) | (x >> (bits - r))) & ((1 << bits) - 1)


def _mm3_mix(h, k):
    k = _rotl((k * _C1) & M32, 15, 32) * _C2 & M32
    h = _rotl(h ^ k, 13, 32)
    return (h * 5 + 0xE6546B64) & M32


def _mm3_fmix(h, length):
    h ^= length
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & M32
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & M32
    return h ^ (h >> 16)


def _spark_bits(v, np_dtype):
    if np_dtype.kind == "f":
        v = np_dtype.type(0.0) if v == 0 else v
        if np.isnan(v):
            v = np_dtype.type(np.nan)
        ui = np.dtype(f"u{np_dtype.itemsize}")
        return int(np.array(v, np_dtype).view(ui)), np_dtype.itemsize
    if np_dtype.itemsize == 8:
        return int(v) & M64, 8
    return int(v) & M32, 4


def scalar_murmur3(sample):
    """murmur3 int32[k] of ``sample``, a list of ``(values, valid)`` per
    column, one row at a time."""
    k = len(sample[0][0])
    mm = [SEED] * k
    for vals, valid in sample:
        dt = vals.dtype
        for r in range(k):
            if not valid[r]:
                continue
            u, width = _spark_bits(vals[r], dt)
            if width == 4:
                mm[r] = _mm3_fmix(_mm3_mix(mm[r], u), 4)
            else:
                h = _mm3_mix(_mm3_mix(mm[r], u & M32), u >> 32)
                mm[r] = _mm3_fmix(h, 8)
    return np.array(mm, np.uint32).view(np.int32)


# -- vectorized over rows ----------------------------------------------------

def _rotl32(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _v_mm3_mix(h, k):
    k = _rotl32(k * np.uint32(_C1), 15) * np.uint32(_C2)
    h = _rotl32(h ^ k, 13)
    return h * np.uint32(5) + np.uint32(0xE6546B64)


def _v_mm3_fmix(h, length):
    h = h ^ np.uint32(length)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _v_bits(vals):
    """Spark's hash input: uint32 for 4-byte-or-narrower values, uint64
    for 8-byte ones."""
    if vals.dtype.kind == "f":
        vals = np.where(vals == 0, vals.dtype.type(0), vals)
        vals = np.where(np.isnan(vals), vals.dtype.type(np.nan), vals)
        return vals.view(np.dtype(f"u{vals.dtype.itemsize}"))
    if vals.dtype.itemsize == 8:
        return vals.view(np.uint64)
    return vals.astype(np.int32).view(np.uint32)


def murmur3(sample, *, keep_nulls: bool = True):
    """Vectorized :func:`scalar_murmur3`.  ``keep_nulls=False`` hashes
    null fields as if they were valid: the control that breaks the null
    guarantee."""
    k = len(sample[0][0])
    mm = np.full(k, SEED, np.uint32)
    with np.errstate(over="ignore"):
        for vals, valid in sample:
            u = _v_bits(np.asarray(vals))
            ok = np.asarray(valid) if keep_nulls else np.ones(k, bool)
            if u.dtype == np.uint32:
                m = _v_mm3_fmix(_v_mm3_mix(mm, u), 4)
            else:
                lo = (u & np.uint64(M32)).astype(np.uint32)
                hi = (u >> np.uint64(32)).astype(np.uint32)
                m = _v_mm3_fmix(_v_mm3_mix(_v_mm3_mix(mm, lo), hi), 8)
            mm = np.where(ok, m, mm)
    return mm.view(np.int32)


def pmod(h, divisor: int):
    """Spark's positive modulus (``HashPartitioning``)."""
    return np.mod(h.astype(np.int64), divisor).astype(np.int32)
