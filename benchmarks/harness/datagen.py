"""Seeded input generation on the device, kept with the benchmark so
that no change to the program can change the data a cell measures.

The fixed-width table follows the program's ``utils/datagen.py`` as it
stood when the benchmark was written (per-dtype groups generated in one
vector op, raw random bits over each integer range, floats in [-1, 1),
64-bit floats as two words with the top exponent bit cleared so no
value is inf or NaN, a 1% null mask packed LSB-first).  Each table is
one jitted call; the seed enters as data, so every seed runs the same
compiled program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words drawn from ``seed``, whatever its size."""
    return np.random.SeedSequence(int(seed)).generate_state(2).astype(
        np.uint32)


def _key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def pack_bools_2d(valid):
    """bool[m, n] -> uint8[m, ceil(n/8)] LSB-first bitmasks, by 8
    strided lane slices (a reshape to [m, n/8, 8] pads 16x on TPU)."""
    m, n = valid.shape
    pad = -n % 8
    v = valid.astype(jnp.uint8)
    if pad:
        v = jnp.concatenate([v, jnp.zeros((m, pad), jnp.uint8)], axis=1)
    out = v[:, 0::8]
    for j in range(1, 8):
        out = out | (v[:, j::8] << j)
    return out


def _gen_fixed(key, kind: str, itemsize: int, shape):
    """Uniform values of one dtype: ``shape`` is ``(g, n)``; 64-bit
    dtypes (signed or not) come as ``[g, 2, n]`` uint32 word pairs (low
    word first)."""
    g, n = shape
    if kind == "float64":
        bits = jax.random.bits(key, (g, n, 2), dtype=jnp.uint32)
        hi = bits[..., 1] & jnp.uint32(0xBFEFFFFF)
        return jnp.stack([bits[..., 0], hi], axis=-2)
    if kind == "float32":
        return jax.random.uniform(key, shape, dtype=jnp.float32,
                                  minval=-1.0, maxval=1.0)
    if kind == "bool8":
        return jax.random.bernoulli(key, 0.5, shape).astype(jnp.uint8)
    if itemsize == 8:
        return jax.random.bits(key, (g, 2, n), dtype=jnp.uint32)
    bits = jax.random.bits(key, shape, dtype=jnp.dtype(f"uint{itemsize * 8}"))
    if kind.startswith("uint"):
        return bits
    return jax.lax.bitcast_convert_type(bits, jnp.dtype(f"int{itemsize * 8}"))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _fixed_table(words, kinds, num_rows: int, null_probability: float):
    key = _key(words)
    ncols = len(kinds)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                 1.0 - null_probability, (ncols, num_rows))
    packed = pack_bools_2d(valid)
    groups = {}
    for i, k in enumerate(kinds):
        groups.setdefault(k, []).append(i)
    datas = [None] * ncols
    for gi, ((kind, itemsize), idxs) in enumerate(groups.items()):
        arr = _gen_fixed(jax.random.fold_in(key, 100 + gi), kind, itemsize,
                         (len(idxs), num_rows))
        for j, i in enumerate(idxs):
            datas[i] = arr[j]
    return datas, [packed[i] for i in range(ncols)]


def fixed_width_dtypes(config):
    """The program's dtypes for ``num_columns`` columns cycling
    ``dtype_cycle``."""
    import spark_rapids_jni_tpu as srj
    cycle = [getattr(srj, name.upper()) for name in config["dtype_cycle"]]
    return [cycle[i % len(cycle)] for i in range(config["num_columns"])]


def fixed_width_table(seed: int, dtypes, num_rows: int,
                      null_probability: float):
    """A ``Table`` of random fixed-width columns with validity masks,
    built through the program's public ``Column``/``Table``
    constructors."""
    from spark_rapids_jni_tpu.table import Column, Table
    kinds = tuple((dt.kind, dt.itemsize) for dt in dtypes)
    datas, masks = _fixed_table(seed_words(seed), kinds, int(num_rows),
                                float(null_probability))
    return Table(tuple(Column(dt, d, v)
                       for dt, d, v in zip(dtypes, datas, masks)))
