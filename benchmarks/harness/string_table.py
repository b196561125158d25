"""Seeded tables with string columns, made on the device, and the
gathers that bring sampled rows of them to the host.

The fixed-width columns come from ``datagen.fixed_width_table``, the
generator of the fixed-width cells.  Each string column draws a null
mask of its own at the configuration's null share, and lengths uniform
in ``[string_len_min, string_len_max]`` bytes (a null string is empty),
filled with printable ASCII bytes.  It is held in the program's device
form for strings: a dense ``[n, W]`` byte matrix, zero past each length
(``W`` the longest length rounded up to 4), and int32 offsets
``[n + 1]``.  The whole string part is one jitted call; the seed enters
as data.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.harness import datagen

FIRST_CHAR, NUM_CHARS = 32, 95     # printable ASCII


def window(config) -> int:
    """The padded width of every string column: the longest length
    rounded up to whole 4-byte words."""
    return -(-int(config["string_len_max"]) // 4) * 4


def dtypes(config):
    """The fixed-width columns cycling ``dtype_cycle``, then the string
    columns."""
    import spark_rapids_jni_tpu as srj
    fixed = datagen.fixed_width_dtypes(
        dict(config, num_columns=config["num_fixed_columns"]))
    return fixed + [srj.STRING] * int(config["num_string_columns"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _strings(words, m: int, n: int, lo: int, hi: int, width: int,
             null_probability: float):
    key = jax.random.fold_in(datagen._key(words), 0x5757)
    valid = jax.random.bernoulli(jax.random.fold_in(key, 1),
                                 1.0 - null_probability, (m, n))
    lens = jax.random.randint(jax.random.fold_in(key, 2), (m, n), lo,
                              hi + 1, dtype=jnp.int32)
    lens = jnp.where(valid, lens, 0)
    bits = jax.random.bits(jax.random.fold_in(key, 3), (m, n, width),
                           dtype=jnp.uint8)
    chars = FIRST_CHAR + bits % NUM_CHARS
    inside = jnp.arange(width, dtype=jnp.int32) < lens[..., None]
    chars = jnp.where(inside, chars, jnp.uint8(0))
    offsets = jnp.concatenate([jnp.zeros((m, 1), jnp.int32),
                               jnp.cumsum(lens, axis=1, dtype=jnp.int32)],
                              axis=1)
    return ([chars[i] for i in range(m)], [offsets[i] for i in range(m)],
            list(datagen.pack_bools_2d(valid)), jnp.sum(lens, axis=0))


def string_table(seed: int, config):
    """``(table, row_chars)``: the configuration's table, built through
    the program's public ``Column``/``Table`` constructors, and each
    row's string bytes over all its string columns (host int64 ``[n]``).
    """
    from spark_rapids_jni_tpu.table import Column, Table
    all_dtypes = dtypes(config)
    nfixed = int(config["num_fixed_columns"])
    n = int(config["num_rows"])
    p = float(config["null_probability"])
    fixed = datagen.fixed_width_table(seed, all_dtypes[:nfixed], n, p)
    chars, offsets, masks, row_chars = _strings(
        datagen.seed_words(seed), len(all_dtypes) - nfixed, n,
        int(config["string_len_min"]), int(config["string_len_max"]),
        window(config), p)
    strings = tuple(Column(dt, jnp.zeros((0,), jnp.uint8), v, o, None, c)
                    for dt, c, o, v in zip(all_dtypes[nfixed:], chars,
                                           offsets, masks))
    table = Table(tuple(fixed.columns) + strings)
    return table, np.asarray(row_chars).astype(np.int64)


def _validity(col):
    """The packed validity mask; all valid where the column has none."""
    if col.validity is not None:
        return col.validity
    return jnp.full((-(-col.num_rows // 8),), 0xFF, jnp.uint8)


@jax.jit
def _gather_padded(columns, idx):
    out = []
    for chars2d, offsets, validity in columns:
        lens = offsets[idx + 1] - offsets[idx]
        ok = ((validity[idx >> 3] >> (idx & 7).astype(jnp.uint8)) & 1) == 1
        out.append((chars2d[idx], lens, ok))
    return out


def sample_strings(columns, idx):
    """``[(values, valid)]`` of string ``columns`` at rows ``idx``:
    values a list of ``bytes``, valid a bool array.  A column in the
    dense form is gathered on the device; one with a flat chars buffer
    (the compact form) is cut on the host from its offsets."""
    idx = np.asarray(idx, np.int32)
    out = [None] * len(columns)
    dense = [i for i, c in enumerate(columns) if c.chars2d is not None]
    got = jax.device_get(_gather_padded(
        [(columns[i].chars2d, columns[i].offsets, _validity(columns[i]))
         for i in dense], jnp.asarray(idx)))
    for i, (mat, lens, ok) in zip(dense, got):
        out[i] = ([bytes(row[:ln]) for row, ln in zip(mat, lens)],
                  np.asarray(ok))
    for i, c in enumerate(columns):
        if out[i] is not None:
            continue
        offs = np.asarray(c.offsets).astype(np.int64)
        flat = np.asarray(c.chars)
        vbits = np.unpackbits(np.asarray(_validity(c)), bitorder="little")
        out[i] = ([flat[offs[r]:offs[r + 1]].tobytes() for r in idx],
                  vbits[idx] == 1)
    return out
