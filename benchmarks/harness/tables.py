"""Device-side gathers that bring sampled rows of a table, or of a row
blob, to the host for comparison.  Each takes a fixed number of rows,
so one compiled gather serves every seed."""

from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _gather_columns(columns, idx):
    out = []
    for data, validity in columns:
        d = data[:, idx] if data.ndim == 2 else data[idx]
        if validity is None:
            v = jnp.ones(idx.shape, jnp.bool_)
        else:
            v = ((validity[idx >> 3] >> (idx & 7).astype(jnp.uint8)) & 1) == 1
        out.append((d, v))
    return out


def sample_table(table, idx):
    """``[(values, valid)]`` per column at rows ``idx``: values in the
    column's native numpy dtype (64-bit word pairs joined), valid as
    bools."""
    cols = [(c.data, c.validity) for c in table.columns]
    got = jax.device_get(_gather_columns(cols, jnp.asarray(idx, jnp.int32)))
    out = []
    for c, (d, v) in zip(table.columns, got):
        dt = c.dtype.np_dtype
        d = np.asarray(d)
        if d.ndim == 2:  # [2, k] low/high words
            d = np.ascontiguousarray(d.T).view(dt).reshape(-1)
        out.append((d.astype(dt, copy=False), np.asarray(v)))
    return out


def sample_rows(blob, idx, row_size: int) -> np.ndarray:
    """``[k, row_size]`` bytes of rows ``idx`` of a row blob (2-D, or
    flat), gathered on the host.  The program hands its blobs over in a
    layout of its own choosing, which a jitted reader loaded from the
    compile cache can refuse; the copy to the host reads the buffer in
    whatever layout it has."""
    return np.asarray(blob).reshape(-1, int(row_size))[idx]


def draw_rows(rng, n: int, k: int) -> np.ndarray:
    """``k`` sorted row indices in ``[0, n)`` with the first and last
    row always among them (a fixed count: one compiled gather)."""
    k = min(k, n)
    idx = np.concatenate([[0, n - 1], rng.integers(0, n, max(k - 2, 0))])
    return np.sort(idx[:k]).astype(np.int32)
