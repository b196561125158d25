"""Distributed hash-partition shuffle: the exchange capability under Spark's
``Exchange`` operator, built TPU-native.

In the reference lineage this is the GPU shuffle the RAPIDS plugin does with
UCX/NCCL *above* the kernel library (SURVEY.md §2 "Distributed communication
backend: absent in-repo"); here it is first-class: rows cross devices as
JCUDF row blobs (the same wire format Spark itself shuffles) via
``jax.lax.all_to_all`` over the mesh axis — ICI within a slice, DCN across
slices, chosen by XLA from the mesh layout.

Static-shape design (XLA needs fixed buffer sizes where NCCL send/recv can
be ragged): each device packs its rows into ``[P, capacity, row_size]``
send buckets by partition id, all-to-alls the buckets, and carries per-bucket
counts so receivers know the valid prefix of each bucket.  ``capacity`` is
a static shape, so every distinct value is a full XLA recompile — both
paths quantize it up the :mod:`runtime.shapes` pow-2 grid so the compiled
exchange variants stay O(log N) over any skew pattern.

Two-phase protocol (default; kill switch ``SRJ_TPU_SHUFFLE_RAGGED=0``):

- **Phase 1** dispatches one tiny sizing program — partition-id hash +
  per-destination ``bincount`` + size ``all_gather`` — and, without
  waiting for its host sync, dispatches the row encode+sort program
  behind it.  The expensive encode overlaps the size exchange: by the
  time the ``[P, P]`` count matrix lands on host, the payload is already
  sorted by destination on device.
- **Phase 2** routes on the observed skew.  The *collective* route packs
  the sorted rows onto the pow-2 capacity grid and issues the bucket
  all-to-all (or ppermute ring) under ``jax.shard_map`` — the size
  matrix subsumes the legacy path's second counts collective.  The
  *staged* route (single-controller meshes, heavy skew) moves the
  ragged segments host-side through ``staging.stage_ragged_shards``:
  ONE arena sub-blob per device (the ``mesh.shard_table`` staged
  transport), so padded bytes on the wire drop to the pow-2 envelope of
  the true per-destination sizes instead of ``P² × max-bucket``.

Capacity sizing: an exact count pre-pass by default (overflow impossible,
even under heavy key skew); an explicit ``capacity_factor`` estimate
instead retries internally with doubled capacity when its overflow flag
trips — the static-shape analogue of the reference's data-dependent batch
re-planning (``build_batches`` host sync, ``row_conversion.cu:1521``).
Retried capacities stay on the pow-2 grid (``srj_tpu_shuffle_capacity_
retries_total`` counts the bumps) so a retry hits ``_exchange_cache``
instead of compiling a fresh program.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import math
import os
import threading
import weakref
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_jni_tpu.table import Column, Table
from spark_rapids_jni_tpu.runtime import shapes
from spark_rapids_jni_tpu.ops.row_layout import compute_row_layout
from spark_rapids_jni_tpu.ops import row_conversion as rc
from spark_rapids_jni_tpu.ops.hashing import hash_partition_ids

_RAGGED_ENV = "SRJ_TPU_SHUFFLE_RAGGED"
_ROUTE_ENV = "SRJ_TPU_SHUFFLE_ROUTE"
_MIN_PAD_ENV = "SRJ_TPU_SHUFFLE_STAGED_MIN_PAD"


def ragged_enabled() -> bool:
    """Two-phase ragged protocol on?  ``SRJ_TPU_SHUFFLE_RAGGED=0``
    restores the legacy single-program pad-to-max exchange."""
    return os.environ.get(_RAGGED_ENV, "1").strip().lower() not in (
        "0", "off", "no", "false")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ShuffleResult:
    """Padded post-shuffle rows on each device.

    ``rows``: [slots, row_size] uint8 per device (JCUDF rows) — the
    legacy/collective routes lay slots out as ``P`` per-sender buckets of
    ``capacity``; the staged route delivers one contiguous valid prefix.
    Consumers are layout-agnostic: ``row_valid`` masks the live slots and
    the valid rows appear in the same (sender, within-sender) order on
    every route.
    ``row_valid``: bool mask over those slots,
    ``num_valid``: int32 scalar per device,
    ``overflow``: bool scalar — True anywhere means capacity was exceeded
    and rows were dropped.  :func:`shuffle_table_sharded` handles this
    itself (exact pre-pass sizing by default; internal capacity-doubling
    retry on the estimated path): callers only see a True flag when they
    opted out with ``max_retries=0``.
    """

    rows: jnp.ndarray
    row_valid: jnp.ndarray
    num_valid: jnp.ndarray
    overflow: jnp.ndarray
    # static: padded string-slot widths the rows were encoded with (None
    # for fixed-width tables); decode_shuffle_result reads them from here
    str_widths: Optional[Tuple[int, ...]] = None

    def tree_flatten(self):
        return (self.rows, self.row_valid, self.num_valid,
                self.overflow), self.str_widths

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, aux)


def _col_sig(c):
    """Hashable structural signature of a column — everything
    ``table_partition_specs`` and the exchange trace depend on besides
    the input avals (which ``jax.jit`` keys on itself)."""
    return (c.dtype, getattr(c.data, "ndim", None),
            c.validity is not None, c.offsets is not None,
            c.chars is not None, c.chars2d is not None,
            c.lens is not None, c.capped,
            tuple(_col_sig(ch) for ch in c.children) if c.children else ())


class _ExchangeCache:
    """Compiled exchange programs, bounded and collectable.

    Entries hang off the Mesh object through a ``WeakKeyDictionary``, so
    retiring a mesh releases every exchange program traced against it
    (the old module-global dict pinned them forever).  Within a mesh a
    small LRU bounds the variants — the capacity grid
    (``runtime/shapes.py``) already bounds them in practice; the LRU
    turns that into a hard cap.  Sized for the two-phase split: per
    schema one sizing + one pack program, plus O(log N) capacity ×
    method exchange programs (which no longer key on the schema at
    all), plus the legacy path's per-schema variants when the kill
    switch is exercised side by side."""

    PER_MESH = 64

    def __init__(self):
        self._by_mesh = weakref.WeakKeyDictionary()

    def get(self, mesh: Mesh, key):
        lru = self._by_mesh.get(mesh)
        if lru is None:
            return None
        fn = lru.get(key)
        if fn is not None:
            lru.move_to_end(key)
        return fn

    def put(self, mesh: Mesh, key, fn):
        lru = self._by_mesh.get(mesh)
        if lru is None:
            lru = self._by_mesh[mesh] = collections.OrderedDict()
        lru[key] = fn
        lru.move_to_end(key)
        while len(lru) > self.PER_MESH:
            lru.popitem(last=False)


_exchange_cache = _ExchangeCache()


def _pack_buckets(rows2d, pids, num_parts: int, capacity: int):
    """Sort rows by destination partition into ``[P, capacity, width]``
    send buckets; returns (send, send_counts, overflow_local)."""
    n_local = rows2d.shape[0]
    rs = rows2d.shape[1]
    order = jnp.argsort(pids, stable=True)
    pids_sorted = pids[order]
    rows_sorted = rows2d[order]
    counts = jnp.bincount(pids, length=num_parts).astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n_local, dtype=jnp.int32) - starts[pids_sorted]
    overflow_local = jnp.any(counts > capacity)
    rank = jnp.minimum(rank, capacity - 1)  # clamp (flagged overflow)
    send = jnp.zeros((num_parts, capacity, rs), rows2d.dtype)
    send = send.at[pids_sorted, rank].set(rows_sorted)
    return send, jnp.minimum(counts, capacity), overflow_local


def _finish_exchange(recv, recv_counts, overflow_local,
                     num_parts: int, capacity: int, axis_name: str):
    """Shared epilogue: slot-validity mask, valid count, global overflow."""
    rs = recv.shape[-1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (num_parts, capacity), 1)
    valid = slot < recv_counts[:, None]
    num_valid = jnp.sum(recv_counts)
    overflow = jax.lax.pmax(overflow_local, axis_name)
    return (recv.reshape(num_parts * capacity, rs),
            valid.reshape(-1), num_valid, overflow)


def bucket_exchange(num_parts: int, capacity: int, axis_name: str):
    """Per-device all-to-all bucket exchange body (run under shard_map).

    Packs ``payload2d[n_local, width]`` rows into ``[P, capacity, width]``
    send buckets by ``pids``, exchanges them, and returns
    ``(recv[P*capacity, width], slot_valid, num_valid, overflow)``.  Works
    for any payload dtype; the JCUDF shuffle feeds uint8 row blobs, the
    query pipeline feeds int32 column stacks.
    """

    def body(rows2d, pids):
        send, send_counts, overflow_local = _pack_buckets(
            rows2d, pids, num_parts, capacity)
        recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                  concat_axis=0, tiled=False)
        recv_counts = jax.lax.all_to_all(
            send_counts.reshape(num_parts, 1), axis_name,
            split_axis=0, concat_axis=0, tiled=False).reshape(num_parts)
        return _finish_exchange(recv, recv_counts, overflow_local,
                                num_parts, capacity, axis_name)

    return body


def ring_bucket_exchange(num_parts: int, capacity: int, axis_name: str):
    """Ring variant of :func:`bucket_exchange`: the all-to-all is decomposed
    into ``P - 1`` shifted ``ppermute`` steps (step ``s`` sends each
    device's bucket for ``d + s`` directly to ``d + s``).

    Total bytes on the wire match the fused all-to-all, but only ONE bucket
    is in flight per device per step instead of ``P`` — the right shape
    when buckets are large (long rows / long sequences) and the fused
    exchange buffer would not fit.  This is the same decomposition ring
    attention applies to sequence-parallel KV exchange; XLA overlaps each
    ppermute with the next step's pack on ICI.
    """

    def body(rows2d, pids):
        send, send_counts, overflow_local = _pack_buckets(
            rows2d, pids, num_parts, capacity)
        d = jax.lax.axis_index(axis_name)
        recv = jnp.zeros_like(send)
        recv_counts = jnp.zeros((num_parts,), jnp.int32)
        # self bucket stays local
        recv = jax.lax.dynamic_update_index_in_dim(
            recv, jax.lax.dynamic_index_in_dim(send, d, 0), d, 0)
        recv_counts = recv_counts.at[d].set(send_counts[d])

        # python-unrolled: ppermute's permutation must be static, and the
        # step count (P - 1) is a mesh constant
        for s in range(1, num_parts):
            perm = [(i, (i + s) % num_parts) for i in range(num_parts)]
            tgt = (d + s) % num_parts
            blk = jax.lax.dynamic_index_in_dim(send, tgt, 0)
            cnt = jax.lax.dynamic_index_in_dim(send_counts, tgt, 0)
            got = jax.lax.ppermute(blk, axis_name, perm)
            got_cnt = jax.lax.ppermute(cnt, axis_name, perm)
            src = (d - s) % num_parts
            recv = jax.lax.dynamic_update_index_in_dim(recv, got, src, 0)
            recv_counts = jax.lax.dynamic_update_slice(
                recv_counts, got_cnt, (src,))

        return _finish_exchange(recv, recv_counts, overflow_local,
                                num_parts, capacity, axis_name)

    return body


def two_phase_exchange(num_parts: int, capacity: int, axis_name: str,
                       method: str = "all_to_all"):
    """Two-phase twin of :func:`bucket_exchange` /
    :func:`ring_bucket_exchange` (run under shard_map).

    Phase 1 ``all_gather``s the per-(sender, destination) bucket counts —
    a ``[P, P]`` int32 matrix, bytes-trivial next to the payload — with no
    data dependence on the pack, so XLA overlaps it with the row sort.
    Phase 2 moves the payload buckets only: the legacy path's second
    counts collective is subsumed by reading this device's column of the
    size matrix (``recv_counts[p] = min(counts[p, d], capacity)``), which
    is value-identical to what the legacy exchange delivers.  Byte-for-
    byte the same result as the legacy body for both methods.
    """

    def body(rows2d, pids):
        counts = jnp.bincount(pids, length=num_parts).astype(jnp.int32)
        all_counts = jax.lax.all_gather(counts, axis_name)  # [P, P]
        send, _, overflow_local = _pack_buckets(
            rows2d, pids, num_parts, capacity)
        d = jax.lax.axis_index(axis_name)
        if method == "ring":
            recv = jnp.zeros_like(send)
            recv = jax.lax.dynamic_update_index_in_dim(
                recv, jax.lax.dynamic_index_in_dim(send, d, 0), d, 0)
            for s in range(1, num_parts):
                perm = [(i, (i + s) % num_parts) for i in range(num_parts)]
                tgt = (d + s) % num_parts
                blk = jax.lax.dynamic_index_in_dim(send, tgt, 0)
                got = jax.lax.ppermute(blk, axis_name, perm)
                src = (d - s) % num_parts
                recv = jax.lax.dynamic_update_index_in_dim(
                    recv, got, src, 0)
        else:
            recv = jax.lax.all_to_all(send, axis_name, split_axis=0,
                                      concat_axis=0, tiled=False)
        recv_counts = jnp.minimum(all_counts[:, d], capacity)
        return _finish_exchange(recv, recv_counts, overflow_local,
                                num_parts, capacity, axis_name)

    return body


def _string_layout_of(table: Table, layout):
    """(slot_starts, fe_pad, row_size, widths) for string tables, or
    ``None`` row params for fixed-width ones."""
    if not layout.has_strings:
        return None, None, layout.fixed_row_size, None
    scols = [c for c in table.columns if c.dtype.is_string]
    if not all(c.is_padded for c in scols):
        raise ValueError(
            "string shuffle requires dense-padded string columns "
            "(Column.to_padded / strings_padded); Arrow-layout chars "
            "cannot cross the static-shape exchange")
    widths = tuple(c.chars2d.shape[1] for c in scols)
    slot_starts, fe_pad, row_size = rc.padded_variable_layout(layout, widths)
    return slot_starts, fe_pad, row_size, widths


def max_bucket_count(table: Table, key_cols: Sequence[int], mesh: Mesh,
                     axis_name: str = "data", seed: int = 42) -> int:
    """Exact-capacity pre-pass: the largest (source device, destination
    partition) bucket the exchange will produce.  One cheap jit (hash +
    bincount + pmax) before the row encode — the static-shape analogue of
    the reference's data-dependent host sync (``build_batches``,
    ``row_conversion.cu:1521``): spend one tiny device round-trip to size
    the buffers exactly instead of guessing and overflowing."""
    num_parts = mesh.shape[axis_name]
    from spark_rapids_jni_tpu.parallel.mesh import table_partition_specs

    cache_key = ("count", tuple(_col_sig(c) for c in table.columns),
                 tuple(key_cols), num_parts, axis_name, seed,
                 bool(jax.config.jax_enable_x64))
    fn = _exchange_cache.get(mesh, cache_key)
    if fn is None:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(table_partition_specs(table, axis_name),),
            out_specs=P(), check_vma=False)
        def count(tbl):
            pids = hash_partition_ids(
                [tbl.columns[i] for i in key_cols], num_parts, seed)
            counts = jnp.bincount(pids, length=num_parts).astype(jnp.int32)
            return jax.lax.pmax(jnp.max(counts), axis_name)

        fn = jax.jit(count)
        _exchange_cache.put(mesh, cache_key, fn)
    return int(fn(table))


def exchange_size_matrix(table: Table, key_cols: Sequence[int], mesh: Mesh,
                         axis_name: str = "data", seed: int = 42):
    """Phase 1 of the two-phase protocol as ONE cached program:
    partition-id hash + per-destination ``bincount`` + size ``all_gather``.

    Returns ``(pids, counts)``: the partition ids, still sharded over the
    mesh axis (phase 2's pack consumes them without rehashing), and the
    replicated ``[P, P]`` (sender, destination) count matrix.  Callers
    dispatch this, dispatch the row encode behind it, and only then sync
    the counts to host — the encode overlaps the size exchange."""
    num_parts = mesh.shape[axis_name]
    from spark_rapids_jni_tpu.parallel.mesh import table_partition_specs

    cache_key = ("sizes", tuple(_col_sig(c) for c in table.columns),
                 tuple(key_cols), num_parts, axis_name, seed,
                 bool(jax.config.jax_enable_x64))
    fn = _exchange_cache.get(mesh, cache_key)
    if fn is None:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(table_partition_specs(table, axis_name),),
            out_specs=(P(axis_name), P()), check_vma=False)
        def sizes(tbl):
            pids = hash_partition_ids(
                [tbl.columns[i] for i in key_cols], num_parts, seed)
            counts = jnp.bincount(pids, length=num_parts).astype(jnp.int32)
            return pids.astype(jnp.int32), jax.lax.all_gather(
                counts, axis_name)

        fn = jax.jit(sizes)
        _exchange_cache.put(mesh, cache_key, fn)
    return fn(table)


def _align_capacity(capacity: int, num_parts: int) -> int:
    # per-device slot count (num_parts * capacity) must land on a byte
    # boundary: decode packs validity bitmasks per device and concatenates
    # them across the mesh, so a non-multiple-of-8 count would misalign
    # every later device's bits
    capacity = max(8, capacity)
    while (capacity * num_parts) % 8:
        capacity += 1
    return capacity


def exchange_capacity(need: int, num_parts: int) -> int:
    """Quantize a per-bucket row need up the repo-wide pow-2 capacity
    grid, then bump to the decode bitmask alignment.  EVERY capacity an
    exchange compiles against — initial sizing, plan-node estimates, and
    overflow retries alike — comes from here, so the distinct exchange
    programs stay O(log N) and a retried capacity re-hits
    ``_exchange_cache`` instead of compiling fresh."""
    return _align_capacity(shapes.bucket_rows(max(8, int(need))), num_parts)


# ---------------------------------------------------------------------------
# Two-phase phase 2: pack + routed transport
# ---------------------------------------------------------------------------


def _pack_program(table: Table, mesh: Mesh, axis_name: str, layout,
                  slot_starts, fe_pad, row_size, widths,
                  key_cols=None, num_parts=None, seed=42):
    """The overlapped encode: JCUDF row assembly + stable sort by
    destination, ONE cached program per schema.  With ``key_cols`` the
    program hashes its own partition ids (the estimated path, which has
    no phase-1 sizing dispatch to reuse); otherwise it consumes the ids
    the sizing program produced.  Splitting the encode out of the
    exchange keeps the exchange programs schema-independent, so their
    count is bounded by the capacity grid alone."""
    from spark_rapids_jni_tpu.parallel.mesh import table_partition_specs
    self_hash = key_cols is not None
    cache_key = ("pack", tuple(_col_sig(c) for c in table.columns),
                 widths, axis_name,
                 (tuple(key_cols), num_parts, seed) if self_hash else None,
                 bool(jax.config.jax_enable_x64))
    fn = _exchange_cache.get(mesh, cache_key)
    if fn is not None:
        return fn

    def _encode(tbl):
        if widths is not None:
            return rc.padded_rows2d(tbl, layout, slot_starts,
                                    fe_pad, row_size)
        return rc._assemble_fixed_rows(tbl, layout)

    spec = P(axis_name)
    if self_hash:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(table_partition_specs(table, axis_name),),
            out_specs=(spec, spec), check_vma=False)
        def pack(tbl):
            rows2d = _encode(tbl)
            pids = hash_partition_ids(
                [tbl.columns[i] for i in key_cols], num_parts, seed)
            order = jnp.argsort(pids, stable=True)
            return rows2d[order], pids[order].astype(jnp.int32)
    else:
        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(table_partition_specs(table, axis_name), spec),
            out_specs=(spec, spec), check_vma=False)
        def pack(tbl, pids):
            rows2d = _encode(tbl)
            order = jnp.argsort(pids, stable=True)
            return rows2d[order], pids[order].astype(jnp.int32)

    fn = jax.jit(pack)
    _exchange_cache.put(mesh, cache_key, fn)
    return fn


def _exchange_program(mesh: Mesh, num_parts: int, capacity: int,
                      method: str, axis_name: str):
    """Phase-2 collective program over (sorted rows, sorted pids).
    Schema-independent — the cache key carries only mesh geometry,
    capacity and method, so one compiled variant per capacity-grid point
    serves every table shape (jit retraces per row-size aval under the
    same cache slot)."""
    cache_key = ("xchg", num_parts, capacity, method, axis_name)
    fn = _exchange_cache.get(mesh, cache_key)
    if fn is None:
        spec = P(axis_name)
        body = two_phase_exchange(num_parts, capacity, axis_name, method)

        @functools.partial(
            jax.shard_map, mesh=mesh, in_specs=(spec, spec),
            out_specs=(spec, spec, spec, P()), check_vma=False)
        def run(rows_sorted, pids_sorted):
            rows, valid, num_valid, overflow = body(rows_sorted,
                                                    pids_sorted)
            return rows, valid, num_valid[None], overflow[None]

        fn = jax.jit(run)
        _exchange_cache.put(mesh, cache_key, fn)
    return fn


@dataclasses.dataclass(frozen=True)
class ExchangePlan:
    """Host-side phase-2 plan derived from the phase-1 size matrix."""
    counts: np.ndarray           # [P, P] rows from sender s to dest d
    num_parts: int
    row_size: int
    capacity: int                # collective capacity (pow-2 grid, aligned)
    total_rows: int
    skew: float                  # hottest destination share × P (1 = uniform)
    true_bytes: int              # payload actually owed to the exchange
    collective_wire_bytes: int   # P² × capacity × row_size (incl. loopback)
    staged_wire_bytes: int       # pow-2 blob envelope of the ragged sizes


def plan_exchange(counts: np.ndarray, num_parts: int,
                  row_size: int) -> ExchangePlan:
    """Derive capacity, skew factor and per-route wire-byte estimates
    from the ``[P, P]`` size matrix."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    capacity = exchange_capacity(int(counts.max()) if total else 8,
                                 num_parts)
    recv_totals = counts.sum(axis=0)
    skew = (float(recv_totals.max()) * num_parts / total) if total else 1.0
    staged = 0
    for d in range(num_parts):
        b_d = int(shapes.bucket_rows(max(8, int(recv_totals[d]))))
        # rows blob + count word, quantized like staging's arena blobs
        staged += int(shapes.bucket_rows(b_d * row_size + 16))
    return ExchangePlan(
        counts=counts, num_parts=num_parts, row_size=row_size,
        capacity=capacity, total_rows=total, skew=skew,
        true_bytes=total * row_size,
        collective_wire_bytes=num_parts * num_parts * capacity * row_size,
        staged_wire_bytes=staged)


def _staged_transport_ok(mesh: Mesh) -> bool:
    """The host-routed staged transport needs a single-controller 1-D
    mesh (every shard addressable); multi-process pods always take the
    collective route."""
    try:
        if jax.process_count() > 1:
            return False
    except Exception:
        return False
    return len(mesh.shape) == 1


def _note_route(route: str, source: str) -> str:
    """Stamp one route decision on the optimizer's counters
    (``srj_tpu_plan_opt_route_total{route,source}``).  Never raises."""
    try:
        from spark_rapids_jni_tpu.runtime import optimizer as _opt
        _opt.note_route(route, source)
    except Exception:
        pass
    return route


def _choose_route(xplan: ExchangePlan, mesh: Mesh, method: str) -> str:
    """Collective vs staged, priced off measured wire costs.

    Priority order: ``SRJ_TPU_SHUFFLE_ROUTE=collective|staged`` is a
    forced override; transport constraints (multi-process pods, ring
    method) force collective; an explicitly-set
    ``SRJ_TPU_SHUFFLE_STAGED_MIN_PAD`` forces the legacy pad-ratio rule
    with that threshold.  Otherwise the pick is **priced**: staged wins
    when ``collective_wire_bytes > C × staged_wire_bytes`` with ``C``
    the measured staged-vs-collective throughput crossover (live
    costmodel ledger, falling back to the value persisted alongside
    calibration — ``runtime.optimizer.staged_crossover``).  With no
    measurement anywhere, the old 4.0 pad-ratio heuristic remains the
    default.  Every decision is stamped
    ``srj_tpu_plan_opt_route_total{route,source=forced|priced|default}``.
    """
    forced = os.environ.get(_ROUTE_ENV, "").strip().lower()
    if forced in ("collective", "staged"):
        if forced == "staged" and not _staged_transport_ok(mesh):
            return _note_route("collective", "forced")
        return _note_route(forced, "forced")
    if method != "all_to_all" or not _staged_transport_ok(mesh):
        return _note_route("collective", "default")
    if xplan.true_bytes <= 0:
        return _note_route("collective", "default")
    raw_pad = os.environ.get(_MIN_PAD_ENV, "").strip()
    if raw_pad:
        try:
            min_pad = float(raw_pad)
        except ValueError:
            min_pad = 4.0
        ratio = xplan.collective_wire_bytes / xplan.true_bytes
        if ratio >= min_pad and (xplan.staged_wire_bytes
                                 < xplan.collective_wire_bytes):
            return _note_route("staged", "forced")
        return _note_route("collective", "forced")
    try:
        from spark_rapids_jni_tpu.runtime import optimizer as _opt
        priced = _opt.price_route(xplan)
    except Exception:
        priced = None
    if priced is not None:
        return _note_route(priced[0], priced[1])
    # no measured crossover anywhere: the historical 4.0 placeholder
    ratio = xplan.collective_wire_bytes / xplan.true_bytes
    if ratio >= 4.0 and (xplan.staged_wire_bytes
                         < xplan.collective_wire_bytes):
        return _note_route("staged", "default")
    return _note_route("collective", "default")


@functools.lru_cache(maxsize=256)
def _staged_finish_program(b: int, cap: int, rs: int):
    """Per-device epilogue for the staged route: pad the pow-2-tight
    staged rows up to the uniform shard capacity and build the valid
    prefix mask.  Keyed on grid points only — (staged bucket, capacity,
    row size) — so the variants stay O(log² N)."""

    def fin(rows_b, nv):
        if b < cap:
            rows = jnp.concatenate(
                [rows_b, jnp.zeros((cap - b, rs), rows_b.dtype)], axis=0)
        else:
            rows = rows_b
        valid = jnp.arange(cap, dtype=jnp.int32) < nv[0]
        return rows, valid

    return jax.jit(fin)


def _staged_ragged_transport(rows_sorted, xplan: ExchangePlan, mesh: Mesh,
                             axis_name: str):
    """Phase-2 staged route: move the ragged per-destination segments
    through the host with ONE arena sub-blob per device
    (``staging.stage_ragged_shards`` — the ``mesh.shard_table`` staged
    transport), so the wire carries the pow-2 envelope of the TRUE sizes
    instead of the collective's ``P² × max-bucket``.

    The sorted send buffers are already grouped by destination on each
    device, so routing is pure ``np`` segment slicing: destination ``d``
    receives senders' segments in sender order, which is exactly the
    (sender-bucket, stable-sort) order the collective routes deliver —
    the valid-row streams are identical.

    Returns the four ShuffleResult leaves plus the staged wire bytes."""
    from spark_rapids_jni_tpu.runtime import staging
    num_parts, rs = xplan.num_parts, xplan.row_size
    counts = xplan.counts
    devs = list(mesh.devices.flat)
    shards = sorted(rows_sorted.addressable_shards,
                    key=lambda s: (s.index[0].start or 0))
    host_send = [np.asarray(s.data) for s in shards]
    starts = np.cumsum(counts, axis=1) - counts     # per-sender dest offsets
    recv_totals = counts.sum(axis=0)
    cap = int(shapes.bucket_rows(max(8, int(recv_totals.max())
                                     if counts.size else 8)))
    per_dev_bufs, b_sizes = [], []
    for d in range(num_parts):
        r_d = int(recv_totals[d])
        b_d = int(shapes.bucket_rows(max(8, r_d)))
        buf = np.zeros((b_d, rs), np.uint8)
        if r_d:
            segs = [host_send[s][starts[s, d]:starts[s, d] + counts[s, d]]
                    for s in range(num_parts) if counts[s, d]]
            buf[:r_d] = np.concatenate(segs, axis=0)
        per_dev_bufs.append([buf, np.asarray([r_d], np.int32)])
        b_sizes.append(b_d)
    staged, wire = staging.stage_ragged_shards(per_dev_bufs, mesh,
                                               axis_name)
    rows_list, valid_list, nv_list = [], [], []
    for d in range(num_parts):
        rows_d, valid_d = _staged_finish_program(b_sizes[d], cap, rs)(
            staged[d][0], staged[d][1])
        rows_list.append(rows_d)
        valid_list.append(valid_d)
        nv_list.append(staged[d][1])
    spec = NamedSharding(mesh, P(axis_name))
    rows = jax.make_array_from_single_device_arrays(
        (num_parts * cap, rs), spec, rows_list)
    valid = jax.make_array_from_single_device_arrays(
        (num_parts * cap,), spec, valid_list)
    num_valid = jax.make_array_from_single_device_arrays(
        (num_parts,), spec, nv_list)
    overflow = jax.device_put(np.zeros((1,), np.bool_),
                              NamedSharding(mesh, P()))
    return rows, valid, num_valid, overflow, wire, cap


# ---------------------------------------------------------------------------
# Observability: srj_tpu_shuffle_* metric families + healthz sub-doc
# ---------------------------------------------------------------------------

_EXPORTED = False
_EXPORT_LOCK = threading.Lock()
_STATS_LOCK = threading.Lock()
_STATS: Dict = {
    "ragged": True,
    "exchanges": {},          # route -> count
    "send_bytes": 0,
    "recv_bytes": 0,
    "padded_bytes": {},       # route -> padded wire bytes
    "capacity_retries": 0,
    "last": {},               # route/method/capacity/skew of the last exchange
}


def _health() -> Dict:
    with _STATS_LOCK:
        snap = {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in _STATS.items()}
    snap["ragged"] = ragged_enabled()
    return snap


def _publish_gauges() -> None:
    from spark_rapids_jni_tpu.obs import metrics
    with _STATS_LOCK:
        last = dict(_STATS["last"])
    skew = last.get("skew")
    if isinstance(skew, (int, float)) and math.isfinite(skew):
        metrics.gauge("srj_tpu_shuffle_skew_factor",
                      "Hottest-destination share × P of the most recent "
                      "exchange (1.0 = perfectly uniform).").set(
            float(skew))


def _ensure_exported() -> None:
    global _EXPORTED
    if _EXPORTED:
        return
    with _EXPORT_LOCK:
        if _EXPORTED:
            return
        try:
            from spark_rapids_jni_tpu.obs import exporter, metrics
            metrics.counter("srj_tpu_shuffle_exchanges_total",
                            "Shuffle exchanges by transport route.",
                            ("route", "method"))
            metrics.counter("srj_tpu_shuffle_send_bytes_total",
                            "True payload bytes offered to the exchange.")
            metrics.counter("srj_tpu_shuffle_recv_bytes_total",
                            "True payload bytes delivered by the exchange.")
            metrics.counter("srj_tpu_shuffle_padded_bytes_total",
                            "Wire bytes minus true payload bytes, by "
                            "route.", ("route",))
            metrics.counter("srj_tpu_shuffle_capacity_retries_total",
                            "Overflow-capacity bumps on the estimated "
                            "sizing path.")
            metrics.register_collect_hook(_publish_gauges)
            exporter.register_health_provider("shuffle", _health)
        except Exception:
            pass
        _EXPORTED = True


def _count_retry() -> None:
    with _STATS_LOCK:
        _STATS["capacity_retries"] += 1
    try:
        from spark_rapids_jni_tpu.obs import metrics
        metrics.counter("srj_tpu_shuffle_capacity_retries_total").inc()
    except Exception:
        pass


def _record_exchange(route: str, method: str, true_bytes: int,
                     wire_bytes: int, capacity: int, skew: float,
                     counts=None) -> None:
    padded = max(0, int(wire_bytes) - int(true_bytes))
    # the estimated/legacy paths never observe counts, so their skew is
    # unknown — store None, not NaN: NaN breaks both the Prometheus
    # exposition (int(nan)) and strict-JSON healthz consumers
    skew = float(skew) if math.isfinite(skew) else None
    try:
        # plan-stats feed: the phase-1 [P, P] size matrix and skew are
        # exactly what EXPLAIN ANALYZE reports for exchange nodes,
        # attributed via planstats.plan_scope when a plan is bound
        from spark_rapids_jni_tpu.obs import planstats
        if planstats.enabled():
            planstats.observe_exchange(
                route=route, method=method, capacity=int(capacity),
                skew=skew, true_bytes=int(true_bytes),
                wire_bytes=int(wire_bytes), counts=counts)
    except Exception:
        pass
    with _STATS_LOCK:
        _STATS["exchanges"][route] = _STATS["exchanges"].get(route, 0) + 1
        _STATS["send_bytes"] += int(true_bytes)
        _STATS["recv_bytes"] += int(true_bytes)
        _STATS["padded_bytes"][route] = (
            _STATS["padded_bytes"].get(route, 0) + padded)
        _STATS["last"] = {"route": route, "method": method,
                          "capacity": int(capacity), "skew": skew,
                          "wire_bytes": int(wire_bytes)}
    try:
        from spark_rapids_jni_tpu.obs import metrics
        metrics.counter("srj_tpu_shuffle_exchanges_total").inc(
            1, route=route, method=method)
        metrics.counter("srj_tpu_shuffle_send_bytes_total").inc(true_bytes)
        metrics.counter("srj_tpu_shuffle_recv_bytes_total").inc(true_bytes)
        metrics.counter("srj_tpu_shuffle_padded_bytes_total").inc(
            padded, route=route)
    except Exception:
        pass
    try:
        # once the ledger has seen BOTH routes, persist the measured
        # staged-vs-collective crossover next to the calibration file
        # (throttled inside; replaces the 4.0 min-pad placeholder for
        # later processes on this host)
        from spark_rapids_jni_tpu.runtime import optimizer as _opt
        _opt.maybe_persist_crossover()
    except Exception:
        pass


# ---------------------------------------------------------------------------
# The shuffle entry
# ---------------------------------------------------------------------------


def shuffle_table_sharded(table: Table, key_cols: Sequence[int],
                          mesh: Mesh, axis_name: str = "data",
                          capacity_factor: Optional[float] = None,
                          seed: int = 42,
                          method: str = "all_to_all",
                          max_retries: int = 4) -> ShuffleResult:
    """Hash-partition a row-sharded table across the mesh axis.

    Fixed-width tables exchange fixed-size JCUDF rows; string tables
    exchange dense-padded variable-width rows (uniform ``row_size`` =
    fixed section + one padded slot per string column) — the static-shape
    wire format the all-to-all needs, self-describing via each row's
    (offset, length) pairs.  Decode with :func:`decode_shuffle_result`.

    Default protocol is the two-phase ragged exchange (module
    docstring): phase 1 overlaps the size all_gather with the row
    encode+sort, phase 2 routes between the collective bucket exchange
    and the staged ragged sub-blob transport on observed skew.
    ``SRJ_TPU_SHUFFLE_RAGGED=0`` restores the legacy single-program
    pad-to-max exchange.  Either way the delivered valid-row streams are
    identical.

    Capacity sizing: with ``capacity_factor=None`` (the default) the
    exact size pre-pass means skewed key distributions — the normal case
    for group-by exchanges — cannot overflow.  Passing an explicit
    factor skips the pre-pass and estimates ``capacity = n_local / P *
    factor``; if that estimate overflows, the exchange is retried with
    doubled capacity on the pow-2 grid (host-checked, at most
    ``max_retries`` times) before raising.  ``max_retries=0`` opts out
    of the retry and returns the flagged result for callers that inspect
    the flag themselves.
    """
    if method not in ("all_to_all", "ring"):
        raise ValueError(f"unknown shuffle method {method!r}")
    layout = compute_row_layout(table.dtypes)
    slot_starts, fe_pad, row_size, widths = _string_layout_of(table, layout)
    num_parts = mesh.shape[axis_name]
    n_local = table.num_rows // num_parts
    _ensure_exported()
    from spark_rapids_jni_tpu.obs import spans as _spans

    with _spans.span("shuffle_table_sharded", rows=table.num_rows,
                     method=method) as sp:
        if not ragged_enabled():
            result = _legacy_shuffle(
                table, key_cols, mesh, axis_name, capacity_factor, seed,
                method, max_retries, layout, slot_starts, fe_pad,
                row_size, widths, num_parts, n_local, sp)
        elif capacity_factor is not None:
            result = _ragged_estimated(
                table, mesh, axis_name, capacity_factor, seed, method,
                max_retries, layout, slot_starts, fe_pad, row_size,
                widths, num_parts, n_local, key_cols, sp)
        else:
            result = _ragged_exact(
                table, key_cols, mesh, axis_name, seed, method, layout,
                slot_starts, fe_pad, row_size, widths, num_parts, sp)
        sp.fence((result.rows, result.num_valid))
    return result


def _stamp_span(sp, route: str, capacity: int, true_bytes: int,
                wire_bytes: int, row_size: int, skew: float) -> None:
    """Attribute the exchange on its span: ``sig``/``bucket``/``bytes``/
    ``padded_bytes`` are the costmodel ledger's cell keys and sums, so
    the roofline report gets a per-(row-size, capacity, route) shuffle
    row for free."""
    sp.set(sig=f"rs{row_size}", bucket=capacity, impl=route, route=route,
           bytes=int(true_bytes), wire_bytes=int(wire_bytes),
           padded_bytes=max(0, int(wire_bytes) - int(true_bytes)),
           send_bytes=int(true_bytes), recv_bytes=int(true_bytes),
           capacity=int(capacity))
    if math.isfinite(skew):
        sp.set(skew=float(skew))


def _ragged_exact(table, key_cols, mesh, axis_name, seed, method, layout,
                  slot_starts, fe_pad, row_size, widths, num_parts,
                  sp) -> ShuffleResult:
    # phase 1: size matrix, dispatched async
    pids, counts_dev = exchange_size_matrix(table, key_cols, mesh,
                                            axis_name, seed)
    # overlap: the row encode+sort enqueues behind phase 1 immediately —
    # the host only blocks on the (tiny) count matrix afterwards, while
    # the payload encode is still running on device
    pack = _pack_program(table, mesh, axis_name, layout, slot_starts,
                         fe_pad, row_size, widths)
    rows_sorted, pids_sorted = pack(table, pids)
    counts = np.asarray(jax.device_get(counts_dev))
    xplan = plan_exchange(counts, num_parts, row_size)
    route = _choose_route(xplan, mesh, method)
    if route == "staged":
        rows, valid, num_valid, overflow, wire, cap = (
            _staged_ragged_transport(rows_sorted, xplan, mesh, axis_name))
        capacity = cap
    else:
        fn = _exchange_program(mesh, num_parts, xplan.capacity, method,
                               axis_name)
        rows, valid, num_valid, overflow = fn(rows_sorted, pids_sorted)
        wire = xplan.collective_wire_bytes
        capacity = xplan.capacity
    _record_exchange(route, method, xplan.true_bytes, wire, capacity,
                     xplan.skew, counts=xplan.counts)
    _stamp_span(sp, route, capacity, xplan.true_bytes, wire, row_size,
                xplan.skew)
    return ShuffleResult(rows, valid, num_valid, overflow, widths)


def _ragged_estimated(table, mesh, axis_name, capacity_factor, seed,
                      method, max_retries, layout, slot_starts, fe_pad,
                      row_size, widths, num_parts, n_local, key_cols,
                      sp) -> ShuffleResult:
    # the estimated path skips the phase-1 sizing dispatch entirely: the
    # pack program hashes its own partition ids and the in-trace size
    # all_gather of the two-phase body supplies the receive counts
    capacity = exchange_capacity(int(n_local / num_parts
                                     * capacity_factor), num_parts)
    pack = _pack_program(table, mesh, axis_name, layout, slot_starts,
                         fe_pad, row_size, widths, key_cols=key_cols,
                         num_parts=num_parts, seed=seed)
    rows_sorted, pids_sorted = pack(table)
    true_bytes = table.num_rows * row_size
    attempt = 0
    while True:
        fn = _exchange_program(mesh, num_parts, capacity, method,
                               axis_name)
        rows, valid, num_valid, overflow = fn(rows_sorted, pids_sorted)
        if max_retries == 0:
            break
        if not bool(jax.device_get(overflow).any()):
            break
        if attempt >= max_retries:
            raise RuntimeError(
                f"shuffle bucket overflow persists after "
                f"{max_retries} capacity doublings (final "
                f"capacity={capacity}); the key distribution "
                "concentrates more rows on one (device, partition) "
                "bucket than the exchange can grow to hold")
        capacity = exchange_capacity(capacity * 2, num_parts)
        _count_retry()
        attempt += 1
    wire = num_parts * num_parts * capacity * row_size
    _record_exchange("collective", method, true_bytes, wire, capacity,
                     float("nan"))
    _stamp_span(sp, "collective", capacity, true_bytes, wire, row_size,
                float("nan"))
    return ShuffleResult(rows, valid, num_valid, overflow, widths)


def _legacy_shuffle(table, key_cols, mesh, axis_name, capacity_factor,
                    seed, method, max_retries, layout, slot_starts,
                    fe_pad, row_size, widths, num_parts, n_local,
                    sp) -> ShuffleResult:
    """The pre-two-phase protocol, verbatim: one program does encode +
    hash + pack + exchange (counts ride a second collective), padded to
    one global max capacity.  Kept behind ``SRJ_TPU_SHUFFLE_RAGGED=0``
    as the equivalence oracle and escape hatch."""
    exact = capacity_factor is None
    if exact:
        need = max(8, max_bucket_count(table, key_cols, mesh, axis_name,
                                       seed))
    else:
        need = max(8, int(n_local / num_parts * capacity_factor))
    capacity = exchange_capacity(need, num_parts)

    make_body = (ring_bucket_exchange if method == "ring"
                 else bucket_exchange)
    spec = P(axis_name)
    rep = P()
    from spark_rapids_jni_tpu.parallel.mesh import table_partition_specs

    def attempt(capacity: int):
        # the jitted exchange is cached on its true statics so repeated
        # shuffles of same-shaped batches reuse one compiled program
        # (jit retraces on aval changes by itself; the key pins what the
        # trace closes over)
        cache_key = (tuple(_col_sig(c) for c in table.columns),
                     tuple(key_cols), num_parts, capacity, method,
                     axis_name, seed, widths,
                     bool(jax.config.jax_enable_x64))
        fn = _exchange_cache.get(mesh, cache_key)
        if fn is None:
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(table_partition_specs(table, axis_name),),
                out_specs=(spec, spec, spec, rep),
                check_vma=False)
            def run(tbl):
                if widths is not None:
                    rows2d = rc.padded_rows2d(tbl, layout, slot_starts,
                                              fe_pad, row_size)
                else:
                    rows2d = rc._assemble_fixed_rows(tbl, layout)
                pids = hash_partition_ids(
                    [tbl.columns[i] for i in key_cols], num_parts, seed)
                body = make_body(num_parts, capacity, axis_name)
                rows, valid, num_valid, overflow = body(rows2d, pids)
                return rows, valid, num_valid[None], overflow[None]

            fn = jax.jit(run)
            _exchange_cache.put(mesh, cache_key, fn)
        return fn(table)

    rows, valid, num_valid, overflow = attempt(capacity)
    if not exact and max_retries > 0:
        # host-checked doubling retry.  The blocking flag sync only
        # happens here: exact sizing cannot overflow and the
        # max_retries=0 opt-out returns the un-synced flagged result,
        # so both stay fully async
        for _ in range(max_retries):
            if not bool(jax.device_get(overflow).any()):
                break
            capacity = exchange_capacity(capacity * 2, num_parts)
            _count_retry()
            rows, valid, num_valid, overflow = attempt(capacity)
        else:
            if bool(jax.device_get(overflow).any()):
                raise RuntimeError(
                    f"shuffle bucket overflow persists after "
                    f"{max_retries} capacity doublings (final "
                    f"capacity={capacity}); the key distribution "
                    "concentrates more rows on one (device, partition) "
                    "bucket than the exchange can grow to hold")
    true_bytes = table.num_rows * row_size
    wire = num_parts * num_parts * capacity * row_size
    _record_exchange("legacy", method, true_bytes, wire, capacity,
                     float("nan"))
    _stamp_span(sp, "legacy", capacity, true_bytes, wire, row_size,
                float("nan"))
    return ShuffleResult(rows, valid, num_valid, overflow, widths)


def decode_shuffle_result(result: ShuffleResult, dtypes,
                          mesh: Mesh, axis_name: str = "data",
                          str_widths=None):
    """Per-device decode of shuffled rows back to a (padded) table plus the
    validity-of-slot mask; aggregations downstream mask with ``row_valid``.

    String slot widths come from the result itself (``ShuffleResult
    .str_widths``); ``str_widths`` overrides for foreign blobs.  Invalid
    slots decode as empty strings (their rows are all-zero, so every pair
    length is 0)."""
    layout = compute_row_layout(dtypes)
    spec = P(axis_name)
    if str_widths is None:
        str_widths = result.str_widths

    def _data_spec(dt):
        # 64-bit plane-pair columns ([2, n]) shard rows on axis 1
        wide = dt.itemsize == 8 and not jax.config.jax_enable_x64
        return P(None, axis_name) if wide else spec

    if not layout.has_strings:
        out_tree = Table(tuple(Column(dt, _data_spec(dt), spec)
                               for dt in layout.dtypes))

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec,),
                           out_specs=out_tree, check_vma=False)
        def run(rows):
            return Table(tuple(rc._disassemble_fixed_rows(rows, layout)))

        return jax.jit(run)(result.rows)

    widths = tuple(str_widths)
    nstr = len(widths)
    fixed_specs = tuple(_data_spec(dt) for dt in layout.dtypes
                        if not dt.is_string)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec,),
        out_specs=(fixed_specs, (spec,) * layout.num_columns,
                   (spec,) * (2 * nstr)), check_vma=False)
    def run(rows):
        m = rows.shape[0]
        datas, masks, str_parts = rc.padded_cols_from_rows(
            rows.reshape(-1), layout, widths, m)
        # string offsets are per-device prefix sums — lens concatenate
        # across devices, offsets would not; globalize outside
        lens = [p[1][1:] - p[1][:-1] for p in str_parts]
        chars = [p[0] for p in str_parts]
        return (tuple(d for d in datas if d is not None),
                tuple(masks), tuple(chars) + tuple(lens))

    fixed_datas, masks, str_out = jax.jit(run)(result.rows)
    chars2ds, lens = str_out[:nstr], str_out[nstr:]
    cols = []
    fi = si = 0
    for i, dt in enumerate(layout.dtypes):
        if dt.is_string:
            offsets = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(lens[si]).astype(jnp.int32)])
            cols.append(Column(dt, jnp.zeros((0,), jnp.uint8),
                               masks[i], offsets, None, chars2ds[si]))
            si += 1
        else:
            cols.append(Column(dt, fixed_datas[fi], masks[i]))
            fi += 1
    return Table(tuple(cols))


def fetch_shuffle_result(result: ShuffleResult):
    """Host images of a shuffle result's device leaves — rows blob, slot
    mask, per-device valid counts, overflow flag — in ONE staged D2H
    (``runtime.staging.fetch_arrays``) instead of four separate
    ``np.asarray`` round trips.  This is the decode-side host boundary
    for wire emission / debugging; device-side consumers should keep
    using :func:`decode_shuffle_result`."""
    from spark_rapids_jni_tpu.runtime import staging
    rows, row_valid, num_valid, overflow = staging.fetch_arrays(
        [result.rows, result.row_valid, result.num_valid,
         result.overflow])
    return rows, row_valid, num_valid, overflow
