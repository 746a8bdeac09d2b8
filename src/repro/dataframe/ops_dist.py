"""Distributed dataframe operators: shuffle / sort / join / groupby /
reduce over the mesh, built on ``shard_map`` + ``jax.lax`` collectives.

This is Cylon's distributed-operator set re-founded on the TPU network:
``all_to_all`` plays MPI_Alltoall (shuffle), ``all_gather`` serves splitter
exchange (sample sort), ``psum`` serves reductions.  Static-shape semantics:
every worker sends a fixed-capacity bucket to every other worker; overflow
rows are dropped and *counted* (returned so callers/tests can assert zero).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dataframe import ops_local as L
from repro.dataframe.table import Table


def _specs_for(table: Table):
    return {k: P(table.axis) if v.ndim == 1 else P(table.axis, *([None] * (v.ndim - 1)))
            for k, v in table.columns.items()}


def _bucket_exchange(cols: Dict, valid, dest: jnp.ndarray, axis: str, cap: int):
    """Per-shard: route rows to destination shards with per-dest capacity
    ``cap``; returns received (cols, valid, n_dropped)."""
    PIDX = jax.lax.axis_size(axis)
    # position of each row within its destination bucket
    onehot = jax.nn.one_hot(jnp.where(valid, dest, PIDX), PIDX + 1, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=-1)
    keep = valid & (pos < cap)
    dropped = jnp.sum(valid & ~keep)
    slot = jnp.where(keep, dest * cap + pos, PIDX * cap)  # sentinel slot

    def scatter(col):
        buf_shape = (PIDX * cap + 1,) + col.shape[1:]
        buf = jnp.zeros(buf_shape, col.dtype)
        return buf.at[slot].set(jnp.where(
            keep.reshape((-1,) + (1,) * (col.ndim - 1)), col, 0), mode="drop")[:-1]

    sent = {k: scatter(v) for k, v in cols.items()}
    sent_valid = jnp.zeros((PIDX * cap + 1,), bool).at[slot].set(keep, mode="drop")[:-1]

    def a2a(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True)

    recv = {k: a2a(v) for k, v in sent.items()}
    recv_valid = a2a(sent_valid)
    total_dropped = jax.lax.psum(dropped, axis)
    return recv, recv_valid, total_dropped


def _wrap(table: Table, fn, extra_tables: Sequence[Table] = (), **out_extra):
    """Run fn under shard_map over the table's mesh axis."""
    mesh = table.mesh
    axis = table.axis
    in_specs = []
    args = []
    for t in (table, *extra_tables):
        in_specs.append((_specs_for(t), P(axis)))
        args.append((t.columns, t.valid))
    return mesh, axis, in_specs, args


def shuffle(table: Table, key: str, *, capacity_factor: float = 2.0):
    """Hash-partition rows by key (Cylon shuffle). Equal keys co-locate."""
    mesh, axis = table.mesh, table.axis
    nshards = mesh.shape[axis]
    per = table.num_rows // nshards
    cap = max(int(per / nshards * capacity_factor), 16)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_specs_for(table), P(axis)),
        out_specs=(_specs_for(table), P(axis), P()),
    )
    def _shuf(cols, valid):
        dest = (L.hash_u32(cols[key]) % jnp.uint32(jax.lax.axis_size(axis))).astype(jnp.int32)
        recv, rvalid, dropped = _bucket_exchange(cols, valid, dest, axis, cap)
        return recv, rvalid, dropped[None]

    cols, valid, dropped = _shuf(table.columns, table.valid)
    out = Table(cols, valid, mesh, axis)
    return out, int(dropped[0])


def sort(table: Table, key: str, *, capacity_factor: float = 2.5,
         oversample: int = 8):
    """Distributed sample sort: local sort -> splitter sampling
    (all_gather) -> range partition (all_to_all) -> local merge."""
    mesh, axis = table.mesh, table.axis
    nshards = mesh.shape[axis]
    per = table.num_rows // nshards
    cap = max(int(per * capacity_factor / nshards), 16)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_specs_for(table), P(axis)),
        out_specs=(_specs_for(table), P(axis), P()),
    )
    def _sort(cols, valid):
        nsh = jax.lax.axis_size(axis)
        cols, valid = L.sort_by_key(cols, valid, key)
        keys = cols[key]
        big = jnp.iinfo(keys.dtype).max
        eff = jnp.where(valid, keys, big)
        # sample oversample*nshards candidates per shard
        n = keys.shape[0]
        idx = jnp.linspace(0, n - 1, oversample * nsh).astype(jnp.int32)
        samples = jnp.take(eff, idx)
        all_samples = jax.lax.all_gather(samples, axis, tiled=True)
        all_samples = jnp.sort(all_samples)
        m = all_samples.shape[0]
        splitters = jnp.take(
            all_samples, ((jnp.arange(1, nsh)) * m // nsh).astype(jnp.int32)
        )
        dest = jnp.searchsorted(splitters, eff, side="right").astype(jnp.int32)
        dest = jnp.clip(dest, 0, nsh - 1)
        recv, rvalid, dropped = _bucket_exchange(cols, valid, dest, axis, cap)
        recv, rvalid = L.sort_by_key(recv, rvalid, key)
        return recv, rvalid, dropped[None]

    cols, valid, dropped = _sort(table.columns, table.valid)
    return Table(cols, valid, mesh, axis), int(dropped[0])


def join(left: Table, right: Table, key: str, *, capacity_factor: float = 2.0):
    """Distributed hash join: co-partition both sides by key hash, then
    local join (right side = build side, at-most-one match per left row)."""
    mesh, axis = left.mesh, left.axis
    nshards = mesh.shape[axis]
    capL = max(int(left.num_rows // nshards / nshards * capacity_factor), 16)
    capR = max(int(right.num_rows // nshards / nshards * capacity_factor), 16)

    out_cols_proto = dict(left.columns)
    for k in right.columns:
        if k != key:
            out_cols_proto[k if k not in left.columns else k + "_r"] = right.columns[k]
    out_spec = {k: P(axis) if v.ndim == 1 else P(axis, *([None] * (v.ndim - 1)))
                for k, v in out_cols_proto.items()}

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_specs_for(left), P(axis), _specs_for(right), P(axis)),
        out_specs=(out_spec, P(axis), P()),
    )
    def _join(lc, lv, rc, rv):
        nsh = jax.lax.axis_size(axis)
        ldest = (L.hash_u32(lc[key]) % jnp.uint32(nsh)).astype(jnp.int32)
        rdest = (L.hash_u32(rc[key]) % jnp.uint32(nsh)).astype(jnp.int32)
        lrecv, lrv, ldrop = _bucket_exchange(lc, lv, ldest, axis, capL)
        rrecv, rrv, rdrop = _bucket_exchange(rc, rv, rdest, axis, capR)
        out, ov = L.local_hash_join(lrecv, lrv, rrecv, rrv, key)
        return out, ov, (ldrop + rdrop)[None]

    cols, valid, dropped = _join(left.columns, left.valid, right.columns, right.valid)
    return Table(cols, valid, mesh, axis), int(dropped[0])


def groupby_sum(table: Table, key: str, value_cols: Sequence[str],
                *, groups_cap_per_shard: int = 4096):
    """Distributed group-by-sum: shuffle by key, then local segment-sum."""
    shuffled, dropped = shuffle(table, key)
    mesh, axis = table.mesh, table.axis

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_specs_for(shuffled), P(axis)),
        out_specs=(P(axis), {c: P(axis) for c in value_cols}, P(axis)),
    )
    def _gb(cols, valid):
        k, sums, count = L.local_groupby_sum(cols, valid, key, value_cols,
                                             groups_cap_per_shard)
        return k, sums, count

    keys, sums, count = _gb(shuffled.columns, shuffled.valid)
    cols = {key: keys, **sums, "_count": count}
    return Table(cols, count > 0, mesh, axis), dropped


def reduce_sum(table: Table, cols: Sequence[str]) -> Dict[str, float]:
    mesh, axis = table.mesh, table.axis

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(_specs_for(table.project(list(cols))), P(axis)),
        out_specs={c: P() for c in cols},
    )
    def _red(c, valid):
        return {k: jax.lax.psum(jnp.sum(jnp.where(valid, v, 0)), axis)[None]
                for k, v in c.items()}

    out = _red(table.project(list(cols)).columns, table.valid)
    return {k: float(v[0]) for k, v in out.items()}
