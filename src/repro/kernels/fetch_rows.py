"""The fetch as a row write: the new server leaves into the fleet's fetched rows.

After a window's apply, every client that fetched holds the new server
parameters: row ``rows[k]`` of each [λ, *row] fleet leaf becomes the
matching [*row] server leaf.  As an XLA scatter this builds K copies of the
leaf and scatters them, serialized because the indices may repeat.  This
kernel reads each server leaf into VMEM once and writes it into each
fetched row: K row writes, no K-copy in HBM.

One launch covers every leaf it is given, over a grid of (chunk, event).
The server leaf's block has one index for all events, so the pipeline
stages it once; the output block of event k is row ``rows[k]`` of the
fleet (scalar prefetch), which the pipeline writes back when the next
event moves to another row.  The fleet is aliased to the output, so it is
updated in place and a row no event names is never touched.  An index ≥ λ
drops that write: the event stays on the row before it, which is then
written back once (`_runs`); with every write dropped, every event is on
row 0, which the kernel writes back from a copy of row 0 read once.  The
kept rows of one leaf must be unique (`engine.last_event_winners`).

A launch splits ``vmem_budget`` evenly among its leaves.  A server leaf
larger than its share is staged in chunks along its leading axis (whole
sublane tiles where that axis is tiled): chunk c of every row is written
in grid row c.  `launches` groups the leaves so that each one's smallest
chunk fits its share, and leaves out a leaf whose smallest chunk does not
fit the budget alone.  ``interpret=True`` runs the same kernel on the CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
import jax.experimental.pallas.tpu as pltpu

# VMEM the kernel's blocks may fill together, under the 16 MiB a TPU
# kernel's scoped VMEM holds by default.
VMEM_BUDGET = 12 << 20


def _sublanes(dtype) -> int:
    return 32 // jnp.dtype(dtype).itemsize


def tiled_bytes(shape, dtype) -> int:
    """Bytes of `shape` in the chip's tiled layout, its minor two dims
    padded to the (sublanes, 128) tile."""
    *lead, r, c = shape
    sub = _sublanes(dtype)
    return (math.prod(lead) * (-(-r // sub) * sub) * (-(-c // 128) * 128)
            * jnp.dtype(dtype).itemsize)


def _unit_bytes(row_shape, dtype) -> int:
    """VMEM bytes of the smallest chunk of a row: one sublane tile of a
    rank-2 row, one slice of its leading axis otherwise."""
    if len(row_shape) == 2:
        return tiled_bytes((_sublanes(dtype), row_shape[1]), dtype)
    return tiled_bytes(row_shape[1:], dtype)


def _chunk_rows(row_shape, dtype, budget: int) -> int:
    """Extent along the row's leading axis of one staged chunk of a leaf:
    the most that fits `budget` bytes of VMEM, in whole sublane tiles
    where that axis is the sublane axis."""
    per_unit = _sublanes(dtype) if len(row_shape) == 2 else 1
    return min(row_shape[0],
               budget // _unit_bytes(row_shape, dtype) * per_unit)


def launches(servers, vmem_budget: int = VMEM_BUDGET) -> list[list[int]]:
    """The leaves `fetch_rows` writes, by their index in `servers`,
    grouped into launches.

    A leaf takes the kernel where its row has rank ≥ 2, so the fleet's λ
    axis is untiled and a row is a whole run of tiles, and its smallest
    chunk's six VMEM buffers fit the budget.  Leaves join a launch in
    order while every leaf's smallest chunk fits its share of the budget.
    """
    groups, group, unit_max = [], [], 0
    for i, s in enumerate(servers):
        unit = (_unit_bytes(s.shape, s.dtype) if s.ndim >= 2
                else vmem_budget)
        if 6 * unit > vmem_budget:
            continue
        if 6 * max(unit_max, unit) * (len(group) + 1) > vmem_budget:
            groups.append(group)
            group, unit_max = [], 0
        group.append(i)
        unit_max = max(unit_max, unit)
    return groups + [group] if group else groups


def _kernel(rows_ref, live_ref, *refs, n_leaves: int):
    fills = refs[:n_leaves]
    servers = refs[n_leaves:2 * n_leaves]
    outs = refs[2 * n_leaves:]
    for i in range(n_leaves):
        @pl.when(live_ref[i] > 0)
        def _(i=i):
            outs[i][...] = servers[i][...]

        @pl.when(live_ref[i] == 0)
        def _(i=i):
            outs[i][...] = fills[i][...]


def _runs(rows, num_rows):
    """The block each grid step writes, and whether any row is kept.

    A dropped write takes the row of the kept write before it (or, before
    the first, after it): consecutive steps on one block write it back
    once, so the kept rows are each written once and nothing else is.
    With every write dropped, every step is on row 0, which the kernel
    writes back as it read it."""
    K = rows.shape[0]
    keep = rows < num_rows
    at = jnp.arange(K)
    before = jax.lax.cummax(jnp.where(keep, at, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(keep))
    live = jnp.any(keep)
    return jnp.where(live, rows[src], 0), live


def fetch_rows(fleets, servers, rows, *, vmem_budget: int = VMEM_BUDGET,
               interpret: bool = False):
    """Write ``servers[i]`` into rows ``rows[i]`` of ``fleets[i]``, each i.

    ``fleets``: [λ, *row_i] arrays, every row of rank ≥ 2; ``servers``:
    the [*row_i] arrays of the same dtypes; ``rows``: one int32 [K] vector
    a leaf, unique below λ, an index ≥ λ dropped.  Returns the fleets,
    written in place where the caller donates them.
    """
    n = len(fleets)
    num_rows = fleets[0].shape[0]
    K = rows[0].shape[0]
    assert all(f.shape[0] == num_rows and f.ndim >= 3 for f in fleets)
    assert all(f.shape[1:] == s.shape and f.dtype == s.dtype
               for f, s in zip(fleets, servers))
    runs, live = zip(*(_runs(r.astype(jnp.int32), num_rows) for r in rows))
    # a leaf's three blocks (row 0 as it was, the server, the written row),
    # each double-buffered by the pipeline; the first two are fetched once
    # a chunk
    share = vmem_budget // (6 * n)
    assert all(_unit_bytes(s.shape, s.dtype) <= share for s in servers), (
        "leaves past their VMEM share: group them by `launches`")
    fill_specs, server_specs, out_specs = [], [], []
    chunks = []
    for i, s in enumerate(servers):
        step = _chunk_rows(s.shape, s.dtype, share)
        last = -(-s.shape[0] // step) - 1
        chunks.append(last + 1)
        block = (None, step) + s.shape[1:]
        tail = (0,) * (s.ndim - 1)

        def chunk(c, last=last):
            return jnp.minimum(c, last)

        def out_row(c, k, rows, i=i, last=last):
            # past the leaf's last chunk, stay on the block written last
            return rows[i * K + jnp.where(c > last, K - 1, k)]

        fill_specs.append(pl.BlockSpec(
            block, lambda c, k, rows, live, chunk=chunk, tail=tail:
            (0, chunk(c)) + tail))
        server_specs.append(pl.BlockSpec(
            block[1:], lambda c, k, rows, live, chunk=chunk, tail=tail:
            (chunk(c),) + tail))
        out_specs.append(pl.BlockSpec(
            block, lambda c, k, rows, live, out_row=out_row, chunk=chunk,
            tail=tail: (out_row(c, k, rows), chunk(c)) + tail))
    written = sum(K * s.size * s.dtype.itemsize for s in servers)
    cost = pl.CostEstimate(
        flops=0, transcendentals=0,
        bytes_accessed=written + 2 * sum(s.size * s.dtype.itemsize
                                         for s in servers))
    return pl.pallas_call(
        functools.partial(_kernel, n_leaves=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(max(chunks), K),
            in_specs=fill_specs + server_specs, out_specs=out_specs),
        out_shape=[jax.ShapeDtypeStruct(f.shape, f.dtype) for f in fleets],
        input_output_aliases={2 + i: i for i in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="fetch_rows",
        cost_estimate=cost,
        interpret=interpret,
    )(jnp.concatenate(runs), jnp.stack(live).astype(jnp.int32), *fleets,
      *servers)
