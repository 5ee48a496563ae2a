"""Sharded parameter server: partition W / n / b / v across a mesh axis.

Every other subsystem in the repo treats the server state — the canonical
parameters W, the scalar timestamp T, and the eq. 4–6 moving averages
n, b, v — as one replicated pytree; only the [λ, ...] *fleet* arrays shard
(`sim.shard_fleet`).  That caps the server at single-device memory.  This
module removes the cap by partitioning the server itself along a dedicated
``'server'`` mesh axis.

**Why the protocol is shard-ready by construction.**  Per-tensor gating
(§5, `engine.per_tensor_gate`) already gives every parameter leaf an
independent eq.-9 transmit decision drawn against that leaf's own
v̄ = mean(v_leaf), an independent timestamp row (``client_leaf_ts``), and
therefore an independent per-leaf staleness τ.  The eq. 4–6 statistics are
elementwise in the leaf, and every rule's ``scale_leaf`` is broadcastable
``jnp`` ops on (v, τ).  So the server update factorizes over leaves — and
over *blocks* of a leaf — with exactly two cross-leaf couplings:

* the whole-copy eq.-9 gate, whose v̄ is the mean over **all** v leaves
  (`rules.vbar`) — under sharding this becomes one tiny cross-shard mean
  reduction per gate draw;
* the scalar timestamp T, which advances once per server update whichever
  leaves transmitted — T stays a replicated scalar and every shard applies
  the same T increment (bitwise: it is an integer sum of push counts).

**Routing** (`server_leaf_spec`, mirroring `sharding.rules.leaf_param_spec`):
each leaf's **last** dimension divisible by the shard count S is
block-partitioned along the ``'server'`` axis, so each shard holds a 1/S
block of that leaf's W/n/b/v slices; leaves with no divisible dimension
(tiny biases) stay replicated.  A leaf additionally has a single
**owner** shard (`make_shard_plan`, greedy byte-balanced) that accounts for
the leaf's control-plane work — its gate draw, its dedup bookkeeping, its
per-leaf byte counters — so every leaf is assigned to exactly one shard and
byte accounting is conserved (property-tested in
``tests/test_server_shard.py``).

**Equivalence invariant** (pinned by ``tests/test_server_shard.py``): with
``server_shards=1`` the placement is a no-op and every trajectory is
*bitwise* identical to the replicated server; with ``server_shards>1`` the
partitioned apply differs only by floating-point reduction order inside
cross-shard means (the whole-copy v̄, the fused mean-gradient einsum), so
serial-vs-sharded trajectories are allclose for every registry rule.  The
gate RNG streams are placement-independent: every Bernoulli draw consumes
its key whether or not the transmit happens, and per-tensor draws are
keyed per leaf, never per shard.

**Clients on the server's devices** (`client_leaf_spec`,
`check_clients_divide`, `shard_clients`, `route_events`): the round
trainer's C clients, a multiple of the axis size, lie over it too: their
[C]-leading copies, timestamps and batches, C/S whole clients a device.  A
push then routes each client's gradient to the blocks (`route_events`), and a
fetch gathers the blocks back into the copies.

The realized dataflow (push → route → shard-apply → fetch, per-shard
ingress queue and one-kernel launches included) is documented in
``docs/SHARDING.md``.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.engine import Counters

# The dedicated mesh-axis name the server state partitions over; fleet
# arrays keep using the 'clients' axis (`sim.shard_fleet`) — the two
# compose on one mesh, e.g. axes ('clients', 'server').
SERVER_AXIS = "server"


def _path_str(path) -> str:
    """'a/b/0'-style key string for one `tree_flatten_with_path` key path."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:  # pragma: no cover - exotic pytree key types
            parts.append(str(p))
    return "/".join(parts)


def _leaf_nbytes(leaf) -> int:
    """Byte size of one leaf from its static shape/dtype (no device math)."""
    shape = jnp.shape(leaf)
    size = 1
    for d in shape:
        size *= int(d)
    dtype = jnp.result_type(getattr(leaf, "dtype", jnp.float32))
    return size * dtype.itemsize


def mesh_axis_size(mesh, axis: str = SERVER_AXIS) -> int:
    """Size of `axis` on `mesh`, or 0 when the mesh is None / lacks the axis."""
    if mesh is None:
        return 0
    if axis not in getattr(mesh, "axis_names", ()):
        return 0
    return int(mesh.shape[axis])


def server_leaf_spec(shape, num_shards: int, axis: str = SERVER_AXIS,
                     lead: int = 0) -> PartitionSpec:
    """Block-routing spec for one server leaf of static `shape`.

    Mirrors `sharding.rules.leaf_param_spec`: scanning dimensions from the
    last, the first one divisible by `num_shards` carries the ``'server'``
    axis — each shard then holds a contiguous 1/S block of the leaf's
    W/n/b/v slices (eq. 4–6 statistics are elementwise, so a block is a
    self-contained slice of server state).  Leaves with no divisible
    dimension (tiny biases) replicate: P().  ``num_shards <= 1`` always
    replicates, which is what makes the S=1 path bitwise-identical to the
    unsharded server.  `lead` leading dims of `shape` are event or slot axes
    (a [K, *leaf] gradient batch, a [capacity, *leaf] queue payload): they
    stay whole and the rest routes like the leaf.
    """
    if num_shards <= 1:
        return PartitionSpec()
    for dim in range(len(shape) - 1, lead - 1, -1):
        if shape[dim] >= num_shards and shape[dim] % num_shards == 0:
            spec = [None] * len(shape)
            spec[dim] = axis
            return PartitionSpec(*spec)
    return PartitionSpec()


class ServerShardPlan(NamedTuple):
    """The leaf → shard routing table for one server-state pytree.

    Parallel per-leaf tuples (`paths` / `specs` / `owners` / `leaf_bytes`,
    flatten order) plus the byte accounting the benchmark and the routing
    property tests consume.  ``owners[i]`` is the single control-plane home
    of leaf i (its gate draw / dedup / telemetry work); ``specs[i]`` is its
    data-plane block placement.  ``shard_bytes[s]`` counts the block bytes
    resident on shard s; `replicated_bytes` counts the bytes every shard
    carries (non-divisible leaves); their sum per shard is
    ``resident_bytes``.
    """

    num_shards: int
    axis: str
    paths: Tuple[str, ...]
    specs: Tuple[PartitionSpec, ...]
    owners: Tuple[int, ...]
    leaf_bytes: Tuple[int, ...]
    owned_bytes: Tuple[int, ...]       # per shard: Σ bytes of owned leaves
    shard_bytes: Tuple[int, ...]       # per shard: Σ block-partitioned bytes
    replicated_bytes: int              # bytes resident on *every* shard
    total_bytes: int

    def resident_bytes(self, shard: int) -> int:
        """Bytes shard `shard` actually holds: its blocks + the replicas."""
        return self.shard_bytes[shard] + self.replicated_bytes

    @property
    def peak_resident_bytes(self) -> int:
        """Max over shards of `resident_bytes` — the BENCH headline number."""
        return max(self.resident_bytes(s) for s in range(self.num_shards))


def client_leaf_spec(ndim: int, axis: str = SERVER_AXIS) -> PartitionSpec:
    """Spec of a [C]-leading client leaf (a copy, a timestamp, a batch)
    whose client axis lies over `axis`: each device holds C/S whole
    clients, one client a device when C == S."""
    return PartitionSpec(axis, *([None] * (ndim - 1)))


def check_clients_divide(num_clients: int, mesh,
                         axis: str = SERVER_AXIS) -> None:
    """Raise ValueError unless the C clients divide over the S devices of
    `mesh[axis]`: on a sharded server they lie over those devices, C/S
    whole clients a device, and a client cannot straddle two."""
    size = mesh_axis_size(mesh, axis)
    if size > 1 and num_clients % size:
        raise ValueError(
            f"num_round_clients={num_clients} is not a multiple of the "
            f"{size} devices of the {axis!r} axis: the clients lie over the "
            f"server's devices, C/S whole clients a device, and a client "
            f"cannot straddle two")


def shard_clients(tree, mesh, axis: str = SERVER_AXIS):
    """Place every [C]-leading leaf of `tree` with its client axis over
    `mesh[axis]` (`client_leaf_spec`)."""
    return jax.tree.map(lambda l: jax.device_put(l, NamedSharding(
        mesh, client_leaf_spec(jnp.ndim(l), axis))), tree)


def route_events(tree, mesh, axis: str = SERVER_AXIS):
    """Route an event batch to the server's blocks: every [K, *leaf] leaf
    of `tree` constrained to its leaf's `server_leaf_spec` on the trailing
    dims, the K events whole on every device.  Where the events were
    computed on the clients' devices this is the push's exchange."""
    size = mesh_axis_size(mesh, axis)
    return jax.tree.map(lambda g: jax.lax.with_sharding_constraint(
        g, NamedSharding(mesh, server_leaf_spec(jnp.shape(g), size, axis,
                                                lead=1))), tree)


def make_shard_plan(tree, num_shards: int,
                    axis: str = SERVER_AXIS) -> ServerShardPlan:
    """Route every leaf of a server-state pytree to the S shards.

    Data plane: each leaf gets its `server_leaf_spec` block placement.
    Control plane: each leaf gets exactly one **owner** shard by greedy
    byte-balanced assignment (largest leaf first, ties broken by path, to
    the least-loaded shard) — deterministic, so the same pytree always
    routes the same way.  Conservation invariants (property-tested):
    ``sum(owned_bytes) == total_bytes`` and
    ``sum(shard_bytes) + num_shards * replicated_bytes ==
    sum over shards of resident_bytes``.
    """
    assert num_shards >= 1, num_shards
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    entries = [(_path_str(path), jnp.shape(leaf), _leaf_nbytes(leaf))
               for path, leaf in flat]

    owned = [0] * num_shards
    blocks = [0] * num_shards
    replicated = 0
    owners_by_path = {}
    specs_by_path = {}
    for path, shape, nbytes in sorted(
            entries, key=lambda e: (-e[2], e[0])):
        home = min(range(num_shards), key=lambda s: (owned[s], s))
        owners_by_path[path] = home
        owned[home] += nbytes
        spec = server_leaf_spec(shape, num_shards, axis)
        specs_by_path[path] = spec
        if any(a is not None for a in spec):
            # divisibility of the routed dim makes nbytes // S exact
            for s in range(num_shards):
                blocks[s] += nbytes // num_shards
        else:
            replicated += nbytes

    paths = tuple(e[0] for e in entries)
    return ServerShardPlan(
        num_shards=num_shards,
        axis=axis,
        paths=paths,
        specs=tuple(specs_by_path[p] for p in paths),
        owners=tuple(owners_by_path[p] for p in paths),
        leaf_bytes=tuple(e[2] for e in entries),
        owned_bytes=tuple(owned),
        shard_bytes=tuple(blocks),
        replicated_bytes=replicated,
        total_bytes=sum(e[2] for e in entries),
    )


def peak_shard_bytes(tree, num_shards: int, axis: str = SERVER_AXIS) -> float:
    """Peak per-shard resident bytes of `tree` under S-way block routing.

    A static quantity (shapes/dtypes only, no device math) — safe to call
    at trace time inside a jitted step and fold into
    `Counters.shard_bytes_peak` via `count_shard`.  Equals
    ``total_bytes / S`` plus the replicated remainder, the ~1/S shrink the
    BENCH acceptance asserts.
    """
    return float(make_shard_plan(tree, num_shards, axis).peak_resident_bytes)


def shard_tree(tree, mesh, axis: str = SERVER_AXIS, *, batch_dims: int = 0):
    """Place every leaf of `tree` on `mesh` under its block-routing spec.

    `batch_dims` leading dimensions are treated as event/slot axes and left
    unpartitioned (the ingress-queue payload carries leaves shaped
    ``[capacity, *leaf]`` — the *leaf* dims route exactly like the live
    server state, so a queued gradient block already lives with the shard
    that will apply it).  None passes through (optional carries).
    """
    if tree is None:
        return None
    num_shards = mesh_axis_size(mesh, axis)

    def put(leaf):
        spec = server_leaf_spec(jnp.shape(leaf), num_shards, axis,
                                lead=batch_dims)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def shard_server_state(server, mesh, axis: str = SERVER_AXIS):
    """Partition a `rules.ServerState` across `mesh[axis]`.

    W, n, b, v (and any params-shaped rule-private `extra` leaves, e.g.
    gap's ĝ EMA or ssgd's pending buffer) are block-routed per
    `server_leaf_spec`; the scalar timestamp T and scalar extras replicate
    (`server_leaf_spec` maps shape () to P()).  When the mesh lacks the
    axis or it has size 1 the state is returned unplaced — the bitwise
    S=1 contract.
    """
    if mesh_axis_size(mesh, axis) <= 1:
        return server
    return shard_tree(server, mesh, axis)


def shard_queue_state(queue, mesh, axis: str = SERVER_AXIS):
    """Partition the ingress queue's payload across `mesh[axis]`.

    Only the heavy payload pytree (leaves ``[capacity, *leaf]``) routes —
    each slot's gradient blocks land on the shard that owns those blocks,
    making the PR 6 ring buffer per-shard in exactly the sense the live
    server state is.  The [capacity] slot bookkeeping (ts / client / enq_T)
    and the head/size scalars are tiny control-plane state and stay
    replicated.  None (no queue configured) passes through.
    """
    if queue is None or mesh_axis_size(mesh, axis) <= 1:
        return queue
    return queue._replace(
        payload=shard_tree(queue.payload, mesh, axis, batch_dims=1))


def count_shard(counters: Counters, *, applies, events, bytes_peak,
                depth_peak) -> Counters:
    """Fold one partitioned apply window into the `shard_*` Counters fields.

    `applies` counts server apply windows run against the partitioned
    state, `events` the gradient events those windows consumed,
    `bytes_peak` the max-over-shards resident server-state bytes (a static
    `peak_shard_bytes` value; max-folded so re-folding is idempotent), and
    `depth_peak` the largest per-window event batch any shard was asked to
    apply (max-folded).  The fields are filtered from `run_simulation`
    output when ``server_shards <= 1``, keeping the goldens byte-stable —
    the same contract as the ``queue_*`` / ``scenario_*`` / ``kernel_*``
    groups.
    """
    return counters._replace(
        shard_applies=counters.shard_applies + jnp.asarray(applies,
                                                           jnp.int32),
        shard_events=counters.shard_events + jnp.asarray(events, jnp.int32),
        shard_bytes_peak=jnp.maximum(
            counters.shard_bytes_peak,
            jnp.asarray(bytes_peak, jnp.float32)),
        shard_depth_peak=jnp.maximum(
            counters.shard_depth_peak,
            jnp.asarray(depth_peak, jnp.int32)),
    )


def validate_server_mesh(mesh, num_shards: int,
                         axis: str = SERVER_AXIS) -> None:
    """Raise ValueError unless `mesh` carries a size-`num_shards` `axis`.

    Called by both consumers before placing state, so a mis-sized mesh
    fails loudly at setup instead of silently replicating.
    """
    size = mesh_axis_size(mesh, axis)
    if size != num_shards:
        raise ValueError(
            f"server_shards={num_shards} requires a mesh with a "
            f"{axis!r} axis of exactly that size; got "
            f"{'no mesh' if mesh is None else f'axis size {size}'} — build "
            f"one with launch.mesh.make_server_mesh(server={num_shards}) "
            f"(simulated multi-device CPU via "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N)")


__all__ = [
    "SERVER_AXIS",
    "ServerShardPlan",
    "check_clients_divide",
    "client_leaf_spec",
    "count_shard",
    "make_shard_plan",
    "mesh_axis_size",
    "peak_shard_bytes",
    "route_events",
    "server_leaf_spec",
    "shard_clients",
    "shard_queue_state",
    "shard_server_state",
    "shard_tree",
    "validate_server_mesh",
]
