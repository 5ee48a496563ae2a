"""Production meshes (TPU v5e).  Functions, not module constants — importing
this module must never touch jax device state (the dry-run forces 512 host
devices *before* any jax init; tests must keep seeing 1 device)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (GSPMD-partitioned placement)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """A small mesh over whatever devices exist (CPU tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = min(model, max(n // data, 1))
    return make_mesh((data, model), ("data", "model"))


def make_server_mesh(server: int = 1, data: int = 1):
    """Mesh carrying the sharded-parameter-server axis (docs/SHARDING.md).

    Axis ``'server'`` (size S, clamped to the available devices) partitions
    the server state — W and the eq. 4–6 statistics — via
    `core.server_shard`; the trailing ``'data'`` axis is free for fleet /
    batch parallelism.  On a single-device CPU, force S simulated devices
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=S`` *before*
    importing jax.
    """
    n = len(jax.devices())
    server = max(1, min(server, n))
    data = max(1, min(data, n // server))
    return make_mesh((server, data), ("server", "data"))


def init_distributed_mesh(server: int = 1, *, coordinator_address=None,
                          num_processes=None, process_id=None):
    """Multi-process (``jax.distributed``) variant of `make_server_mesh`.

    Every participating process calls this with the same arguments; when
    ``coordinator_address`` is given, `jax.distributed.initialize` joins the
    process group first (idempotent if already initialized), and the
    returned mesh spans the *global* device set, so a sharded server (and a
    λ≥100k FRED fleet) can exceed single-host memory.  With no coordinator
    this degrades to the single-process `make_server_mesh` — which is also
    the simulated multi-host path (`XLA_FLAGS`, docs/SHARDING.md recipe).
    """
    if coordinator_address is not None and not jax.distributed.is_initialized():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    return make_server_mesh(server=server)


# Hardware constants for the roofline analysis (TPU v5e, per chip).
PEAK_FLOPS_BF16 = 197e12          # FLOP/s
HBM_BW = 819e9                    # bytes/s
ICI_BW = 50e9                     # bytes/s per link
