"""The yardstick's arithmetic: required operations, kernel bytes, chip peaks.

Everything here is computed from shapes and dtypes, never measured, so a
later change to the program cannot move it.  Counts follow what the model
requires, not what an implementation happens to execute: recomputation
(remat), the duplicate stale-offset GEMM of the shared/delta split and
padding do not count.
"""
from __future__ import annotations

import math

# Published peaks of one chip, keyed by `jax.Device.device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}
PEAKS_SOURCE = "Google Cloud documentation, TPU v5e"


def peaks(device_kind: str) -> dict:
    """The peak table's row for `device_kind`; an unknown chip is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/flops.py PEAKS with their source")
    return PEAKS[device_kind]


def mlp_matmul_params(sizes) -> int:
    """Weights that take part in a GEMM: Σ d_in·d_out over the layers."""
    return sum(a * b for a, b in zip(sizes[:-1], sizes[1:]))


def mlp_train_flops_per_event(sizes, batch: int) -> float:
    """Forward and backward of one client event on `batch` samples:
    6 operations per GEMM weight per sample (2 forward, 4 backward)."""
    return 6.0 * mlp_matmul_params(sizes) * batch


def mamba2_matmul_params(cfg: dict) -> int:
    """GEMM weights of the Mamba2 LM per token: in_proj and out_proj of
    every layer plus the unembedding, at the published (unpadded) vocabulary.
    The embedding is a gather and the conv is depthwise: neither counts."""
    d, di, N = cfg["d_model"], cfg["expand"] * cfg["d_model"], cfg["d_state"]
    H = di // cfg["headdim"]
    in_proj = d * (2 * di + 2 * N + H)       # z, x, B, C, dt
    out_proj = di * d
    return cfg["n_layer"] * (in_proj + out_proj) + d * cfg["vocab_size"]


def ssd_flops_per_token(cfg: dict) -> float:
    """Forward operations of one SSD layer per token in the chunked form
    (arXiv:2405.21060 §6), chunk Q, state N, H heads of size P, one group:
    C·Bᵀ scores within the chunk (2QN), scores times inputs (2QHP), the
    chunk state Bᵀx (2NHP) and the state's output C·h (2NHP)."""
    Q, N = cfg["chunk_size"], cfg["d_state"]
    P = cfg["headdim"]
    H = cfg["expand"] * cfg["d_model"] // P
    return 2.0 * Q * N + 2.0 * Q * H * P + 4.0 * N * H * P


def mamba2_train_flops_per_token(cfg: dict) -> float:
    """Forward and backward operations the Mamba2 LM requires per token:
    6 per GEMM weight, plus 3× the SSD's forward terms in every layer."""
    return (6.0 * mamba2_matmul_params(cfg)
            + 3.0 * cfg["n_layer"] * ssd_flops_per_token(cfg))


def apply_kernel_bytes(leaves, num_events: int) -> int:
    """HBM bytes the one-kernel server apply must move for one window.

    `leaves` is a list of (shape, dtype itemsize) of the server's parameter
    leaves, unpadded.  Per leaf the kernel reads θ and the K gradients in
    the parameters' dtype and n, b, v in float32, and writes θ' in the
    parameters' dtype and n', b', v' in float32: (2 + K)·P·s + 6·P·4.  For a
    float32 leaf that is the (K + 8)·P·4 of the kernel's own docstring.
    Padding to (rows, 128) tiles is not counted, so it shows as lost share.
    """
    total = 0
    for shape, itemsize in leaves:
        p = math.prod(shape)
        total += (2 + num_events) * p * itemsize + 6 * p * 4
    return total


# The one-kernel apply in a device trace.  The program's `pallas_call` has
# no `name=`, so the trace shows it as `%body.<n> = ... custom-call(...)`
# with this target; on the paths the cells run it is the only Mosaic kernel.
APPLY_KERNEL = 'custom_call_target="tpu_custom_call"'


def apply_roofline(ctx):
    """HBM-roofline share (%) of the apply kernel in a traced window, or
    None when no launch of it ran there.  `ctx` carries the trace's
    `op_time`, the server's `leaves`, `events_per_apply` and `peaks`."""
    seconds, launches = ctx.op_time(lambda name: APPLY_KERNEL in name)
    if not launches:
        return None
    windows = launches / len(ctx.leaves)
    moved = apply_kernel_bytes(ctx.leaves, ctx.events_per_apply) * windows
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / seconds
