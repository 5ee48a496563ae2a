"""The comparison that decides `correct` for a training cell.

Three numbers, each against a limit of its own kept with the cell's
traffic (`limits`):

- `loss_gap`: over the first three steps, the largest relative gap between
  the program's step loss and the reference's;
- `grad_gap`: the mean gradient of the first step, as the optimizer got it
  (read back from its first-moment statistic b = (1 − γ)·ḡ), by the worst
  leaf: |‖ḡ‖ − ‖ḡ_ref‖| over the larger of the reference's norm of that
  leaf and of the median leaf;
- `update_gap`: the parameters' change over the three steps, ‖θ₃ − θ₀‖,
  by the worst leaf in the same way.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of `update_gap`: their change is round-off alone.

Where the records carry a fleet (the copies that the clients which fired
hold after the three steps, and the timestamps of those copies), two more:

- `fetch_gap`: by the worst client and leaf, ‖copy − reference copy‖ over
  how far the reference copy moved from the start, ‖reference copy − θ₀‖
  (or the median leaf's move, where that is larger).  A copy the fetch
  never wrote reads 1;
- `stale_ts`: how many of those copies carry another timestamp than the
  reference's.  An exact comparison.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEGLIGIBLE = 1e-3


def leaf_gaps(prog, ref):
    """Every leaf's |prog − ref| / max(ref_leaf, median(ref))."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref)
    return [float(abs(p - r) / max(r, floor)) for p, r in zip(prog, ref)]


def leaf_gap(prog, ref, skip=()):
    """The worst leaf's gap (`leaf_gaps`), leaving out the leaves `skip`."""
    return max(g for i, g in enumerate(leaf_gaps(prog, ref))
               if i not in set(skip))


def loss_gap(prog, ref):
    """Largest relative gap between the steps' losses."""
    return float(max(abs(p - r) / abs(r) for p, r in zip(prog, ref)))


def negligible_leaves(ref_grad_norms):
    """Leaves whose reference gradient is nought to rounding."""
    g = np.asarray(ref_grad_norms, np.float64)
    med = np.median(g)
    return [i for i, x in enumerate(g) if x < NEGLIGIBLE * med]


def fetch_gap(prog, ref, base):
    """Worst ‖prog − ref‖ / max(‖ref − base‖, the median leaf's) over the
    clients and leaves; `prog` and `ref` are leaf lists [n, ...], `base`
    the leaves [...] the copies started from."""
    prog, ref, base = (jax.tree.leaves(t) for t in (prog, ref, base))
    norm = lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                      axis=tuple(range(1, a.ndim))))
    diff = jnp.stack([norm(jnp.asarray(p) - r) for p, r in zip(prog, ref)])
    moved = jnp.stack([norm(r - b[None]) for r, b in zip(ref, base)])
    floor = jnp.median(moved, axis=0, keepdims=True)
    return float(jnp.max(diff / jnp.maximum(jnp.maximum(moved, floor),
                                             1e-30)))


def readings(prog, ref):
    """The numbers from the program's and the reference's records, each
    {"losses": [3], "gbar": [leaf norms], "update": [leaf norms]} and,
    where a cell checks its fleet, "copies" and "ts" (and in the
    reference's, "base")."""
    skip = negligible_leaves(ref["gbar"])
    out = {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": leaf_gap(prog["gbar"], ref["gbar"]),
        "update_gap": leaf_gap(prog["update"], ref["update"], skip),
    }
    if "copies" in ref:
        out["fetch_gap"] = fetch_gap(prog["copies"], ref["copies"],
                                     ref["base"])
        out["stale_ts"] = int(np.sum(np.asarray(prog["ts"])
                                     != np.asarray(ref["ts"])))
    return out


def judge(values, limits):
    """(correct, {name: {"value", "limit"}}): every number under its limit,
    and finite."""
    out = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    ok = all(np.isfinite(v) and v <= limits[k] for k, v in values.items())
    return ok, out
