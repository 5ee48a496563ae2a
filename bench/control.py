"""Readings that set a cell's limits: the program, the control and the faults.

    python3 bench/control.py --workload <name> --seeds 11 12 13

For each seed, in one process: the program's first steps through the
cell's own set-up, then the plain reference, and the numbers of
`bench/check.py` for

- `program`: the program against the reference;
- `control`: the reference in the next precision below the configuration's
  (bfloat16 for the float32 MLP; for the bfloat16 LM, GEMM operands
  rounded to float8 e4m3 with a per-tensor scale) against the reference;
- `half_batch`: the reference with half of each step's events left out and
  the mean taken over the rest, against the reference;
- `fetch_skipped`: the program with the fetch left out of its step (the
  clients keep their copies and timestamps), set up again at the cell's
  own size, against the reference.

A step that returns its state unchanged reads `update_gap` = 1 by
construction and needs no run.  One JSON line per seed and reading goes to
standard output.  The benchmark's own runs never run this.

`planted(fault)` breaks the timed path underneath the drivers; the tests
in `bench/tests/test_faults.py` plant the same faults at smoke size.
"""
import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lower_precision(traffic):
    """Keyword arguments that put the reference one precision lower."""
    import jax
    import jax.numpy as jnp
    if traffic["driver"] == "fred":
        return {"dtype": jnp.bfloat16}
    def fp8(a):
        # per-tensor scaling to e4m3's largest normal, as an fp8 GEMM path
        # would scale its operands; the rounding is passed straight through
        # by the backward pass, whose cotangents would underflow in fp8
        s = jax.lax.stop_gradient(
            jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0)
        q = (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s
        return a + jax.lax.stop_gradient(q - a)
    return {"quant": fp8}


def half(n):
    """Keep the first half of a step's n events."""
    import numpy as np
    return np.arange(n) < n // 2


def _keep_state(keep):
    """A step wrapper that hands back the fields `keep` of the state it was
    given, in place of those the step computed."""
    def wrap(build):
        def built(*a, **k):
            step = build(*a, **k)

            def broken(state, *args):
                new, m = step(state, *args)
                return new._replace(**{f: getattr(state, f)
                                       for f in keep(new)}), m
            return broken
        return built
    return wrap


def _halve(push):
    import jax.numpy as jnp
    return push & (jnp.arange(push.shape[0]) < push.shape[0] // 2)


@contextlib.contextmanager
def planted(fault):
    """Break the timed path for the drivers while the context is open:

    - `state_unchanged`: every step hands back the state it was given;
    - `half_batch`: only the first half of each window's events reaches
      the server, which takes the mean over them;
    - `fetch_skipped`: the clients keep their copies and timestamps.
    """
    from bench.drivers import fred, round as rnd
    from repro.core import engine
    saved = [(fred, "build_step_fn"), (rnd, "build_round_step"),
             (engine, "fused_apply"), (engine, "fused_apply_cotangent")]
    saved = [(mod, name, getattr(mod, name)) for mod, name in saved]
    if fault in ("state_unchanged", "fetch_skipped"):
        keep = ((lambda new: new._fields) if fault == "state_unchanged"
                else (lambda new: ("client_params", "client_ts")))
        fred.build_step_fn = _keep_state(keep)(fred.build_step_fn)
        rnd.build_round_step = _keep_state(keep)(rnd.build_round_step)
    elif fault == "half_batch":
        apply, cot = engine.fused_apply, engine.fused_apply_cotangent
        engine.fused_apply = (lambda scfg, server, grads, push, *a, **k:
                              apply(scfg, server, grads, _halve(push),
                                    *a, **k))
        engine.fused_apply_cotangent = (
            lambda scfg, server, losses, stale, push, *a, **k:
            cot(scfg, server, losses, stale, _halve(push), *a, **k))
    else:
        raise ValueError(f"no fault {fault!r}")
    try:
        yield
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def readings_for(make_cell, traffic):
    """{kind: readings} of one seed's cell, each set up and released here.
    `make_cell()` builds the seed's cell afresh."""
    from bench import check

    def read(rec):
        # each number, and every leaf's gradient and change gap beside it
        return dict(check.readings(rec, ref),
                    grad_leaves=check.leaf_gaps(rec["gbar"], ref["gbar"]),
                    update_leaves=check.leaf_gaps(rec["update"],
                                                  ref["update"]))

    cell = make_cell()
    cell.setup()
    cell.release()
    ref = cell.reference()
    out = {"program": read(cell.program_record())}
    out["control"] = read(cell.reference(**lower_precision(traffic)))
    out["half_batch"] = read(cell.reference(keep=half))
    broken = make_cell()
    with planted("fetch_skipped"):
        broken.setup()
    broken.release()
    ref = broken.reference()
    out["fetch_skipped"] = read(broken.program_record())
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import importlib

    import jax

    from bench.run import cell_spec, load_json
    bench = load_json("BENCHMARK.json")
    _, config, traffic = cell_spec(bench, args.workload)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver = importlib.import_module("bench.drivers." + traffic["driver"])
    for seed in args.seeds:
        make = lambda: driver.Cell(config, traffic, seed)  # noqa: B023
        for kind, r in readings_for(make, traffic).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
