"""The benchmark's one command: run a cell of `BENCHMARK.json` on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The workload names a configuration (`bench/configs/<config>.json`) and a
traffic mix (`bench/traffic/<traffic>.json`); the traffic names its driver
(`bench/drivers/<driver>.py`).  Set-up builds the cell from the seed,
compiles it and runs the first steps that the reference follows; the
window then measures for `--seconds`.  With `--trace 1` a short traced
window follows, and the cell's per-layer metrics are read from it, each by
the reader of its kind, the name's first part (`mfu.lm` →
`bench/metrics/mfu.py`).  Last, the program's state
is freed and the plain reference decides `correct`.

The last line of standard output is the result, one JSON object; the
numbers compared are the last lines of standard error.  Before them,
standard error says where set-up went (compile-cache hits and misses,
seconds spent tracing, lowering and compiling) and how the window's calls
ended in time, so that a slow run can be read from its own output.  Without a TPU, with
fewer chips than the cell asks for, or with `REPRO_KERNEL_INTERPRET` set,
the run exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def cell_spec(bench: dict, workload: str):
    """(workload entry, configuration, traffic) of `workload`."""
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    wl = found[0]
    config = load_json("bench", "configs", wl["config"] + ".json")
    traffic = load_json("bench", "traffic", wl["traffic"] + ".json")
    return wl, config, traffic


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end(bench, workload, values):
    """The cell's end-to-end metrics, from the values the run measured."""
    out = {}
    for m in bench["end_to_end"]:
        if _applies(m, workload):
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(bench, workload, reported, ctx):
    """The cell's per-layer metrics, each read by its kind's reader; a
    reader that finds nothing to read returns None and the metric is left
    out."""
    out = {}
    for m in bench["per_layer"]:
        if not (_applies(m, workload) and m["moves"] in reported):
            continue
        kind = m["name"].split(".")[0]
        path = os.path.join(ROOT, "bench", "metrics", kind + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class CompileLog:
    """Tallies what JAX reports while it compiles, until `close`:
    persistent-cache hits and misses, and the seconds spent tracing,
    lowering and compiling."""

    EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
              "/jax/compilation_cache/cache_misses": "cache_misses"}
    SPANS = {"/jax/core/compile/jaxpr_trace_duration": "trace_s",
             "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
             "/jax/core/compile/backend_compile_duration": "compile_s",
             "/jax/compilation_cache/cache_retrieval_time_sec": "load_s"}

    def __init__(self):
        import jax.monitoring as mon
        self.tally = dict.fromkeys(
            list(self.EVENTS.values()) + list(self.SPANS.values()), 0)
        self._on = True
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._span)

    def _event(self, event, **_):
        if self._on and event in self.EVENTS:
            self.tally[self.EVENTS[event]] += 1

    def _span(self, event, seconds, **_):
        if self._on and event in self.SPANS:
            self.tally[self.SPANS[event]] += seconds

    def close(self) -> dict:
        self._on = False
        return {k: round(v, 3) for k, v in self.tally.items()}


def window_log(ends) -> dict:
    """The window's calls in time: the first five, the median and the
    slowest (with its place), in seconds from one wait's end to the next."""
    import statistics
    gaps = [b - a for a, b in zip([0.0] + ends[:-1], ends)]
    return {"calls": len(gaps), "first": [round(g, 4) for g in gaps[:5]],
            "median": round(statistics.median(gaps), 4),
            "max": round(max(gaps), 4), "argmax": gaps.index(max(gaps))}


def measure(bench, workload, config, traffic, *, seed, seconds, trace,
            interpret=None):
    """Set up, measure, trace, check: the result dict of one run.

    Takes no notice of the platform: `main` refuses anything but a TPU
    before it calls this.  The traced run's profile is deleted once read.
    Besides the result's keys, `log` says where set-up and the window went.
    """
    import jax

    from bench import check, flops
    from bench import trace as tr

    devices = jax.devices()
    kind = devices[0].device_kind
    peaks = flops.peaks(kind) if devices[0].platform == "tpu" else None
    driver = importlib.import_module("bench.drivers." + traffic["driver"])
    cell = driver.Cell(config, traffic, seed, interpret=interpret)
    log = {"before_setup_s": round(time.perf_counter() - T_START, 3)}
    compiles = CompileLog()
    with jax.profiler.TraceAnnotation("setup"):
        cell.setup()
        # set-up's garbage (tracing, lowering) is collected here, and what
        # it keeps is left out of later collections: no collection of
        # set-up's heap stalls the host inside the window
        gc.collect()
        gc.freeze()
    setup_s = time.perf_counter() - T_START
    log["setup"] = compiles.close()
    compiles = CompileLog()
    win = cell.window(seconds)
    log["window"] = dict(window_log(win["ends"]), compiles=compiles.close())
    rate = win["work"] / win["seconds"]
    # the runtime keeps a program's temporaries in a reservation that
    # `peak_bytes_in_use` leaves out: the chip's peak holds both
    stats = [d.memory_stats() or {} for d in devices]
    log["memory"] = stats[0]
    peak_bytes = max(st.get("peak_bytes_in_use", 0)
                     + st.get("peak_bytes_reserved", 0) for st in stats)
    values = {"setup_s": setup_s, "peak_hbm_gib": peak_bytes / 2**30,
              f"{cell.unit}_per_s": rate}
    e2e = end_to_end(bench, workload, values)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    result = {"metrics": e2e, "device": device}
    if trace:
        tdir = os.path.join(ROOT, ".bench_trace", workload)
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(tdir)
        try:
            with jax.profiler.TraceAnnotation(tr.WINDOW):
                cell.traced(jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        red = tr.reduce(tr.find_xplane(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            red=red, rate=rate, peaks=peaks, leaves=cell.leaves,
            events_per_apply=cell.events_per_apply,
            flops_per_unit=cell.flops_per_unit,
            op_time=lambda match: tr.op_time(red, match))
        result["metrics"] = per_layer(bench, workload, set(e2e), ctx)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = tr.breakdown(red)
    gc.unfreeze()
    ok, compared = check.judge(cell.check(), traffic["limits"])
    out = {"correct": ok and win["failed"] == 0,
           "attempted": win["attempted"], "failed": win["failed"]}
    out.update(result)
    out["log"] = log
    out["checks"] = compared
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    wl, config, traffic = cell_spec(bench, args.workload)
    if os.environ.get("REPRO_KERNEL_INTERPRET"):
        print("REPRO_KERNEL_INTERPRET is set: it would take the kernel off "
              "the chip", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"needs {wl['chips']} TPU chip(s), JAX found {devices}",
              file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    # every program of the cell, however quick to compile, comes from the
    # cache after the first run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    out = measure(bench, args.workload, config, traffic, seed=args.seed,
                  seconds=args.seconds, trace=args.trace)
    log = out.pop("log")
    print(f"setup {json.dumps(log['setup'])} before set-up "
          f"{log['before_setup_s']} s", file=sys.stderr)
    print(f"window {json.dumps(log['window'])}", file=sys.stderr)
    print(f"memory {json.dumps(log['memory'])}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the checkout's root in place of this directory, so `bench/trace.py`
    # never shadows the standard library's `trace`
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))
    sys.exit(main())
