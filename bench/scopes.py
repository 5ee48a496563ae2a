"""Device time by protocol layer, and idle time put down to the runtime's
launch, from a profiler trace (`.xplane.pb`).

The program names its layers with `jax.named_scope` (`SCOPES`).  XLA keeps
the name stack as each instruction's `op_name` metadata, and the profiler
writes it into the `tf_op` stat of the op's event metadata on the device
plane, e.g. `jit(round_step)/dispatch/client_grad/vmap(transpose(jvp()))/
dot_general`.  An op belongs to the innermost scope on its path; the
clients' gradient splits into forward and backward (`client_fwd`,
`client_bwd`) by whether the path holds `transpose(`; an op on no scope
(layout copies XLA inserts, the scan's own loop) goes to `other`.
`jax.profiler.ProfileData` shows event stats but not those of event
metadata, so this module decodes the few fields of `xplane.proto` it needs
itself.

The window, busy time and clipping are those of `bench/trace.py`: the host
span `bench.window`, the union of the `XLA Ops` intervals inside it, and
container ops (`while`, ...) left out of the op sums.  The launch idle
time is the part of the window in which the device ran no op while the
host was inside the runtime's launch of a call
(`PJRT_LoadedExecutable_Execute`); the runtime events within the launches
that cover most of it name where the launch spent that time.

Run as a script on a TPU, it sets up one cell of `BENCHMARK.json` as
`bench/run.py` does, traces the cell's traced window and prints one JSON
line: each layer's device time per unit of work (µs a FRED window, ms an
LM round), the launch idle share, and the seconds the reduction took:

    python3 bench/scopes.py --workload <name> --seed <n> [--windows <w>]
        [--keep <path.xplane.pb.gz>]

`--windows` traces one span of w windows in a FRED cell, for a small trace
(each span also copies the fleet in and out: 21 ms, which a cell's spans
of 256 windows spread thin); `--keep` keeps the trace, gzipped.  JAX keys
its persistent compilation cache on the program without its debug
information, and the scopes are debug information: the script puts them in
the key, or an executable compiled before the scopes existed would be
served and its trace would carry none.
"""
from __future__ import annotations

import collections
import re
import struct
import sys

if __name__ == "__main__":
    import os
    # the checkout's root in place of this directory, so `bench/trace.py`
    # never shadows the standard library's `trace`
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = _ROOT
    sys.path.insert(1, os.path.join(_ROOT, "src"))

from bench import trace  # noqa: E402

# The program's scopes, one a protocol layer (src/repro: sim/fred.py,
# core/round_trainer.py, core/engine.py, kernels/ops.py).
SCOPES = ("dispatch", "minibatch", "stale_gather", "client_grad",
          "server_apply", "apply_pack", "fetch_scatter", "fetch_refresh")
OTHER = "other"
LAUNCH = "PJRT_LoadedExecutable_Execute"
TF_OP = "tf_op"
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def layer_of(tf_op: str) -> str:
    """The layer of a device op with JAX path `tf_op`: its innermost scope,
    `client_grad` split into `client_fwd` and `client_bwd`.  An op XLA
    merged from several carries their paths joined by `;`: the last
    scope named counts."""
    found = [w for w in _WORD.findall(tf_op) if w in SCOPES]
    if not found:
        return OTHER
    if found[-1] == "client_grad":
        return "client_bwd" if "transpose(" in tf_op else "client_fwd"
    return found[-1]


# --- xplane.proto, the fields read here --------------------------------------

# An event as `jax.profiler.ProfileData` gives it, with the `tf_op` of its
# metadata ("" where it has none).
Event = collections.namedtuple("Event", "name start_ns duration_ns tf_op")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")


def _fields(buf: memoryview):
    """(field number, value) of a protobuf message: ints for varints and
    fixed-width fields, memoryviews for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            value, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _stat_string(stat, stat_names):
    """The string an `XStat` holds, inline or by reference, else None."""
    for f, v in _fields(stat):
        if f == 5:
            return _text(v)
        if f == 7:
            return stat_names.get(v)
    return None


def _plane(buf) -> Plane:
    name, raw_lines, meta, stat_names = "", [], {}, {}
    for f, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            raw_lines.append(v)
        elif f in (4, 5):                  # map entries: key 1, value 2
            entry = dict(_fields(v))
            if 2 in entry:
                meta.setdefault(f, {})[entry.get(1, 0)] = entry[2]
    for mid, m in meta.get(5, {}).items():          # XStatMetadata
        stat_names[mid] = _text(dict(_fields(m)).get(2, b""))
    tf_op_id = next((k for k, v in stat_names.items() if v == TF_OP), None)
    events_meta = {}
    for mid, m in meta.get(4, {}).items():          # XEventMetadata
        ev_name, tf_op = "", ""
        for f, v in _fields(m):
            if f == 2:
                ev_name = _text(v)
            elif f == 5 and tf_op_id is not None:
                stat = v
                if dict(_fields(stat)).get(1) == tf_op_id:
                    tf_op = _stat_string(stat, stat_names) or ""
        events_meta[mid] = (ev_name, tf_op)
    lines = []
    for raw in raw_lines:
        line_name, t0, events = "", 0, []
        for f, v in _fields(raw):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        out = []
        for ev in events:
            mid = offset_ps = duration_ps = 0
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = v
                elif f == 3:
                    duration_ps = v
            ev_name, tf_op = events_meta.get(mid, ("", ""))
            # whole nanoseconds, as `jax.profiler.ProfileData` gives them
            out.append(Event(ev_name, float(t0 + offset_ps // 1000),
                             float(duration_ps // 1000), tf_op))
        lines.append(Line(line_name, out))
    return Plane(name, lines)


def decode(xspace: bytes) -> list:
    """The planes of a serialized `XSpace`, shaped as `trace.reduce_planes`
    reads them, each event with its `tf_op`."""
    return [_plane(v) for f, v in _fields(memoryview(xspace)) if f == 1]


def read(path: str) -> list:
    """`decode` of the trace file at `path` (gzipped if `.gz`)."""
    import gzip
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return decode(f.read())


# --- the reduction ------------------------------------------------------------

def _measure(intervals):
    return sum(e - s for s, e in intervals)


def _intersect(a, b):
    """The overlap of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append([s, e])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(events):
    """[(start, end, name), ...] cut where any event starts or ends, each
    piece named by the innermost event open over it: the latest to start,
    then the shortest."""
    cuts = sorted({t for s, e, _ in events for t in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(s, -e, name) for s, e, name in events if s <= a and b <= e]
        if over:
            out.append((a, b, max(over)[2]))
    return out


def reduce_scopes(planes, top: int = 3) -> dict:
    """Device seconds by layer, and the launch idle time, of a trace.

    Returns `trace.reduce_planes`'s dict with, besides: "layers" {layer:
    seconds}, the mean over the devices that ran any op; "launch_idle_s",
    the idle time inside the host's launches, the mean over those devices;
    and "launch_events" [[name, seconds], ...], the `top` runtime events
    that hold most of that time, each piece of a launch put down to the
    innermost event open over it on the launching thread.
    """
    red = trace.reduce_planes(planes)
    host = [line for p in planes if p.name.startswith("/host:")
            for line in p.lines]
    w0, w1 = next((ev.start_ns, ev.start_ns + ev.duration_ns)
                  for line in host for ev in line.events
                  if ev.name == trace.WINDOW)
    span = lambda ev: [max(ev.start_ns, w0),
                       min(ev.start_ns + ev.duration_ns, w1)]
    # the harness's annotations and the runtime's events of one thread
    # come on separate lines (`python3`, `main/<tid>`): the launches are
    # those of every host line, and within them the time goes to the
    # innermost runtime event open on the launch's own line
    launched, pieces = [], []
    for line in host:
        mine = [span(ev) for ev in line.events if ev.name == LAUNCH]
        mine = [iv for iv in mine if iv[1] > iv[0]]
        launched += mine
        held = [(max(ev.start_ns, s), min(ev.start_ns + ev.duration_ns, e),
                 ev.name) for ev in line.events for s, e in mine
                if ev.start_ns < e and ev.start_ns + ev.duration_ns > s]
        pieces += _innermost(held)
    launches = trace._union(launched)

    layers = collections.defaultdict(float)
    idle_in_launch = []
    for p in planes:
        if not p.name.startswith("/device:"):
            continue
        spans = []
        for line in p.lines:
            if line.name != trace.OPS_LINE:
                continue
            for ev in line.events:
                s, e = span(ev)
                if e <= s:
                    continue
                spans.append((s, e))
                if trace.opcode(ev.name) not in trace.CONTAINERS:
                    layers[layer_of(ev.tf_op)] += (e - s) * 1e-9
        if not spans:
            continue
        edges = [w0] + [x for iv in trace._union(spans) for x in iv] + [w1]
        idle = [[s, e] for s, e in zip(edges[::2], edges[1::2]) if e > s]
        idle_in_launch.append(_intersect(idle, launches))

    devices = len(idle_in_launch)
    covered = collections.defaultdict(float)
    for gaps in idle_in_launch:
        for a, b, name in pieces:
            covered[name] += _measure(_intersect(gaps, [[a, b]]))
    ranked = sorted(covered.items(), key=lambda kv: -kv[1])[:top]
    red.update(
        layers={k: v / devices for k, v in layers.items()},
        launch_idle_s=sum(_measure(g) for g in idle_in_launch)
        * 1e-9 / devices,
        launch_events=[[n, t * 1e-9 / devices] for n, t in ranked if t > 0])
    return red


# --- per unit of work -----------------------------------------------------------

# The unit of work a cell's layer times are given per: (program, unit,
# scale from seconds, the cell's size of one unit in its own work).
UNITS = {"events": ("fred", "us/window", 1e6, lambda cell: cell.K),
         "tokens": ("lm", "ms/round", 1e3,
                    lambda cell: cell.tokens_per_round)}


def per_unit(red: dict, program: str, units: float, scale: float) -> dict:
    """`busy.<program>.<layer>` device time per unit of work, in the unit
    that `scale` converts seconds to, and `idle_dispatch.<program>`, the
    launch idle time as a share of the window, in %.  A trace in which no
    op carries a scope gives no layer."""
    out = {}
    if set(red["layers"]) - {OTHER}:
        for layer, seconds in sorted(red["layers"].items()):
            out[f"busy.{program}.{layer}"] = seconds * scale / units
    out[f"idle_dispatch.{program}"] = (
        100.0 * red["launch_idle_s"] / red["window_s"])
    return out


# --- the script -------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    import gzip
    import importlib
    import json
    import os
    import shutil
    import time

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--windows", type=int, default=None,
                    help="trace one span of this many windows (FRED)")
    ap.add_argument("--keep", default=None,
                    help="keep the trace here, gzipped")
    args = ap.parse_args(argv)

    from bench import run
    bench = run.load_json("BENCHMARK.json")
    _, config, traffic = run.cell_spec(bench, args.workload)
    if args.windows is not None:
        traffic = dict(traffic, windows_per_span=args.windows,
                       traced_spans=1)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"needs a TPU chip, JAX found {devices}", file=sys.stderr)
        return 2
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        run.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

    driver = importlib.import_module("bench.drivers." + traffic["driver"])
    cell = driver.Cell(config, traffic, args.seed)
    cell.setup()
    tdir = os.path.join(run.ROOT, ".bench_trace", "scopes." + args.workload)
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            done = cell.traced(jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    path = trace.find_xplane(tdir)
    t0 = time.perf_counter()
    red = reduce_scopes(read(path))
    reduce_s = time.perf_counter() - t0
    if args.keep:
        with open(path, "rb") as src, gzip.open(args.keep, "wb") as dst:
            shutil.copyfileobj(src, dst)
    shutil.rmtree(tdir, ignore_errors=True)

    program, unit, scale, size = UNITS[cell.unit]
    units = done["work"] / size(cell)
    metrics = per_unit(red, program, units, scale)
    for name, seconds in red["launch_events"]:
        print(f"launch idle under {name!r}: {seconds!r} s", file=sys.stderr)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "units": units, "unit": unit, "window_s": red["window_s"],
        "busy_s": red["busy_s"],
        "busy_per_unit": red["busy_s"] * scale / units,
        "metrics": metrics, "launch_events": red["launch_events"],
        "reduce_s": reduce_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
