"""Reduce a profiler trace (`.xplane.pb`) to device busy time, op times and
idle gaps.

The window is the host span `bench.window` that the harness opens around
the traced calls.  A device's busy time is the union of the intervals in
which an op of its `XLA Ops` line ran inside that window; `busy_s` is the
mean over the devices that ran any op.  An idle gap is a stretch of the
window in which no op ran on a device, named by the harness span (`span`,
`round`, `sync`, `setup`) that was open on the host at its middle, or
`none`.
"""
from __future__ import annotations

import collections
import glob
import gzip
import os
import re

WINDOW = "bench.window"
HOST_TAGS = ("span", "round", "sync", "setup")
OPS_LINE = "XLA Ops"
# ops that only contain other ops of the same line: their time is their
# body's, so they count towards busy time but not as ops of their own
CONTAINERS = ("while", "conditional", "call")
_HLO = re.compile(r"%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The HLO opcode of a trace op's name (`%x = type opcode(...)`)."""
    m = _HLO.match(name)
    return m.group(3) if m else ""


def short_name(name: str) -> str:
    """`<instruction> <opcode> <result type>` of a trace op's name, with
    layouts dropped and the type cut to 80 characters."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    kind = re.sub(r"\{[^{}]*\}", "", m.group(2))[:80]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    op = m.group(3) + (f" {target.group(1)}" if target else "")
    return f"{m.group(1)} {op} {kind}"


def find_xplane(directory: str) -> str:
    """The one `.xplane.pb` that a profiler session wrote under `directory`."""
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {directory}: {found}")
    return found[0]


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(plane):
    for line in plane.lines:
        for ev in line.events:
            yield line.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns


def reduce_planes(planes):
    """The reduction over planes: each has `.name` and `.lines`, each line
    `.name` and `.events`, each event `.name`, `.start_ns`, `.duration_ns`.

    Returns {"window_s", "busy_s", "devices", "ops": {name: [seconds,
    count]} (containers left out), "idle_gaps": [[tag, seconds], ...]
    longest first}.
    """
    host, devices = [], []
    for p in planes:
        if p.name.startswith("/host:"):
            host.extend(_events(p))
        elif p.name.startswith("/device:"):
            devices.append(p)
    windows = [(s, e) for _, n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} span, found {windows}")
    w0, w1 = windows[0]
    tags = [(s, e, n) for _, n, s, e in host if n in HOST_TAGS]

    ops = collections.defaultdict(lambda: [0.0, 0])
    busy, gaps = [], []
    for p in devices:
        spans = []
        for line, name, s, e in _events(p):
            if line != OPS_LINE:
                continue
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            spans.append((s, e))
            if opcode(name) in CONTAINERS:
                continue
            ops[name][0] += (e - s) * 1e-9
            ops[name][1] += 1
        if not spans:
            continue
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append([_tag_at(tags, (s + e) / 2), (e - s) * 1e-9])
    if not busy:
        raise RuntimeError("no device op ran inside the traced window")
    gaps.sort(key=lambda g: -g[1])
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": sum(busy) / len(busy),
            "devices": len(busy), "ops": dict(ops), "idle_gaps": gaps}


def _tag_at(tags, t):
    """The innermost harness span open at time t (the latest to start)."""
    open_ = [(s, n) for s, e, n in tags if s <= t <= e]
    return max(open_)[1] if open_ else "none"


def reduce(path: str) -> dict:
    """`reduce_planes` of the trace file at `path` (gzipped if `.gz`)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return reduce_planes(
                ProfileData.from_serialized_xspace(f.read()).planes)
    return reduce_planes(ProfileData.from_file(path).planes)


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's `breakdown`: the device ops that took most time
    and the longest idle gaps, at most `top` of each."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[short_name(n), v[0]] for n, v in ops],
            "idle_gaps": red["idle_gaps"][:top]}


def op_time(red: dict, match) -> tuple:
    """(seconds, count) summed over the ops whose name `match` accepts."""
    hits = [v for n, v in red["ops"].items() if match(n)]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)
