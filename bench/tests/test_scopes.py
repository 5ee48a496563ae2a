"""The reduction by scope (`bench/scopes.py`): layers from the ops' JAX
paths, the launch idle time, and the decoder they rest on, on a synthetic
XSpace and on traces recorded on a TPU v5e."""
import gzip
import os

import pytest

from bench import flops, scopes, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROUND = os.path.join(HERE, "data", "round.fasgd.seq2048.xplane.pb.gz")
FRED = os.path.join(HERE, "data", "fred.fasgd.lam5000.short.xplane.pb.gz")


@pytest.mark.parametrize("tf_op,layer", [
    ("jit(span)/while/body/closed_call/dispatch/vmap(jit(_randint))/xor",
     "dispatch"),
    ("jit(span)/while/body/closed_call/dispatch/minibatch/gather",
     "minibatch"),
    ("jit(span)/while/body/closed_call/dispatch/stale_gather/gather",
     "stale_gather"),
    ("jit(span)/while/body/closed_call/dispatch/client_grad/"
     "vmap(jvp())/dot_general", "client_fwd"),
    ("jit(round_step)/dispatch/client_grad/vmap(transpose(jvp()))/"
     "scatter-add:", "client_bwd"),
    ("jit(span)/dispatch/server_apply/fused_event_apply/pallas_call",
     "server_apply"),
    ("jit(span)/dispatch/server_apply/apply_pack/jit(_pad)/pad",
     "apply_pack"),
    # a gate draw inside the fetch is the gate's: the innermost scope
    ("jit(span)/dispatch/fetch_scatter/dispatch/lt", "dispatch"),
    ("jit(span)/dispatch/fetch_scatter/scatter", "fetch_scatter"),
    ("jit(round_step)/dispatch/fetch_refresh/select_n", "fetch_refresh"),
    # a transform around the scope: `jvp(client_grad)` is client_grad's
    ("jit(f)/transpose(jvp(client_grad))/mul", "client_bwd"),
    ("jit(span)/while/body/dynamic_slice", "other"),
    ("", "other"),
])
def test_layer_is_the_innermost_scope(tf_op, layer):
    assert scopes.layer_of(tf_op) == layer


# --- a synthetic XSpace, encoded here ----------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(num, v):
    return _varint(num << 3) + _varint(v)


def _bytes(num, b):
    if isinstance(b, str):
        b = b.encode()
    return _varint((num << 3) | 2) + _varint(len(b)) + b


def _plane(name, lines, tf_ops=None):
    """An XPlane; `lines` is {line name: [(event name, start_ns, dur_ns)]}
    at line time 0, `tf_ops` {event name: tf_op}.  Every second tf_op is
    held by reference to a stat metadata name, as the profiler may."""
    tf_ops = tf_ops or {}
    names = sorted({e[0] for evs in lines.values() for e in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    stat_names = {1: scopes.TF_OP}
    out = _bytes(2, name)
    for n in names:
        meta = _int(1, ids[n]) + _bytes(2, n)
        if n in tf_ops:
            if ids[n] % 2:
                stat = _int(1, 1) + _bytes(5, tf_ops[n])
            else:
                ref = len(stat_names) + 1
                stat_names[ref] = tf_ops[n]
                stat = _int(1, 1) + _int(7, ref)
            meta += _bytes(5, stat)
        out += _bytes(4, _int(1, ids[n]) + _bytes(2, meta))
    for sid, sname in stat_names.items():
        out += _bytes(5, _int(1, sid) + _bytes(2, _int(1, sid)
                                              + _bytes(2, sname)))
    for lname, evs in lines.items():
        body = _bytes(2, lname) + _int(3, 0)
        for n, start, dur in evs:
            body += _bytes(4, _int(1, ids[n]) + _int(2, start * 1000)
                           + _int(3, dur * 1000))
        out += _bytes(3, body)
    return out


OPS = {
    "%fusion.1 = f32[8] fusion(f32[8] %p), kind=kLoop":
        "jit(step)/dispatch/minibatch/gather",
    "%fusion.2 = f32[8] fusion(f32[8] %p), kind=kLoop":
        "jit(step)/dispatch/client_grad/vmap(transpose(jvp()))/dot_general",
    "%fused_event_apply.3 = f32[8] custom-call(f32[8] %p), "
    'custom_call_target="tpu_custom_call"':
        "jit(step)/dispatch/server_apply/fused_event_apply/pallas_call",
    "%while.4 = (s32[]) while((s32[]) %t), body=%b": "jit(step)/while",
    "%copy.5 = f32[8] copy(f32[8] %p)": "",
}


def _synthetic():
    f1, f2, kern, loop, copy = OPS
    host = _plane("/host:CPU", {
        "python3": [(trace.WINDOW, 1000, 10_000), ("span", 1000, 800),
                    ("sync", 1800, 9_000)],
        "main/7": [(scopes.LAUNCH, 1100, 1_400),
                   ("AllocateOutputBuffers", 1200, 600),
                   ("DeferredTpuAllocator::Allocate", 1300, 300),
                   (scopes.LAUNCH, 9000, 500)]})
    dev = _plane("/device:TPU:0", {trace.OPS_LINE: [
        (loop, 2000, 6000),                 # a container: busy, not an op
        (f1, 2000, 1000), (f2, 3000, 2000), (kern, 5000, 2500),
        (copy, 7500, 500), (f1, 9200, 2000)],   # clipped to the window
        "XLA Modules": [("jit_step", 2000, 9000)]}, tf_ops=OPS)
    return _bytes(1, host) + _bytes(1, dev)


def test_synthetic_layers_and_launch_idle():
    red = scopes.reduce_scopes(scopes.decode(_synthetic()))
    us = {k: v * 1e6 for k, v in red["layers"].items()}
    assert us == pytest.approx({"minibatch": 1.0 + 1.8, "client_bwd": 2.0,
                                "server_apply": 2.5, "other": 0.5})
    # busy: [2000, 8000] and [9200, 11000]
    assert red["busy_s"] == pytest.approx(7_800e-9)
    assert sum(red["layers"].values()) == pytest.approx(red["busy_s"])
    # idle inside a launch: [1100, 2000] of the first, [9000, 9200] of the
    # second; the first's put down to its innermost runtime events
    assert red["launch_idle_s"] == pytest.approx(1_100e-9)
    assert dict(red["launch_events"]) == pytest.approx({
        "AllocateOutputBuffers": 300e-9,
        "DeferredTpuAllocator::Allocate": 300e-9,
        scopes.LAUNCH: 500e-9})
    assert red["launch_events"][0][0] == scopes.LAUNCH


def test_decoder_reads_what_profile_data_reads():
    """The synthetic XSpace through `jax.profiler.ProfileData` and through
    the decoder gives `trace.reduce_planes` the same planes."""
    from jax.profiler import ProfileData
    raw = _synthetic()
    assert trace.reduce_planes(scopes.decode(raw)) == trace.reduce_planes(
        ProfileData.from_serialized_xspace(raw).planes)


def test_per_unit():
    red = {"layers": {"client_fwd": 2e-3, "other": 1e-3},
           "launch_idle_s": 0.5, "window_s": 10.0}
    out = scopes.per_unit(red, "lm", units=4, scale=1e3)
    assert out == pytest.approx({"busy.lm.client_fwd": 0.5,
                                 "busy.lm.other": 0.25,
                                 "idle_dispatch.lm": 5.0})
    # a program without scopes gives no layer, and still its launch idle
    red["layers"] = {"other": 3e-3}
    assert set(scopes.per_unit(red, "lm", 4, 1e3)) == {"idle_dispatch.lm"}


# --- traces recorded on a TPU v5e ---------------------------------------------

def test_recorded_round_trace_reduces_as_before():
    """`trace.reduce_planes` gives on the recorded round trace what it gave
    when the trace was recorded, through either reader of the file."""
    from jax.profiler import ProfileData
    with gzip.open(ROUND, "rb") as f:
        raw = f.read()
    red = trace.reduce_planes(ProfileData.from_serialized_xspace(raw).planes)
    assert red["window_s"] == pytest.approx(0.745004589, rel=1e-12)
    assert red["busy_s"] == pytest.approx(0.7401122560000001, rel=1e-12)
    assert red["devices"] == 1 and len(red["ops"]) == 967
    assert sum(v[1] for v in red["ops"].values()) == 9147
    assert sum(v[0] for v in red["ops"].values()) == pytest.approx(
        0.7400433750000001, rel=1e-12)
    assert len(red["idle_gaps"]) == 1249
    assert red["idle_gaps"][:2] == [
        ["sync", pytest.approx(0.002003138, rel=1e-9)],
        ["round", pytest.approx(0.001636221, rel=1e-9)]]
    assert trace.reduce_planes(scopes.decode(raw)) == red


def test_recorded_round_trace_launch_idle():
    """The trace predates the scopes: every op is `other`, and the launch
    idle time is there all the same, inside the share that is idle."""
    red = scopes.reduce_scopes(scopes.read(ROUND))
    assert set(red["layers"]) == {scopes.OTHER}
    assert red["layers"][scopes.OTHER] == pytest.approx(
        sum(v[0] for v in red["ops"].values()))
    idle = red["window_s"] - red["busy_s"]
    assert 0 < red["launch_idle_s"] < idle
    assert len(red["launch_events"]) == 3
    assert sum(s for _, s in red["launch_events"]) <= red["launch_idle_s"]


def test_recorded_fred_trace_by_scope():
    """One short span (4 windows) of `fred.fasgd.lam5000` traced on a TPU
    v5e with the scopes on (`bench/scopes.py --windows 4`): every layer is
    there, the layers add up to the busy time, and what is left on no
    scope is the span's own copies of the fleet, in and out, which XLA
    adds without an `op_name`."""
    from jax.profiler import ProfileData
    with gzip.open(FRED, "rb") as f:
        raw = f.read()
    planes = scopes.decode(raw)
    assert trace.reduce_planes(planes) == trace.reduce_planes(
        ProfileData.from_serialized_xspace(raw).planes)
    red = scopes.reduce_scopes(planes)
    layers = red["layers"]
    assert set(layers) == {"dispatch", "minibatch", "stale_gather",
                           "client_fwd", "client_bwd", "server_apply",
                           "apply_pack", "fetch_scatter", scopes.OTHER}
    total = sum(layers.values())
    assert total == pytest.approx(red["busy_s"], rel=0.02)
    fleet = 0.0
    for p in planes:
        for line in p.lines:
            if p.name.startswith("/device:") and line.name == trace.OPS_LINE:
                fleet += sum(ev.duration_ns * 1e-9 for ev in line.events
                             if trace.opcode(ev.name) == "copy"
                             and not ev.tf_op and "[5000," in ev.name)
    assert fleet > 0.9 * layers[scopes.OTHER]
    assert layers[scopes.OTHER] - fleet < 0.02 * total


def test_recorded_fred_trace_names_the_kernel():
    """The apply kernel's op carries the `pallas_call`'s name: one launch a
    leaf of the MLP each window."""
    red = scopes.reduce_scopes(scopes.read(FRED))
    kernel = {n: v for n, v in red["ops"].items() if flops.APPLY_KERNEL in n}
    assert kernel and all(n.startswith("%fused_event_apply.") for n in kernel)
    assert sum(v[1] for v in kernel.values()) == 4 * 4
