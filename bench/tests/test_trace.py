import os
import types

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=n, events=e) for n, e in lines.items()])


def synthetic():
    host = plane("/host:CPU", {"python": [
        ev(trace.WINDOW, 1000, 10_000), ev("span", 1000, 500),
        ev("sync", 1500, 9_000)]})
    dev = plane("/device:TPU:0", {trace.OPS_LINE: [
        ev("fusion.1", 500, 1_000),          # clipped to the window: 500
        ev("fusion.1", 1800, 2_000),
        ev("fused_event_apply", 3000, 1_000),   # overlaps: union
        ev("copy.2", 6000, 1_000)],
        "XLA Modules": [ev("jit_span", 1000, 9_000)]})
    return [host, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    red = trace.reduce_planes(synthetic())
    assert red["window_s"] == pytest.approx(10_000e-9)
    # [1000,1500] + [1800,4000] + [6000,7000]
    assert red["busy_s"] == pytest.approx(3_700e-9)
    assert red["ops"]["fusion.1"] == [pytest.approx(2_500e-9), 2]


def test_gaps_are_named_by_the_open_host_span():
    red = trace.reduce_planes(synthetic())
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps] == ["sync", "sync", "sync"]
    assert [g[1] for g in gaps] == pytest.approx([4000e-9, 2000e-9, 300e-9])
    assert sum(g[1] for g in gaps) + red["busy_s"] == pytest.approx(
        red["window_s"])


def test_op_time_and_breakdown():
    red = trace.reduce_planes(synthetic())
    assert trace.op_time(red, lambda n: "apply" in n) == (
        pytest.approx(1_000e-9), 1)
    b = trace.breakdown(red, top=2)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(2_500e-9)]
    assert len(b["device_ops"]) == 2
    assert len(b["idle_gaps"]) == 2


def test_a_trace_without_device_ops_is_refused():
    host, dev = synthetic()
    dev.lines[0].events = []
    with pytest.raises(RuntimeError):
        trace.reduce_planes([host, dev])


RECORDED = os.path.join(HERE, "data", "round.fasgd.seq2048.xplane.pb.gz")


def test_recorded_chip_trace():
    """Three rounds of `round.fasgd.seq2048`, traced on a TPU v5e (with one
    sequence a client and the state donated, as the cell first ran)."""
    from bench import flops
    red = trace.reduce(RECORDED)
    assert red["devices"] == 1
    assert 0.5 < red["window_s"] < 1.0
    assert 0.95 < red["busy_s"] / red["window_s"] <= 1.0
    # the apply kernel: one launch per leaf of the 12-leaf LM each round
    seconds, launches = trace.op_time(
        red, lambda n: flops.APPLY_KERNEL in n)
    assert launches == 36 and 0 < seconds < red["busy_s"]
    assert not any(trace.opcode(n) == "while" for n in red["ops"])
    b = trace.breakdown(red)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert {tag for tag, _ in b["idle_gaps"]} <= set(trace.HOST_TAGS) | {
        "none"}
    assert sum(s for _, s in red["idle_gaps"]) + red["busy_s"] == (
        pytest.approx(red["window_s"], rel=1e-6))
