"""Smoke-size cells for the CPU tests: the real cells' files, shrunk."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def bench():
    return load("BENCHMARK.json")


def spec(workload):
    """(config, traffic) of `workload` at a size the CPU runs in seconds."""
    wl = next(w for w in bench()["workloads"] if w["name"] == workload)
    config = load("bench", "configs", wl["config"] + ".json")
    traffic = load("bench", "traffic", wl["traffic"] + ".json")
    if traffic["driver"] == "fred":
        traffic.update(num_clients=16, batch_size=4, events_per_window=8,
                       train_rows=256, windows_per_span=2, traced_spans=1)
    else:
        config.update(d_model=64, n_layer=2, vocab_size=500, d_state=16,
                      headdim=16, chunk_size=16)
        config["assumed"] = dict(config["assumed"], loss_chunk=32)
        traffic.update(clients=2, seqs_per_client=2, seq_len=64,
                       pool_rounds=4, traced_rounds=1)
    return config, traffic
