"""FRED driver: the paper's simulator over a λ-client fleet, K events a window.

Set-up builds one object, the compiled span with its state: the server,
the λ stale copies and their timestamps, made on the device from the seed.
A span is what `sim.fred.run_simulation`'s `run_span` runs: the event
keys of its windows built first, then a `lax.scan` of
`sim.fred.build_step_fn` over them, the dataset an argument and the state
not donated.  The key the events fold from is an argument too, so one
executable serves every seed.  The first three windows, which the
reference follows, run one at a time as one-window spans, as
`run_simulation` runs a remainder; one span of the measured length then
runs in set-up, so the window compiles nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rules import ServerConfig
from repro.models.mlp import nll_loss
from repro.sim.fred import SimConfig, build_step_fn, init_sim

from bench import check, flops
from bench.drivers import common
from bench.reference import mlp_fred
from bench.traffic.mnist import make_train_set

CHECKED = 3                     # windows the reference follows


def init_params(key, sizes):
    """The MLP's weights as the program's pytree: [{"w", "b"}, ...]."""
    keys = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(k, (a, b)) * jnp.sqrt(2.0 / a),
             "b": jnp.zeros((b,))}
            for k, a, b in zip(keys, sizes[:-1], sizes[1:])]


class Cell:
    """One FRED cell: set-up, measured window, traced window, check."""

    unit = "events"

    def __init__(self, config, traffic, seed, interpret=None):
        self.config, self.traffic = config, traffic
        t = traffic
        self.sizes = tuple(config["layer_sizes"])
        self.K = t["events_per_window"]
        self.W = t["windows_per_span"]
        self.sim = SimConfig(
            num_clients=t["num_clients"], batch_size=t["batch_size"],
            events_per_step=self.K, apply_mode="fused",
            fused_mode=t["fused_mode"], dispatcher="uniform",
            server=ServerConfig(rule=t["rule"], lr=t["lr"],
                                use_fused_kernel=t["use_fused_kernel"],
                                kernel_interpret=interpret))
        base = common.seed_key(seed)
        self.k_data, self.k_params, self.k_events = (
            jax.random.fold_in(base, i) for i in range(3))
        self.flops_per_unit = flops.mlp_train_flops_per_event(
            self.sizes, t["batch_size"])
        self.events_per_apply = self.K

    # --- set-up -----------------------------------------------------------
    def _data(self):
        return make_train_set(self.k_data, n=self.traffic["train_rows"],
                              dim=self.sizes[0], num_classes=self.sizes[-1])

    def _params(self):
        return jax.jit(init_params, static_argnums=1)(self.k_params,
                                                      self.sizes)

    def _build(self):
        sim, K = self.sim, self.K

        @functools.partial(jax.jit, static_argnames="n_windows")
        def span(state, start_event, x, y, ekey, n_windows):
            keys = jax.vmap(lambda i: jax.random.fold_in(ekey, i))(
                start_event + jnp.arange(n_windows * K))
            keys = keys.reshape((n_windows, K) + keys.shape[1:])
            step = build_step_fn(sim, nll_loss, x, y, events=K)
            return jax.lax.scan(step, state, keys)

        return span

    def _run(self, first_window, n_windows):
        """One span from `first_window` on; returns its stacked metrics."""
        start = jnp.int32(first_window * self.K)
        self.state, m = self.span(self.state, start, self.x, self.y,
                                  self.k_events, n_windows=n_windows)
        return m

    def setup(self):
        """Build the state, compile, and run the checked windows."""
        self.x, self.y = self._data()
        self.state = jax.jit(lambda k: init_sim(
            self.sim, init_params(k, self.sizes)))(self.k_params)
        self.span = self._build()
        self.leaves = [(l.shape, l.dtype.itemsize)
                       for l in jax.tree.leaves(self.state.server.params)]
        losses, gbar, fired = [], None, []
        for w in range(CHECKED):
            m = self._run(w, 1)
            losses.append(float(jnp.mean(m["loss"])))
            fired.append(m["client"].reshape(-1))
            if gbar is None:
                gamma = self.sim.server.gamma
                gbar = [float(n) / (1 - gamma) for n in common.leaf_norms(
                    _pairs(self.state.server.b))]
        # the copies and timestamps of the clients of every checked event:
        # what the stale-copy gather read and the fetch scatter wrote
        fired = jnp.concatenate(fired)
        rows, ts = _take(self.state.client_params, self.state.client_ts,
                         fired)
        self.prog = {"losses": losses, "gbar": gbar,
                     "theta3": jax.device_get(self.state.server.params),
                     "fired": np.asarray(fired),
                     "copies": jax.device_get(_pairs(rows)),
                     "ts": np.asarray(ts)}
        # the measured spans' own program, compiled and run once here
        jax.block_until_ready(self._run(CHECKED, self.W))
        self.next_window = CHECKED + self.W

    # --- measured and traced windows -------------------------------------
    def _spans(self, until=None, count=None, annotate=None):
        """Spans back to back (`common.back_to_back`); returns (events,
        seconds, spans, spans whose losses are not finite, the ends of the
        spans' waits)."""
        def launch():
            with (annotate or common.quiet)("span"):
                out = self._run(self.next_window, self.W)["loss"]
            self.next_window += self.W
            return out

        runs, dt, failed, ends = common.back_to_back(
            launch, lambda out: np.all(np.isfinite(np.asarray(out))),
            until=until, count=count, note=annotate)
        return runs * self.W * self.K, dt, runs, failed, ends

    def window(self, seconds):
        events, dt, runs, failed, ends = self._spans(until=seconds)
        return {"work": events, "seconds": dt, "attempted": runs,
                "failed": failed, "ends": ends}

    def traced(self, annotate):
        events, dt, _, _, _ = self._spans(
            count=self.traffic["traced_spans"], annotate=annotate)
        return {"work": events, "seconds": dt}

    # --- the comparison -----------------------------------------------------
    def release(self):
        """Free the program's state before the reference runs."""
        self.state = self.span = None
        self.x = self.y = None

    def reference(self, *, dtype=jnp.float32, keep=None):
        """The reference's record over the checked windows."""
        x, y = self._data()
        params0 = _pairs(self._params())
        ev = np.arange(CHECKED * self.K).reshape(CHECKED, self.K)
        wkeys = [jax.vmap(lambda i: jax.random.fold_in(self.k_events, i))(
            jnp.asarray(e)) for e in ev]
        srv = self.sim.server
        out = mlp_fred.run_windows(
            params0, x, y, wkeys, lam=self.sim.num_clients,
            mu=self.sim.batch_size, rule=srv.rule, lr=srv.lr,
            gamma=srv.gamma, beta=srv.beta, eps=srv.eps, dtype=dtype,
            keep=keep)
        # what the fleet should hold for the clients that fired in the
        # program: the version each fetched last, and the T of that fetch
        fired = self.prog["fired"]
        copies = [out["versions"][v] for v in out["held"][fired]]
        return {"losses": out["losses"], "gbar": out["gbar"],
                "update": common.change_norms(out["params"], params0),
                "copies": jax.tree.map(lambda *a: jnp.stack(a), *copies),
                "ts": out["ts"][fired], "base": params0}

    def program_record(self):
        params0 = _pairs(self._params())
        theta3 = _pairs(self.prog["theta3"])
        return {"losses": self.prog["losses"], "gbar": self.prog["gbar"],
                "update": common.change_norms(theta3, params0),
                "copies": self.prog["copies"], "ts": self.prog["ts"]}

    def check(self):
        self.release()
        return check.readings(self.program_record(), self.reference())


@jax.jit
def _take(client_params, client_ts, idx):
    """The fleet's rows and timestamps of the clients `idx`."""
    return jax.tree.map(lambda a: a[idx], client_params), client_ts[idx]


def _pairs(params):
    """The program's [{"w", "b"}, ...] as the reference's [(w, b), ...]."""
    return [(l["w"], l["b"]) for l in params]
