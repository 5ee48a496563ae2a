"""Round-trainer driver for an LM whose training state only fits over
several chips: the server sharded, one client a chip.

The cell is `bench/drivers/round.py`'s, by import: its weights
(`init_params`, `model_config`), its token pool, its rounds, its measured
and traced windows.  It is placed as `launch.train.run_round_trainer`
places a sharded run: a `make_server_mesh(server=S)`, S the traffic's
`server_shards`, the server's leaves blocked over it and the clients'
copies, timestamps and batches laid over the same devices (`round_trainer.round_state_shardings`); the state is
built there by one jitted call, and the round step, built with the mesh,
is jitted with its state donated.  The reference is
`bench/reference/mamba2_lm_blocked.py`, which places its own state over
the devices; the program's change of the parameters over the checked
rounds is read on the device before the state is freed.

Two of the yardstick's inputs are per chip:

- `flops_per_unit` is the model's operations per token over the S chips,
  so that `mfu.lm` reads a share of the S chips' peak;
- `leaves` lists, for every device, the (shape, itemsize) of the block of
  each server leaf that its launch of the apply kernel applies.
  `apply_roofline` sums the kernel's time over the devices and divides its
  launches by the length of this list, so each byte is counted once.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core import server_shard
from repro.core.round_trainer import init_round_state, round_state_shardings
from repro.launch.mesh import make_server_mesh
from repro.models.lm import make_lm_loss
from repro.models.transformer import loss_fn

from bench.drivers import common
from bench.drivers import round as rnd
from bench.reference import mamba2_lm_blocked


class Cell(rnd.Cell):
    """A round-trainer cell over S chips: set-up, measured window, traced
    window, check."""

    def __init__(self, config, traffic, seed, interpret=None):
        super().__init__(config, traffic, seed, interpret=interpret)
        S = traffic["server_shards"]
        self.mesh = make_server_mesh(server=S)
        self.tc = dataclasses.replace(self.tc, server_shards=S)
        self.flops_per_unit /= S
        self.placed = round_state_shardings(
            jax.eval_shape(self._state, self.k_params), self.mesh,
            self.tc.server_axis)

    def _init_params(self, key):
        return rnd.init_params(key, self.config, self.mcfg.padded_vocab)

    def _state(self, key):
        return init_round_state(self.tc, self._init_params(key))

    def _params(self):
        return jax.jit(self._init_params,
                       out_shardings=self.placed.server.params)(
                           self.k_params)

    def setup(self):
        """Build the state where the step holds it, compile, and run the
        checked rounds."""
        cfg = self.mcfg

        def grad_fn(p, b):
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                p, cfg, b)
            return loss, g

        lm_loss = make_lm_loss(cfg)

        def batched_loss_fn(W, deltas, b):
            return lm_loss.event_batched(W, deltas, b["tokens"],
                                         b["targets"])

        pool = self._pool()
        self.batches = [self._round_batch(pool, r)
                        for r in range(self.traffic["pool_rounds"])]
        del pool
        self.batches = server_shard.shard_clients(
            self.batches, self.mesh, self.tc.server_axis)
        self.state = jax.jit(self._state, out_shardings=self.placed)(
            self.k_params)
        self.step = jax.jit(rnd.build_round_step(
            self.tc, grad_fn, apply_mode="fused",
            batched_loss_fn=batched_loss_fn, mesh=self.mesh),
            donate_argnums=0)
        self.leaves = [(s.data.shape, l.dtype.itemsize)
                       for l in jax.tree.leaves(self.state.server.params)
                       for s in l.addressable_shards]
        losses, gbar = [], None
        self.next_round = 0
        for _ in range(rnd.CHECKED):
            m = self._launch()
            losses.append(float(m["loss"]))
            if gbar is None:
                gbar = [float(n) / (1 - self.tc.gamma)
                        for n in common.leaf_norms(self.state.server.b)]
        self.prog = {"losses": losses, "gbar": gbar,
                     "update": common.change_norms(
                         self.state.server.params, self._params())}

    def reference(self, *, quant=None, keep=None):
        """The blocked reference's record over the checked rounds."""
        params0 = mamba2_lm_blocked.place(self._params())
        rounds = [(np.asarray(b["tokens"]), np.asarray(b["targets"]))
                  for b in self.batches[:rnd.CHECKED]]
        tc, c = self.tc, self.config
        out = mamba2_lm_blocked.run_rounds(
            params0, rounds, c, lr=tc.lr, gamma=tc.gamma, beta=tc.beta,
            eps=tc.eps, quant=quant, keep=keep)
        return {"losses": out["losses"], "gbar": out["gbar"],
                "update": common.change_norms(out["params"], params0)}

    def program_record(self):
        return self.prog
