"""Round-trainer driver: an LM trained through the async protocol.

Set-up builds one object, the compiled `core.round_trainer.build_round_step`
with its state: the server and C client copies of the weights, made on the
device from the seed in the weights' dtype.  Each round is one call of that
step, built and called as `launch.train.run_round_trainer` builds and calls
it (the event-batched loss beside the gradient, the state not donated), on
the next C × B sequences of a token pool that set-up generated on the
device.  The first three rounds, which the reference follows, are the first
calls of the same step.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, TrainerConfig
from repro.core.round_trainer import build_round_step, init_round_state
from repro.models.lm import make_lm_loss
from repro.models.transformer import loss_fn

from bench import check, flops
from bench.drivers import common
from bench.reference import mamba2_lm
from bench.traffic.tokens import make_pool

CHECKED = 3                     # rounds the reference follows


def model_config(c: dict) -> ModelConfig:
    """The program's config for a Mamba2 LM file of `bench/configs/`."""
    return ModelConfig(
        name=c["name"], arch_type="ssm", num_layers=c["n_layer"],
        d_model=c["d_model"], num_heads=0, num_kv_heads=0, d_ff=0,
        vocab_size=c["vocab_size"], ssm_state=c["d_state"],
        ssm_expand=c["expand"], ssm_headdim=c["headdim"],
        ssm_chunk=c["chunk_size"], conv_width=c["d_conv"],
        norm_eps=c["norm_eps"], param_dtype=c["dtype"],
        remat=c["assumed"]["remat"], loss_chunk=c["assumed"]["loss_chunk"])


def init_params(key, c: dict, padded_vocab: int):
    """Seeded Mamba2 LM weights in the program's pytree and the stored
    dtype; the vocabulary rows and columns padded as the program holds
    them (the padded rows are never read, the padded logits masked)."""
    d, N, P, W, L = (c["d_model"], c["d_state"], c["headdim"], c["d_conv"],
                     c["n_layer"])
    di = c["expand"] * d
    H = di // P
    dt = jnp.dtype(c["dtype"])
    ks = jax.random.split(key, 5)
    dense = lambda k, shape, scale: (
        scale * jax.random.normal(k, shape)).astype(dt)
    one = lambda n: jnp.ones((L, n), dt)
    lin = lambda a, b: jnp.broadcast_to(jnp.linspace(a, b, H), (L, H))
    return {
        "embed": dense(ks[0], (padded_vocab, d), 0.02),
        "final_norm": jnp.ones((d,), dt),
        "unembed": dense(ks[1], (d, padded_vocab), 0.02),
        "layers": {
            "ln": one(d),
            "mamba": {
                "in_proj": dense(ks[2], (L, d, 2 * di + 2 * N + H),
                                 d ** -0.5),
                "conv_w": dense(ks[3], (L, W, di + 2 * N), W ** -0.5),
                "conv_b": jnp.zeros((L, di + 2 * N), dt),
                "A_log": jnp.log(lin(1.0, 16.0)).astype(dt),
                "D": one(H),
                "dt_bias": jnp.log(jnp.expm1(lin(1e-3, 1e-1))).astype(dt),
                "out_norm": one(di),
                "out_proj": dense(ks[4], (L, di, d), di ** -0.5),
            },
        },
    }


class Cell:
    """One round-trainer cell: set-up, measured window, traced window,
    check."""

    unit = "tokens"

    def __init__(self, config, traffic, seed, interpret=None):
        self.config, self.traffic = config, traffic
        t = traffic
        self.mcfg = model_config(config)
        self.C, self.B, self.seq = (t["clients"], t["seqs_per_client"],
                                    t["seq_len"])
        self.tc = TrainerConfig(
            num_round_clients=self.C, rule=t["rule"], lr=t["lr"],
            use_fused_kernel=t["use_fused_kernel"],
            fused_mode=t["fused_mode"], kernel_interpret=interpret)
        base = common.seed_key(seed)
        self.k_data, self.k_params, self.k_rounds = (
            jax.random.fold_in(base, i) for i in range(3))
        self.flops_per_unit = flops.mamba2_train_flops_per_token(config)
        self.events_per_apply = self.C
        self.tokens_per_round = self.C * self.B * self.seq

    def _params(self):
        vocab = self.mcfg.padded_vocab
        return jax.jit(lambda k: init_params(k, self.config, vocab))(
            self.k_params)

    def _pool(self):
        n = self.traffic["pool_rounds"] * self.C * self.B
        return make_pool(self.k_data, rows=n, seq=self.seq,
                         vocab=self.config["vocab_size"])

    def _round_batch(self, pool, r):
        """Round r's rows of the pool: B sequences a client, [C, B, seq]."""
        n = self.C * self.B
        i = (r % self.traffic["pool_rounds"]) * n
        shape = (self.C, self.B, self.seq)
        return {"tokens": pool[0][i:i + n].reshape(shape),
                "targets": pool[1][i:i + n].reshape(shape)}

    def setup(self):
        """Build the state, compile, and run the checked rounds."""
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
        self.state = jax.jit(lambda k: init_round_state(
            self.tc, init_params(k, self.config, cfg.padded_vocab)))(
                self.k_params)
        self.step = jax.jit(build_round_step(
            self.tc, grad_fn, apply_mode="fused",
            batched_loss_fn=batched_loss_fn))
        self.leaves = [(l.shape, l.dtype.itemsize)
                       for l in jax.tree.leaves(self.state.server.params)]
        losses, gbar = [], None
        self.next_round = 0
        for _ in range(CHECKED):
            m = self._launch()
            losses.append(float(m["loss"]))
            if gbar is None:
                gbar = [float(n) / (1 - self.tc.gamma)
                        for n in common.leaf_norms(self.state.server.b)]
        self.prog = {"losses": losses, "gbar": gbar,
                     "theta3": jax.device_get(self.state.server.params)}

    def _launch(self):
        r = self.next_round
        key = jax.random.fold_in(self.k_rounds, r)
        self.state, m = self.step(
            self.state, self.batches[r % len(self.batches)], key)
        self.next_round += 1
        return m

    # --- measured and traced windows -------------------------------------
    def _rounds(self, until=None, count=None, annotate=None):
        """Rounds back to back (`common.back_to_back`); returns (tokens,
        seconds, rounds, rounds whose loss is not finite, the ends of the
        rounds' waits)."""
        def launch():
            with (annotate or common.quiet)("round"):
                return self._launch()["loss"]

        runs, dt, failed, ends = common.back_to_back(
            launch, lambda loss: np.isfinite(float(loss)),
            until=until, count=count, note=annotate)
        return runs * self.tokens_per_round, dt, runs, failed, ends

    def window(self, seconds):
        tokens, dt, runs, failed, ends = self._rounds(until=seconds)
        return {"work": tokens, "seconds": dt, "attempted": runs,
                "failed": failed, "ends": ends}

    def traced(self, annotate):
        tokens, dt, _, _, _ = self._rounds(
            count=self.traffic["traced_rounds"], annotate=annotate)
        return {"work": tokens, "seconds": dt}

    # --- the comparison -----------------------------------------------------
    def release(self):
        """Free the program's state before the reference runs."""
        self.state = self.step = None

    def reference(self, *, quant=None, keep=None):
        """The reference's record over the checked rounds."""
        params0 = self._params()
        rounds = [(b["tokens"], b["targets"])
                  for b in self.batches[:CHECKED]]
        tc, c = self.tc, self.config
        out = mamba2_lm.run_rounds(
            params0, rounds, c, lr=tc.lr, gamma=tc.gamma, beta=tc.beta,
            eps=tc.eps, quant=quant, keep=keep)
        return {"losses": out["losses"], "gbar": out["gbar"],
                "update": common.change_norms(out["params"], params0)}

    def program_record(self):
        params0 = self._params()
        return {"losses": self.prog["losses"], "gbar": self.prog["gbar"],
                "update": common.change_norms(self.prog["theta3"], params0)}

    def check(self):
        self.release()
        return check.readings(self.program_record(), self.reference())
