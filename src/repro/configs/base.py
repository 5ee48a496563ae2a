"""Config dataclasses for models, meshes, and the FASGD trainer."""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional

import jax.numpy as jnp

if TYPE_CHECKING:  # avoid configs -> core -> configs import cycle
    from repro.core.scenarios import ScenarioConfig


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str               # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 → d_model // num_heads

    # --- MoE ---
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0            # per-expert hidden dim (d_ff used for dense archs)

    # --- MLA (deepseek-v2) ---
    use_mla: bool = False
    kv_lora_rank: int = 0

    # --- SSM (mamba2 / zamba2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_chunk: int = 64
    conv_width: int = 4

    # --- hybrid (zamba2): shared attn block every k ssm layers ---
    hybrid_attn_every: int = 0

    # --- attention flavor ---
    attn_window: int = 0         # 0 = full attention; >0 = sliding window
    causal: bool = True
    is_encoder: bool = False     # hubert: bidirectional, no decode step

    # --- modality stubs ---
    num_image_tokens: int = 0    # vlm: patch embeddings prepended to text
    image_embed_dim: int = 0
    frame_embed_dim: int = 0     # audio: precomputed frame embeddings

    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    remat: bool = False          # checkpoint each layer in the train path
    loss_chunk: int = 0          # >0: compute CE in seq chunks (bounds the
                                 # f32 [B,S,V] logits footprint — §Perf)
    unroll_stack: bool = False   # unroll the layer scan (cost-analysis mode:
                                 # XLA counts while bodies once, so roofline
                                 # terms are measured on small unrolled
                                 # variants and extrapolated linearly in L)
    param_dtype: str = "float32"     # dry-run configs use bfloat16
    citation: str = ""

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.num_heads, 1))

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 128: MXU-lane aligned and
        divisible by the model mesh axis (16), so embedding/unembedding and
        all [_, V] logits tensors shard.  Unpadded vocabs (e.g. mamba2's
        50280, hubert's 504) otherwise force REPLICATED 10GiB+ logit buffers
        — found via the dry-run memory analysis.  Padded logit columns are
        masked to −∞ in the loss and in decode sampling."""
        return -(-self.vocab_size // 128) * 128

    @property
    def dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_headdim

    def supports_decode(self) -> bool:
        return not self.is_encoder

    def supports_long_context(self) -> bool:
        """True if the arch can serve 500k-token decode sub-quadratically /
        with bounded state: SSM & hybrid natively, attention archs via
        sliding window."""
        return self.arch_type in ("ssm", "hybrid") or self.attn_window > 0


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str                    # 'train' | 'prefill' | 'decode'


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Round-based FASGD trainer (DESIGN.md §2)."""
    num_round_clients: int = 4   # C divergent parameter copies
    rule: str = "fasgd"          # any name in core.rules.registered_rules()
    lr: float = 0.005
    gamma: float = 0.9
    beta: float = 0.9
    eps: float = 1e-8
    kappa: float = 0.15          # 'exp' penalty strength
    poly_power: float = 0.5      # 'poly' exponent p in lr / tau**p
    variant: str = "intent"
    c_push: float = 0.0
    c_fetch: float = 0.0
    # §5 per-tensor gating: each parameter tensor pushes/fetches
    # independently, driven by its own v̄ moving average (per-leaf eq. 9);
    # staleness is then tracked per tensor (client_leaf_ts).
    per_tensor_push: bool = False
    per_tensor_fetch: bool = False
    drop_policy: str = "local_apply"   # 'local_apply' | 'discard'
    stats_dtype: str = "float32"       # bfloat16 for the >100B dry-runs
    use_fused_kernel: bool = False     # batched Pallas apply (engine/fused)
    # 'auto' | 'materialized' | 'cotangent': how the fused apply reduces the
    # per-client gradients.  'cotangent' (engine.fused_apply_cotangent)
    # needs a coeffs_are_v_independent rule, whole-copy gating,
    # drop_policy='discard' (local_apply consumes per-client gradients the
    # cotangent path never materializes), and an event-batched loss
    # (build_round_step's batched_loss_fn or grad_fn.event_batched).
    fused_mode: str = "auto"
    # one-kernel apply tuning (kernels/fused_event_apply.py): force / forbid
    # Pallas interpret mode (None = auto: env REPRO_KERNEL_INTERPRET, then
    # platform), and override the row block (0 = derived from the leaf's
    # shape, dtypes and K against a VMEM budget: kernels.ops.apply_blocks).
    kernel_interpret: Optional[bool] = None
    kernel_block_rows: int = 0
    # --- bounded server ingress queue (core/queue.py) ---
    # 0 = immediate apply; > 0 bounds how many pushed gradients the server
    # holds pending — each round the C pushes are admitted under
    # `admission_policy` ('block' | 'reject' | 'drop_oldest') and a drain
    # policy ('drain_all' | 'drain_k' | 'adaptive') decides how many queued
    # events the canonical update applies, so backlog (and staleness) grows
    # when arrivals outpace the drain.  Mirrors fred.SimConfig.
    queue_capacity: int = 0
    drain_policy: str = "drain_all"
    drain_k: int = 1
    drain_adaptive_gain: float = 0.5
    admission_policy: str = "block"
    # --- scenario-lite wall clock (core/scenarios.py) ---
    # A ScenarioConfig gives each round a modeled duration: the C clients
    # draw per-round service times from per-client streams, gradients apply
    # in arrival (fastest-first) order, and the round's wall cost is the
    # barrier_k-th order statistic (K-async partial barrier) or t_(C) for a
    # full round.  Churn/elastic knobs are FRED-only — the round trainer's
    # fleet is a fixed SPMD program (build_round_step raises).
    scenario: Optional[ScenarioConfig] = None
    kasync_k: int = 0                  # kasync partial-barrier K (0 → C)
    # --- sharded parameter server (core/server_shard.py) ---
    # 1 = replicated server (default, bitwise-identical to the pre-shard
    # trainer); S > 1 block-partitions W and the eq. 4–6 statistics across S
    # devices along the `server_axis` mesh axis — place state with
    # `round_trainer.shard_round_state` / `run_simulation(mesh=...)`.
    # See docs/SHARDING.md.
    server_shards: int = 1
    server_axis: str = "server"
    seed: int = 0
