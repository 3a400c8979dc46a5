"""Configuration dataclasses for the repro framework.

Every assigned architecture is expressed as a :class:`ModelConfig`; the GFL
protocol itself is configured by :class:`GFLConfig`; input shapes come from
the fixed :data:`INPUT_SHAPES` registry.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Input shapes (fixed, assigned)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    num_experts: int = 0          # routed experts (the router's outputs)
    num_shared_experts: int = 0   # always-on experts (DeepSeek style)
    top_k: int = 2
    capacity_factor: float = 1.25  # global / row dispatch only
    expert_d_ff: int = 0          # d_ff of each routed expert
    router_aux_coef: float = 0.01  # coefficient of the balance loss
    balance: str = "switch"       # switch: over the whole token pool;
                                  # sequence: DeepSeek-V2's per-sequence
                                  # expert-level loss
    norm_topk: bool = True        # renormalise the top-k gates to sum 1
    first_dense_layers: int = 0   # leading layers that stay dense (DeepSeek)
    first_dense_d_ff: int = 0
    dispatch: str = "global"      # global: capacity over all tokens (t5x);
                                  # row: per-batch-row dispatch — scatter
                                  # stays local to the data shard (§Perf);
                                  # dropless: no capacity, held experts
                                  # through a grouped matmul
    experts_held: int = 0         # routed experts whose weights live here
                                  # (expert parallelism; dropless only);
                                  # 0 -> all of them
    expert_offset: int = 0        # id of the first held expert

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (Peng et al. 2023), as DeepSeek-V2 states it."""
    factor: float = 40.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2 / MiniCPM3)."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 0          # 0 -> no q compression
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba2 (SSD) block config."""
    state_dim: int = 64
    conv_dim: int = 4
    expand: int = 2
    headdim: int = 64
    ngroups: int = 1
    chunk: int = 256


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV-6 (Finch) block config."""
    head_size: int = 64
    decay_lora: int = 64
    mix_lora: int = 32
    gate_lora: int = 64


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // num_heads
    max_seq_len: int = 1 << 20
    rope_theta: float = 10_000.0
    rope_scaling: Optional[YarnConfig] = None   # None -> plain rope
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    sliding_window: int = 0       # 0 -> full attention
    attention: str = "gqa"        # gqa | mla | none
    mlp: str = "swiglu"           # swiglu | gelu
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    # hybrid (zamba2): indices (mod pattern) at which the shared attn block fires
    hybrid_attn_every: int = 0    # 0 -> not hybrid; else attn after every N ssm blocks
    # enc-dec (whisper)
    encoder_layers: int = 0
    encoder_seq_len: int = 0      # fixed encoder frames (whisper: 1500)
    # vlm
    num_image_tokens: int = 0     # prepended stub patch embeddings
    # citation for provenance
    source: str = ""
    param_dtype: str = "bfloat16"

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def is_encoder_decoder(self) -> bool:
        return self.encoder_layers > 0

    @property
    def subquadratic(self) -> bool:
        """True if the arch supports O(window)/O(1)-state 500k decode."""
        return (
            self.family in ("ssm", "hybrid")
            or self.sliding_window > 0
        )

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: <=2 layers, d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        num_heads = min(self.num_heads, 4)
        head_dim = max(d_model // num_heads, 32)
        kv = max(1, min(self.num_kv_heads, num_heads))
        while num_heads % kv:  # kv must divide heads (GQA grouping)
            kv -= 1
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe,
                num_experts=min(self.moe.num_experts, 4),
                experts_held=min(self.moe.experts_held, 4),
                expert_offset=0,
                num_shared_experts=min(self.moe.num_shared_experts, 1),
                top_k=min(self.moe.top_k, 2),
                expert_d_ff=min(self.moe.expert_d_ff or 128, 128),
                first_dense_layers=min(self.moe.first_dense_layers, 1),
                first_dense_d_ff=min(self.moe.first_dense_d_ff or 256, 256),
            )
        mla = None
        if self.mla is not None:
            mla = dataclasses.replace(
                self.mla,
                kv_lora_rank=min(self.mla.kv_lora_rank, 64),
                q_lora_rank=min(self.mla.q_lora_rank, 64),
                qk_nope_head_dim=32,
                qk_rope_head_dim=16,
                v_head_dim=32,
            )
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(self.ssm, state_dim=16, headdim=32, chunk=32)
        rwkv = None
        if self.rwkv is not None:
            rwkv = dataclasses.replace(
                self.rwkv, head_size=32, decay_lora=16, mix_lora=8, gate_lora=16)
        return dataclasses.replace(
            self,
            name=self.name + "-smoke",
            num_layers=2,
            d_model=d_model,
            num_heads=num_heads,
            num_kv_heads=kv,
            head_dim=head_dim if self.head_dim else 0,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            sliding_window=min(self.sliding_window, 64) if self.sliding_window else 0,
            moe=moe,
            mla=mla,
            ssm=ssm,
            rwkv=rwkv,
            hybrid_attn_every=min(self.hybrid_attn_every, 1) if self.hybrid_attn_every else 0,
            encoder_layers=min(self.encoder_layers, 2) if self.encoder_layers else 0,
            encoder_seq_len=min(self.encoder_seq_len, 64) if self.encoder_seq_len else 0,
            num_image_tokens=min(self.num_image_tokens, 16) if self.num_image_tokens else 0,
            param_dtype="float32",
        )


# ---------------------------------------------------------------------------
# GFL protocol configuration (the paper's technique)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GFLConfig:
    """Graph-federated-learning protocol knobs (Rizk & Sayed 2021)."""
    num_servers: int = 10            # P
    clients_per_server: int = 50     # K
    clients_sampled: int = 0         # L; 0 -> full participation
    topology: str = "ring"           # ring | torus | full | erdos |
                                     # hypercube | expander
                                     # (see repro.core.topology)
    topology_seed: int = 0           # seed for randomized graph families
                                     # (erdos, expander) AND the per-round
                                     # fault realizations of `fault`
    torus_rows: int = 0              # torus row count; 0 -> near-square auto
    fault: str = "none"              # resilience fault spec, e.g.
                                     # "links:0.1+dropout:0.2" — see
                                     # repro.core.resilience and
                                     # docs/resilience.md for the grammar
    population: str = "dense"        # client-population spec: dense |
                                     # synthetic:iid|hetero|mixture[,...] |
                                     # dirichlet:<alpha>[,...] — see
                                     # repro.core.population and
                                     # docs/population.md for the grammar
    cohort: str = "uniform"          # cohort-scheduler spec: uniform |
                                     # importance[,floor=..] with optional
                                     # "+trace:always|diurnal|devclass[,..]"
                                     # — see docs/population.md
    async_spec: str = "none"         # event-driven executor spec: none |
                                     # async[:buffer=..,latency=..,
                                     # max_stale=..,alpha=..,rate=..] — see
                                     # repro.core.events and docs/async.md
    data_seed: int = 0               # seed of the lazy population generator
                                     # (client k's shard is a pure function
                                     # of (data_seed, server, client))
    privacy: str = "hybrid"          # registry key into
                                     # repro.core.privacy.mechanism: none |
                                     # iid_dp | hybrid | gaussian_dp |
                                     # scheduled[:inner] | any registered name
    sigma_g: float = 0.2             # server-level noise std
    grad_bound: float = 10.0         # B in Assumption 3 (clipping threshold)
    mu: float = 0.1                  # step size
    epsilon_target: float = 0.0      # scheduled mechanism: total eps budget
                                     # to spend by epsilon_horizon (0 -> off)
    epsilon_horizon: int = 0         # scheduled mechanism: step at which the
                                     # budget is exhausted (0 -> default 100)
    secure_agg: bool = True          # pairwise-mask SMC at client level
    combine_impl: str = "dense"      # dense (einsum/all-gather) | rotate | sparse
    combine_every: int = 1           # beyond-paper: combine every tau steps
    use_kernels: bool = False        # whole-run switch: route the round
                                     # (fused clip->update->privatize->fold
                                     # + graph combine) through the Pallas
                                     # kernel layer in every engine — see
                                     # repro.kernels.ops / docs/kernels.md
    # --- beyond-paper perf knobs (see EXPERIMENTS.md §Perf) ---
    combine_wire: str = "bf16"       # bf16: barrier pins the permute buffer to
                                     # param dtype; f32: let XLA hoist converts
    grad_acc_dtype: str = "float32"  # client-grad accumulator dtype
    client_parallel: bool = False    # small-model mode: clients sharded over
                                     # the "model" axis, params replicated
    sanitize: bool = False           # runtime sanitizer mode: run engines
                                     # under jax key-reuse/NaN debugging and
                                     # cross-check the release/charge ledger
                                     # (repro.sanitize; REPRO_SANITIZE=1
                                     # enables it process-wide)
    telemetry: str = "off"           # telemetry sink spec for engine runs:
                                     # "off" (default; bit-identical to an
                                     # uninstrumented run) or a "+"-joined
                                     # jsonl[:path]|csv[:base]|memory|
                                     # console[:every] spec (repro.telemetry;
                                     # REPRO_TELEMETRY overrides "off")

    @property
    def effective_clients(self) -> int:
        return self.clients_sampled or self.clients_per_server


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "sgd"           # sgd | momentum | adam | adamw
    learning_rate: float = 0.1
    warmup_steps: int = 0
    total_steps: int = 1000
    weight_decay: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.999
    grad_clip: float = 0.0           # global-norm clip; 0 -> off
    microbatch: int = 0              # 0 -> no grad accumulation
    remat: bool = True
    seed: int = 0
