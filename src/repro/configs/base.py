"""Model/run configuration system.

Every assigned architecture is a :class:`ModelConfig` registered under its id
(``--arch <id>``).  Shapes (``--shape <id>``) are :class:`ShapeConfig`.  A
``RunConfig`` bundles (arch, shape, mesh, parallelism/runtime knobs).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

# ---------------------------------------------------------------------------
# Layer kinds used by the generic block stack.
ATTN = "attn"            # global dense softmax attention (FAMOUS applies)
LOCAL_ATTN = "local_attn"  # sliding-window attention (FAMOUS + window mask)
RGLRU = "rglru"          # Griffin/RecurrentGemma recurrent block
RWKV6 = "rwkv6"          # RWKV-6 "Finch" time-mix block
MLA = "mla"              # multi-head latent attention (DeepSeek-V2) over a
                         # paged latent cache


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int

    head_dim: int = 0               # 0 -> d_model // num_heads
    # Block stack: ``pattern_unit`` repeated ``num_layers // len(unit)`` times
    # via lax.scan, plus an explicit tail of ``num_layers % len(unit)`` layers.
    pattern_unit: tuple[str, ...] = (ATTN,)
    # Attention details ------------------------------------------------------
    causal: bool = True             # False for encoder-only (hubert)
    attention_bias: bool = False    # qwen2-style QKV bias (paper: B_q/B_k/B_v)
    qk_norm: bool = False           # qwen3-style per-head RMSNorm on q,k
    window: int = 0                 # local-attention window (0 = global)
    rope: bool = True
    rope_theta: float = 10000.0
    logit_softcap: float = 0.0
    # Latent attention (MLA, kind ``MLA``) -----------------------------------
    # K/V come from one cached row per token: a ``kv_lora_rank`` latent plus
    # a ``qk_rope_head_dim`` rotary key shared by every head.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN RoPE (factor 0: plain RoPE): frequencies ramp between
    # interpolated and extrapolated over the rotary dims, and the softmax
    # scale takes the attention factor squared (mscale_all_dim)
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # MoE ---------------------------------------------------------------------
    num_experts: int = 0            # the router's width: every expert
    experts_per_token: int = 0
    capacity_factor: float = 1.25
    moe_d_ff: int = 0               # per-expert width (0 -> d_ff)
    n_shared_experts: int = 0       # always-on experts, moe_d_ff wide each
    routed_scale: float = 1.0       # routed weights' scale (no renormalising)
    # The experts this chip holds (ids into the router's outputs): given,
    # the FFN is the dropless serving layer, which routes over every expert
    # and computes the held ones; empty, the capacity-based training layer.
    held_experts: tuple[int, ...] = ()
    # Leading layers with a dense FFN of width d_ff before the scanned
    # stack (DeepSeek's first_k_dense_replace).
    first_dense_layers: int = 0
    # SSM / recurrence --------------------------------------------------------
    lru_width: int = 0              # RG-LRU state width (0 -> d_model)
    conv_width: int = 4             # temporal conv in the recurrent block
    rwkv_head_dim: int = 64
    # Misc --------------------------------------------------------------------
    norm: str = "rmsnorm"           # rmsnorm | layernorm
    act: str = "silu"               # silu | gelu | relu_sq
    tie_embeddings: bool = False
    frontend: Optional[str] = None  # None | "audio" | "vlm" (stub embeddings)
    source: str = ""

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))
        assert self.num_layers % len(self.pattern_unit) in range(len(self.pattern_unit))

    # ---- derived ----------------------------------------------------------
    @property
    def is_encoder_only(self) -> bool:
        return not self.causal

    @property
    def is_attention_free(self) -> bool:
        return all(k in (RGLRU, RWKV6) for k in self.pattern_unit)

    @property
    def is_subquadratic(self) -> bool:
        """True if no *global* dense attention layer exists (long_500k ok)."""
        return all(k != ATTN for k in self.pattern_unit)

    @property
    def num_units(self) -> int:
        return ((self.num_layers - self.first_dense_layers)
                // len(self.pattern_unit))

    @property
    def tail_layers(self) -> tuple[str, ...]:
        n_tail = ((self.num_layers - self.first_dense_layers)
                  % len(self.pattern_unit))
        return self.pattern_unit[:n_tail]

    @property
    def head_layers(self) -> tuple[str, ...]:
        """The leading dense-FFN layers, of the stack's first kind."""
        return self.pattern_unit[:1] * self.first_dense_layers

    @property
    def latent_row(self) -> int:
        """Width of an MLA layer's cached row per token (0: no MLA)."""
        return self.kv_lora_rank + self.qk_rope_head_dim if self.kv_lora_rank \
            else 0

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    def param_count(self) -> int:
        """Total parameters (analytic, matches init)."""
        from repro.models.transformer import model_spec
        from repro.models.module import count_params

        return count_params(model_spec(self))

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed experts count)."""
        total = self.param_count()
        if self.num_experts == 0:
            return total
        # experts in the spec: the held share, or every expert
        e = len(self.held_experts) or self.num_experts
        per_expert = 3 * self.d_model * self.expert_d_ff
        moe_layers = self.num_layers - self.first_dense_layers
        return total - moe_layers * per_expert * (e - self.experts_per_token)


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                       # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}

# smoke/test shapes (reduced)
SMOKE_SHAPES: dict[str, ShapeConfig] = {
    "smoke_train": ShapeConfig("smoke_train", 32, 2, "train"),
    "smoke_decode": ShapeConfig("smoke_decode", 64, 2, "decode"),
}

_REGISTRY: dict[str, "ModelConfig"] = {}


def register(cfg: ModelConfig) -> ModelConfig:
    assert cfg.name not in _REGISTRY, cfg.name
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str) -> ModelConfig:
    _ensure_loaded()
    return _REGISTRY[name]


def list_configs() -> list[str]:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    # import all config modules for registration side effects
    from repro.configs import (  # noqa: F401
        recurrentgemma_2b,
        qwen2_7b,
        qwen3_32b,
        deepseek_7b,
        command_r_plus_104b,
        llava_next_34b,
        grok_1_314b,
        kimi_k2_1t_a32b,
        deepseek_v2_lite,
        hubert_xlarge,
        rwkv6_1b6,
        famous_bert,
    )


def supported_cells(arch: str) -> list[str]:
    """Which of the four assigned shapes are well-defined for this arch."""
    cfg = get_config(arch)
    cells = ["train_4k", "prefill_32k"]
    if not cfg.is_encoder_only:
        cells.append("decode_32k")
        if cfg.is_subquadratic:
            cells.append("long_500k")
    return cells


@dataclasses.dataclass(frozen=True)
class RunConfig:
    """Bundle of (arch, shape, parallelism) for a serving/dry-run launch.

    ``tp`` is tensor parallelism on the "model" mesh axis (attention heads /
    kv heads / FFN hidden — see ``parallel.sharding.SERVE_TP_RULES``), ``dp``
    replica groups on "data".  ``tp == dp == 1`` means no mesh at all: the
    engine runs its unsharded single-device baseline.
    """
    arch: str
    shape: str = "smoke_decode"
    tp: int = 1
    dp: int = 1

    def __post_init__(self):
        assert self.tp >= 1 and self.dp >= 1, (self.tp, self.dp)

    @property
    def mesh_shape(self) -> tuple[int, int]:
        return (self.dp, self.tp)

    @property
    def needs_mesh(self) -> bool:
        return self.tp > 1 or self.dp > 1

    def make_mesh(self):
        """Build the (dp, tp) serving mesh, or None when unsharded."""
        if not self.needs_mesh:
            return None
        from repro.launch.mesh import make_serving_mesh
        return make_serving_mesh(tp=self.tp, dp=self.dp)


def shrink(cfg: ModelConfig, **over) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    unit = cfg.pattern_unit
    defaults = dict(
        name=cfg.name + "-smoke",
        num_layers=(2 * len(unit) + len(cfg.tail_layers)
                    + cfg.first_dense_layers),
        d_model=64,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_kv_heads else 2,
        d_ff=128,
        vocab_size=256,
        head_dim=16,
        num_experts=min(cfg.num_experts, 4),
        experts_per_token=min(cfg.experts_per_token, 2),
        lru_width=64 if cfg.lru_width else 0,
        window=min(cfg.window, 16) if cfg.window else 0,
        rwkv_head_dim=16,
    )
    if cfg.kv_lora_rank:
        defaults.update(kv_lora_rank=32, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16,
                        head_dim=24)
    if cfg.moe_d_ff:
        defaults.update(moe_d_ff=32)
    if cfg.held_experts:
        # a share of the shrunk router's experts, more than it routes to
        defaults.update(held_experts=tuple(range(defaults["num_experts"] - 1)))
    defaults.update(over)
    return dataclasses.replace(cfg, **defaults)
