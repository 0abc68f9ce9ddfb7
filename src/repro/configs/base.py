"""Model/run configuration system.

One :class:`ModelConfig` fully describes an architecture (family, dims,
block pattern, MoE, modality stubs), its numerics (dtypes, scan) and
its sharding rules (logical-axis -> mesh-axis mapping, MaxText style).  Every
assigned architecture ships a full config and a reduced ``smoke()`` config of
the same family for CPU tests.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Mapping, Sequence

# ---------------------------------------------------------------------------
# Sharding rule sets: logical axis -> mesh axis (or tuple / None).
# "fsdp" style additionally shards the big weight dim over the data axis.
# ---------------------------------------------------------------------------
RULES_TP = {
    "batch": ("pod", "data"),
    "seq": None,
    # decode KV-cache sequence axis: always divisible by the model axis
    # (32k/512k/window), unlike small GQA head counts -> shard it there.
    "kv_seq": "model",
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "rnn": "model",
    "layers": None,
}
RULES_FSDP_TP = dict(RULES_TP, embed="data")
#: serving variant for very large models: expert/mlp inner dim additionally
#: sharded over the data axis (2-D weight sharding).
RULES_TP_2D = dict(RULES_TP, expert_mlp="data")
#: ZeRO-3 / fully-data-parallel training: both mesh axes act as data
#: parallelism, parameters are stored fully sharded (over data+model on
#: their "embed" dim) and gathered per layer at use (weight_use), so the
#: per-layer Megatron TP activation all-reduces disappear entirely.  The
#: right regime for <=32B dense models at 4k sequence on 256 chips.
RULES_ZERO3 = {
    "batch": ("pod", "data", "model"),
    "seq": None,
    "kv_seq": "model",
    "embed": ("data", "model"),
    "vocab": None,
    "heads": None,
    "kv_heads": None,
    "head_dim": None,
    "mlp": None,
    "experts": None,
    "expert_mlp": None,
    "rnn": None,
    "layers": None,
}


@dataclass(frozen=True)
class MoEConfig:
    #: experts the router scores (its output width)
    n_experts: int
    top_k: int
    #: one routed expert's SwiGLU width; 0 -> the model's ``d_ff``
    d_ff: int = 0
    #: shared experts every token passes through, run as one SwiGLU of
    #: ``n_shared * d_ff`` width
    n_shared: int = 0
    #: renormalise the top-k gates to sum to 1 (False: raw softmax mass)
    norm_topk: bool = True
    #: the experts this chip holds under expert parallelism: the
    #: contiguous range ``[first_held, first_held + n_held)`` of the
    #: ``n_experts`` the router scores; 0 -> all of them.  Routing is over
    #: all experts; what the absent ones would add is left out.
    first_held: int = 0
    n_held: int = 0

    def __post_init__(self):
        if self.n_held == 0:
            object.__setattr__(self, "n_held", self.n_experts - self.first_held)
        if not 0 <= self.first_held < self.first_held + self.n_held \
                <= self.n_experts:
            raise ValueError("held experts must lie inside the router's range")


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values are
    rebuilt per head from a ``kv_rank``-wide latent, and one
    ``rope_dim``-wide rotary key is shared by every head."""

    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128

    @property
    def q_dim(self) -> int:
        return self.nope_dim + self.rope_dim

    @property
    def latent_width(self) -> int:
        """One cached row: the latent, then the shared rotary key."""
        return self.kv_rank + self.rope_dim

    @property
    def cache_width(self) -> int:
        """A cached row as stored: ``latent_width`` padded with zeros to
        whole 128-lane tiles, so the row is the minor dimension of the
        cache's layout on a TPU (a 576-wide minor dimension is not one
        XLA keeps, and re-laying out the cache costs a copy of it)."""
        return -(-self.latent_width // 128) * 128


@dataclass(frozen=True)
class YarnConfig:
    """YaRN rotary scaling (arXiv:2309.00071), as DeepSeek-V2 states it."""

    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def _corr_dim(self, rotations: float, dim: int, theta: float) -> float:
        return (dim * math.log(self.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    def ramp_bounds(self, dim: int, theta: float) -> tuple[int, int]:
        """(low, high): rotary pairs below ``low`` keep their frequency,
        those above ``high`` are divided by ``factor``."""
        low = math.floor(self._corr_dim(self.beta_fast, dim, theta))
        high = math.ceil(self._corr_dim(self.beta_slow, dim, theta))
        return max(low, 0), min(high, dim - 1)

    def get_mscale(self, mscale: float) -> float:
        if self.factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.factor) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        return (self.get_mscale(self.mscale)
                / self.get_mscale(self.mscale_all_dim))

    @property
    def attn_scale(self) -> float:
        """The factor on softmax's ``d^-1/2``: ``mscale(all_dim)^2``."""
        return self.get_mscale(self.mscale_all_dim) ** 2


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | hybrid | ssm | encoder | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    d_head: int = 0                 # 0 -> d_model // n_heads
    moe: MoEConfig | None = None
    #: repeating block pattern; "attn" | "local" | "rglru" | "rwkv" | "mla".
    block_pattern: tuple[str, ...] = ("attn",)
    #: latent attention widths, for "mla" layers
    mla: MLAConfig | None = None
    #: YaRN scaling of the rotary frequencies; None -> plain rotary
    rope_scaling: YarnConfig | None = None
    #: leading layers with a dense ``d_ff`` MLP in place of the MoE, run
    #: unrolled before the scanned stack
    first_dense: int = 0
    bidirectional: bool = False     # encoder-only (no causal mask, no decode)
    local_window: int = 2048
    act: str = "swiglu"             # swiglu | squared_relu | gelu
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    tie_embeddings: bool = False
    #: RG-LRU branch width (hybrid family); 0 -> d_model.
    d_rnn: int = 0
    #: multimodal stub: first mm_prefix positions take precomputed embeddings
    #: (projected from mm_embed_dim); used by [vlm].  [audio]/encoder uses
    #: embeds-only input (no token ids) when embeds_only is set.
    mm_prefix: int = 0
    mm_embed_dim: int = 0
    embeds_only: bool = False
    # ---- numerics & memory ----
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    scan_layers: bool = True
    kv_cache_dtype: str = "bfloat16"    # or "int8"
    attn_block_q: int = 1024
    attn_block_kv: int = 1024
    # ---- distribution ----
    rules: Mapping[str, object] = field(
        default_factory=lambda: dict(RULES_FSDP_TP))
    serve_rules: Mapping[str, object] = field(
        default_factory=lambda: dict(RULES_TP))

    def __post_init__(self):
        if self.d_head == 0:
            object.__setattr__(self, "d_head", self.d_model // self.n_heads)
        if self.d_rnn == 0:
            object.__setattr__(self, "d_rnn", self.d_model)
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        for b in self.block_pattern:
            if b not in ("attn", "local", "rglru", "rwkv", "mla"):
                raise ValueError(f"unknown block kind {b!r}")
        if "mla" in self.block_pattern and self.mla is None:
            raise ValueError("mla layers require MLAConfig")
        if self.family == "moe" and self.moe is None:
            raise ValueError("moe family requires MoEConfig")
        if self.moe is not None and self.act != "swiglu":
            raise ValueError("MoE experts are SwiGLU")
        if self.first_dense and self.moe is None:
            raise ValueError("leading dense layers precede MoE layers")

    # ------------------------------------------------------------------
    @property
    def layer_kinds(self) -> tuple[str, ...]:
        pat = self.block_pattern
        return tuple(pat[i % len(pat)] for i in range(self.n_layers))

    @property
    def sub_quadratic(self) -> bool:
        """True if no layer uses full (global) attention — long_500k eligible."""
        return all(k in ("local", "rglru", "rwkv") for k in self.layer_kinds)

    @property
    def has_decode(self) -> bool:
        return not self.bidirectional

    def n_params(self) -> int:
        """Analytic parameter count (embeddings + blocks + head)."""
        d, ff, V = self.d_model, self.d_ff, self.vocab
        hq, hkv, dh = self.n_heads, self.n_kv_heads, self.d_head
        total = V * d                       # embedding
        if not self.tie_embeddings:
            total += V * d                  # unembedding
        n_mats = 3 if self.act == "swiglu" else 2
        for i, kind in enumerate(self.layer_kinds):
            if kind in ("attn", "local"):
                total += d * hq * dh + 2 * d * hkv * dh + hq * dh * d
                if self.qkv_bias:
                    total += (hq + 2 * hkv) * dh
            elif kind == "rglru":
                r = self.d_rnn
                total += 2 * d * r + r * d   # in / gate / out projections
                total += 2 * r * r           # recurrence + input gates
                total += 8 * r               # conv1d(4) + Λ + biases
            elif kind == "rwkv":
                total += 4 * d * d + d * d  # r,k,v,g,o projections
                total += 2 * d              # decay/bonus params per channel
            elif kind == "mla":
                m = self.mla
                total += d * hq * m.q_dim + d * m.latent_width + m.kv_rank
                total += m.kv_rank * hq * (m.nope_dim + m.v_dim)
                total += hq * m.v_dim * d
            # channel mix / MLP
            if self.moe is not None and i >= self.first_dense:
                mo = self.moe
                total += d * mo.n_experts   # router
                total += mo.n_held * n_mats * d * self.expert_ff
                total += n_mats * d * self.expert_ff * mo.n_shared
            else:
                total += n_mats * d * ff
            total += 2 * d                  # norms
        return total

    @property
    def expert_ff(self) -> int:
        """One routed expert's width."""
        return (self.moe.d_ff or self.d_ff) if self.moe is not None else 0

    def n_active_params(self) -> int:
        """Parameters touched per token (MoE: top-k experts only)."""
        if self.moe is None:
            return self.n_params()
        full = self.n_params()
        n_mats = 3 if self.act == "swiglu" else 2
        mo = self.moe
        per_layer_experts = mo.n_held * n_mats * self.d_model * self.expert_ff
        active = (mo.top_k / mo.n_experts) * per_layer_experts
        n_moe = self.n_layers - self.first_dense
        return int(full - n_moe * per_layer_experts + n_moe * active)

    def reduced(self, **overrides) -> "ModelConfig":
        """Smoke-test sibling: same family/pattern, tiny dims."""
        pat = self.block_pattern
        small = dict(
            n_layers=max(2, 2 * len(pat)),
            d_model=64,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads > 1 else 1,
            d_head=16,
            d_ff=128,
            vocab=256,
            d_rnn=64,
            local_window=32,
            mm_prefix=4 if self.mm_prefix else 0,
            mm_embed_dim=32 if self.mm_embed_dim else 0,
            dtype="float32",
            param_dtype="float32",
            kv_cache_dtype="float32",
            name=self.name + "-smoke",
        )
        if self.moe is not None:
            mo = self.moe
            small["moe"] = MoEConfig(
                n_experts=4, top_k=2, d_ff=64 if mo.d_ff else 0,
                n_shared=mo.n_shared, norm_topk=mo.norm_topk)
        if self.mla is not None:
            small["mla"] = MLAConfig(kv_rank=32, nope_dim=16, rope_dim=8,
                                     v_dim=16)
        small["first_dense"] = min(self.first_dense, 1)
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Input shape cells (assigned): every LM arch is paired with all four.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeCell:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # prefill | decode


SHAPES: dict[str, ShapeCell] = {
    "prefill_32k": ShapeCell("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ModelConfig, shape: str) -> tuple[bool, str]:
    """(runnable, reason-if-skipped) per the assignment's skip rules."""
    cell = SHAPES[shape]
    if cell.kind == "decode" and not cfg.has_decode:
        return False, "encoder-only arch has no decode step"
    if shape == "long_500k":
        if any(k in ("attn", "mla") for k in cfg.layer_kinds):
            return False, ("pure full-attention arch: 512k decode requires "
                           "sub-quadratic attention")
    return True, ""
