"""Model configuration shared by every architecture in the zoo.

One dataclass covers the whole assigned pool (dense / MoE / SSM / hybrid /
VLM / audio / MLM); each ``repro.configs.<arch>`` module instantiates it with
the exact published numbers and cites the source.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | hybrid_mlp | vlm | audio | mlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // n_heads

    # attention flavour
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    use_rope: bool = True              # False -> learned absolute positions (BERT/Whisper)
    nope: bool = False                 # no positions at all (Granite-4.0-H): no RoPE, no table
    sliding_window: int = 0            # 0 = full attention; >0 = windowed (ring cache)
    attn_logit_softcap: float = 0.0

    # mlp flavour
    mlp_type: str = "swiglu"           # swiglu | gelu | relu2
    norm_type: str = "rmsnorm"         # rmsnorm | layernorm
    norm_position: str = "pre"         # pre | post (post = BERT-family)
    norm_eps: float = 1e-5

    # MoE
    n_experts: int = 0                 # 0 = dense MLP
    top_k: int = 0
    router_aux_coef: float = 0.01
    capacity_factor: float = 1.25      # expert capacity multiplier (drop-token)
    moe_local_dispatch: bool = False   # per-data-shard dispatch (see §Perf)

    # SSM (rwkv6 / mamba2)
    ssm_state: int = 0                 # mamba2 state dim
    ssm_heads: int = 0                 # rwkv6 / mamba2 heads (0 -> derive)
    ssm_expand: int = 2                # mamba2 inner expansion
    conv_dim: int = 4                  # mamba2 depthwise conv width
    ssm_chunk: int = 128               # chunked-SSD block length (see §Perf)

    # hybrid (zamba2): shared attention block applied at these (0-based) depths
    shared_attn_positions: Tuple[int, ...] = ()

    # hybrid_mlp (granite-4.0-h): every layer is [mixer -> MLP]; the mixer
    # kinds of one period in published order ("mamba" | "attention"),
    # repeated n_layers / len(layer_pattern) times
    layer_pattern: Tuple[str, ...] = ()

    # Granite's multipliers: embeddings x embedding_multiplier, attention
    # scores x attention_multiplier (0 -> 1/sqrt(head_dim)), each residual
    # branch x residual_multiplier, logits / logits_scaling
    embedding_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # VLM (llama-3.2-vision): a cross-attention layer every N layers
    cross_attn_every: int = 0
    n_image_tokens: int = 0            # stub vision-frontend patch count

    # audio (whisper): encoder stack over stub frame embeddings
    encoder_layers: int = 0
    n_audio_frames: int = 0

    # objective / head
    objective: str = "clm"             # clm | mlm | seq2seq
    tie_embeddings: bool = True
    mlm_mask_rate: float = 0.15

    max_seq_len: int = 131072
    param_dtype: str = "float32"
    compute_dtype: str = "float32"
    remat: bool = True                 # checkpoint each scanned layer
    scan_unroll: bool = False          # unroll layer scans (dry-run: makes
                                       # cost_analysis count every layer)
    source: str = ""                   # citation

    # ------------------------------------------------------------------
    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def is_decoder_only(self) -> bool:
        return self.arch_type in ("dense", "moe", "ssm", "hybrid", "hybrid_mlp",
                                  "vlm")

    @property
    def subquadratic(self) -> bool:
        """True if a 500k-token decode is admissible (O(1)-state or windowed)."""
        return self.arch_type in ("ssm", "hybrid") or self.sliding_window > 0

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_window(self, window: int = 8192) -> "ModelConfig":
        """Sliding-window variant used for long_500k on attention archs."""
        return self.replace(sliding_window=window)

    def reduced(self) -> "ModelConfig":
        """CPU-smoke variant of the same family: 2 layers (one whole period
        of a ``layer_pattern``), d_model<=512, <=4 experts."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4)
        n_kv = max(1, min(self.n_kv_heads, n_heads))
        # keep the GQA flavour: kv < q when the full config has it
        if self.n_kv_heads < self.n_heads:
            n_kv = max(1, n_heads // 2)
        kw = dict(
            n_layers=len(self.layer_pattern) or 2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=d_model // n_heads if n_heads else 0,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 1024),
            max_seq_len=2048,
            remat=False,
            param_dtype="float32",
            compute_dtype="float32",
        )
        if self.n_experts:
            kw.update(n_experts=4, top_k=2)
        if self.ssm_state:
            kw.update(ssm_state=16, ssm_heads=0)
        if self.ssm_heads and not self.ssm_state:   # rwkv6
            kw.update(ssm_heads=0)
        if self.shared_attn_positions:
            kw.update(shared_attn_positions=(1,))
        if self.cross_attn_every:
            kw.update(cross_attn_every=2, n_image_tokens=16)
        if self.encoder_layers:
            kw.update(encoder_layers=2, n_audio_frames=32)
        return self.replace(**kw)

    def validate(self) -> None:
        assert self.d_model > 0 and self.n_layers > 0 and self.vocab_size > 0
        if self.arch_type != "ssm":
            assert self.n_heads > 0
            assert self.n_heads % max(self.n_kv_heads, 1) == 0, \
                f"{self.name}: n_heads {self.n_heads} % n_kv_heads {self.n_kv_heads}"
        if self.n_experts:
            assert 0 < self.top_k <= self.n_experts
        if self.arch_type == "vlm":
            assert self.cross_attn_every > 0 and self.n_image_tokens > 0
        if self.arch_type == "hybrid_mlp":
            kinds = set(self.layer_pattern)
            assert kinds and kinds <= {"mamba", "attention"}, self.layer_pattern
            assert self.n_layers % len(self.layer_pattern) == 0, \
                f"{self.name}: {self.n_layers} layers, period {len(self.layer_pattern)}"
            assert self.ssm_state > 0
        if self.arch_type == "audio":
            assert self.encoder_layers > 0 and self.n_audio_frames > 0
