"""Model / shape configuration system.

Every assigned architecture gets a ``src/repro/configs/<id>.py`` exporting
``CONFIG``; they register here.  ``reduced()`` derives the CPU-smoke-test
variant (2 layers, d_model <= 512, <= 4 experts) of the same family.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Optional, Tuple

from repro.models.layers import YarnScaling
from repro.models.moe import MoECfg
from repro.models.ssm import SSMCfg


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    """One position in the repeating layer pattern."""

    mixer: str          # 'attn' | 'ssm'
    ffn: str            # 'dense' | 'moe' | 'none'


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                       # dense|moe|hybrid|ssm|vlm|audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    mlp_type: str = "swiglu"
    moe: Optional[MoECfg] = None
    ssm: Optional[SSMCfg] = None
    # Repeating block pattern; default = uniform attention+dense.
    pattern: Optional[Tuple[BlockSpec, ...]] = None
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    # Attention kind: 'mha' (multi-head/grouped-query, the default) or
    # 'mla' (DeepSeek-V2 latent attention: queries of qk_nope + qk_rope
    # per head, keys and values up-projected from a kv_lora_rank latent
    # with one rope'd key part shared by all heads; the decode cache
    # holds the latent and that key part).
    attn_kind: str = "mha"
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[YarnScaling] = None
    # Leading layers of attention + dense MLP (d_ff) that run once before
    # the repeating pattern, with their own parameters and cache
    # (``blocks/prefix``); n_layers counts them.
    first_k_dense: int = 0
    # Sliding-window attention width used for the long-context decode shape
    # (and, if ``always_swa``, everywhere).
    sliding_window: Optional[int] = None
    always_swa: bool = False
    logit_softcap: Optional[float] = None
    tie_embeddings: bool = False
    qkv_bias: bool = False
    # Encoder-decoder (whisper): encoder layers share d_model/heads/d_ff.
    encoder_layers: int = 0
    encoder_seq: int = 0                 # e.g. 1500 audio frames
    # Modality frontend stub: first `prefix_len` positions of the decoder
    # input come from precomputed embeddings (vision patches / audio frames
    # already encoded) instead of token ids.
    prefix_len: int = 0
    dtype: str = "bfloat16"
    source: str = ""                     # citation

    # ---- performance variants (hillclimb knobs; EXPERIMENTS.md §Perf) ----
    # Megatron-style sequence parallelism: residual stream sharded over
    # the model axis between blocks (all-reduce -> reduce-scatter+gather).
    seq_parallel: bool = False
    # Embedding lookup as one-hot matmul (avoids the SPMD involuntary
    # full-remat on gather from a vocab-sharded table).
    onehot_embed: bool = False
    # KV cache dtype for decode ('bfloat16' | 'int8'); int8 stores
    # per-(token, head) dynamic scales (the paper's INT8 KV cache).
    kv_dtype: str = "bfloat16"
    # Serve-time expert weights as AMAT int8 codes (the paper's storage
    # format) instead of bf16 — halves decode weight traffic for MoE.
    quantized_serve: bool = False
    # Ring-buffer KV cache of size `sliding_window` for windowed decode:
    # O(window) memory AND no cross-shard gather of the window (the
    # attention set is permutation-invariant, so wraparound needs no
    # reordering).  Decode-only.
    ring_kv: bool = False
    # Activation-checkpoint policy for the layer scan:
    #   'full' — recompute everything (default, min memory, ~4x fwd FLOPs)
    #   'dots' — save matmul outputs, recompute elementwise only
    #            (~3x fwd FLOPs, more live activation memory)
    remat_policy: str = "full"
    # Pad the unembedding (and tied embedding) vocab dim to a multiple of
    # this so it shards over the model axis; padded columns are masked to
    # -inf in the logits.  1 = off.  Fixes the giant logits all-reduce
    # when vocab % mesh_model != 0 (e.g. internvl2's V=151655).
    pad_vocab_to: int = 1

    @property
    def padded_vocab(self) -> int:
        pv = self.pad_vocab_to
        return ((self.vocab_size + pv - 1) // pv) * pv if pv > 1 \
            else self.vocab_size

    # ------------------------------------------------------------------ api
    @property
    def block_pattern(self) -> Tuple[BlockSpec, ...]:
        if self.pattern is not None:
            return self.pattern
        ffn = "moe" if self.moe is not None else "dense"
        mixer = "ssm" if self.arch_type == "ssm" else "attn"
        if self.arch_type == "ssm":
            ffn = "none"
        return (BlockSpec(mixer, ffn),)

    @property
    def n_periods(self) -> int:
        plen = len(self.block_pattern)
        n = self.n_layers - self.first_k_dense
        if n % plen != 0:
            raise ValueError(
                f"{self.name}: n_layers={self.n_layers} less "
                f"first_k_dense={self.first_k_dense} not divisible by "
                f"pattern length {plen}")
        return n // plen

    @property
    def has_attention(self) -> bool:
        return any(b.mixer == "attn" for b in self.block_pattern)

    @property
    def has_ssm(self) -> bool:
        return any(b.mixer == "ssm" for b in self.block_pattern)

    @property
    def has_moe(self) -> bool:
        return any(b.ffn == "moe" for b in self.block_pattern)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def subquadratic(self) -> bool:
        """Can this arch run the 500k decode shape?"""
        if self.arch_type in ("ssm",):
            return True
        if self.arch_type == "hybrid":
            return True      # attention layers get the sliding window
        return self.sliding_window is not None

    def param_count(self) -> int:
        """Total parameters (embedding included)."""
        from repro.models.model import param_shapes
        import numpy as np

        shapes = param_shapes(self)
        total = 0
        for leaf in jax.tree_util.tree_leaves(
                shapes, is_leaf=lambda x: isinstance(x, tuple)):
            total += int(np.prod(leaf))
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only routed experts)."""
        total = self.param_count()
        if self.moe is None:
            return total
        import numpy as np
        from repro.models.moe import moe_param_shapes

        es = moe_param_shapes(self.d_model, self.moe)["experts"]
        per_expert = sum(int(np.prod(s[1:])) for s in es.values())
        n_moe_layers = sum(
            1 for b in self.block_pattern if b.ffn == "moe") * self.n_periods
        inactive = per_expert * (self.moe.n_experts - self.moe.top_k) \
            * n_moe_layers
        return total - inactive

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family, tiny dims."""
        plen = len(self.block_pattern)
        n_layers = 2 * plen if plen > 1 else 2
        d_model = min(self.d_model, 256)
        head_dim = 32
        n_kv = min(self.n_kv_heads, 2)
        n_heads = n_kv * max(1, min(self.n_heads // self.n_kv_heads, 2))
        moe = None
        if self.moe is not None:
            moe = dataclasses.replace(
                self.moe,
                n_experts=min(self.moe.n_experts, 4),
                top_k=min(self.moe.top_k, 2),
                d_ff=min(self.moe.d_ff, 128),
                d_ff_shared=min(self.moe.d_ff_shared, 128)
                if self.moe.d_ff_shared else 0,
            )
        ssm = None
        if self.ssm is not None:
            ssm = dataclasses.replace(
                self.ssm, d_state=min(self.ssm.d_state, 16),
                head_dim=32, chunk=32)
        return dataclasses.replace(
            self,
            name=self.name + "-reduced",
            n_layers=n_layers,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            head_dim=head_dim,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            moe=moe,
            ssm=ssm,
            encoder_layers=min(self.encoder_layers, 2),
            encoder_seq=min(self.encoder_seq, 16) if self.encoder_seq else 0,
            prefix_len=min(self.prefix_len, 8) if self.prefix_len else 0,
            sliding_window=min(self.sliding_window, 64)
            if self.sliding_window else None,
        )


import jax  # noqa: E402  (used by param_count)


# --------------------------------------------------------------------------
# Input shapes (assigned)
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str            # 'train' | 'prefill' | 'decode'


SHAPES = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


ARCH_IDS = (
    "internvl2-1b",
    "llama4-maverick-400b-a17b",
    "jamba-v0.1-52b",
    "starcoder2-3b",
    "llama4-scout-17b-a16e",
    "nemotron-4-15b",
    "gemma-7b",
    "smollm-360m",
    "mamba2-2.7b",
    "whisper-small",
)

# Paper-reproduction MoE configs (DeepSeek-V2-Lite / Qwen1.5-MoE structure).
REPRO_IDS = ("deepseek-v2-lite-repro", "qwen15-moe-repro")


def get_config(arch_id: str) -> ModelConfig:
    mod = importlib.import_module(
        "repro.configs." + arch_id.replace("-", "_").replace(".", "_"))
    return mod.CONFIG


def list_configs():
    return {a: get_config(a) for a in ARCH_IDS}
