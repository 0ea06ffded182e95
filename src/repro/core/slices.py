"""Bit-sliced expert weight store (DBSC's storage layer, paper §4.1).

One AMAT high-bit code buffer per (layer, expert) weight matrix; the MSB
and LSB *slices* are views of that buffer (shift / mask), so supporting
mixed precision costs **zero** extra weight memory — the point of AMAT.

The codes themselves live in the model's param tree (stacked
``QuantizedTensor`` leaves under ``experts/{wi_q,wo_q}``), which the
jitted model reads together with a per-expert ``use_lsb`` mask assembled
from cache state.  :class:`ExpertSliceStore` holds what the **cache
simulator** needs and nothing more: slice identities (:class:`SliceKey`)
and their byte sizes, derived from the per-expert code shapes.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core.amat import MatConfig, amat_quantize, slice_nbytes
from repro.models.moe import moe_param_shapes


class SliceKey(NamedTuple):
    layer: int
    expert: int
    kind: str          # 'msb' | 'lsb'


@dataclasses.dataclass(frozen=True)
class ExpertSliceStore:
    """Slice sizes of all MoE layers' AMAT expert weights (no arrays)."""

    mat: MatConfig
    layers: Tuple[int, ...]        # flat MoE layer indices
    n_experts: int
    wi_shape: Tuple[int, int]      # one expert's wi codes [d, F(|2F)]
    wo_shape: Tuple[int, int]      # one expert's wo codes [F, d]

    @classmethod
    def for_config(cls, cfg, mat: MatConfig) -> "ExpertSliceStore":
        """The store a model config's quantized experts will have — known
        before any weight exists, so a cache can be sized against it."""
        shapes = moe_param_shapes(cfg.d_model, cfg.moe)["experts"]
        n_moe = sum(1 for s in cfg.block_pattern if s.ffn == "moe") \
            * cfg.n_periods
        return cls(mat=mat, layers=tuple(range(n_moe)),
                   n_experts=cfg.moe.n_experts, wi_shape=shapes["wi"][1:],
                   wo_shape=shapes["wo"][1:])

    @classmethod
    def from_float(cls, expert_weights: Dict[int, dict],
                   mat: MatConfig) -> "ExpertSliceStore":
        """expert_weights: {layer: {'wi': [E,d,F], 'wo': [E,F,d]}}."""
        first = expert_weights[min(expert_weights)]
        return cls(mat=mat, layers=tuple(sorted(expert_weights)),
                   n_experts=int(first["wi"].shape[0]),
                   wi_shape=tuple(first["wi"].shape[1:]),
                   wo_shape=tuple(first["wo"].shape[1:]))

    # ------------------------------------------------------------ metadata
    def _slice_total(self, which: str) -> float:
        m = self.mat
        return sum(slice_nbytes(s, m.high_bits, m.group_size, which=which,
                                shift=m.shift)
                   for s in (self.wi_shape, self.wo_shape))

    @property
    def msb_bytes_per_expert(self) -> float:
        return self._slice_total("msb")

    @property
    def lsb_bytes_per_expert(self) -> float:
        return self._slice_total("lsb")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    def slice_bytes(self, key: SliceKey) -> float:
        return (self.msb_bytes_per_expert if key.kind == "msb"
                else self.lsb_bytes_per_expert)

    def highbit_expert_bytes(self) -> float:
        return self.msb_bytes_per_expert + self.lsb_bytes_per_expert

    def total_bytes(self) -> float:
        return self.highbit_expert_bytes() * self.n_layers * self.n_experts

    def code_elements(self) -> int:
        """Expert weight codes over all layers (one byte each)."""
        per_expert = sum(a * b for a, b in (self.wi_shape, self.wo_shape))
        return per_expert * self.n_experts * self.n_layers

    def all_keys(self):
        for lidx in self.layers:
            for e in range(self.n_experts):
                yield SliceKey(lidx, e, "msb")
                yield SliceKey(lidx, e, "lsb")


@partial(jax.jit, static_argnames=("mat",))
def _quantize_periods(w: jax.Array, mat: MatConfig):
    """AMAT-quantize ``[n_periods, E, K, N]`` one period at a time, so the
    f32 transient is one layer's worth, not the whole stack's."""
    return jax.lax.map(
        lambda wp: amat_quantize(wp.astype(jnp.float32), mat), w)


def quantize_moe_params(params: dict, cfg, mat: MatConfig):
    """Replace float expert weights in a model param tree by AMAT tensors.

    Returns (new_params, store, layer_map).  The param tree keeps
    QuantizedTensor leaves (a registered pytree) under
    ``experts/{wi_q,wo_q}``; the store gives the cache simulator their
    slice sizes by *flat layer index*; ``layer_map`` maps
    (pattern position, period) to that index.
    """
    pattern = cfg.block_pattern
    new_blocks = dict(params["blocks"])

    flat_idx = 0
    layer_map = {}   # (pos, period) -> flat moe layer index
    for period in range(cfg.n_periods):
        for i, spec in enumerate(pattern):
            if spec.ffn == "moe":
                layer_map[(i, period)] = flat_idx
                flat_idx += 1

    for i, spec in enumerate(pattern):
        if spec.ffn != "moe":
            continue
        blk = dict(new_blocks[f"pos{i}"])
        experts = blk["moe"]["experts"]
        moe_p = dict(blk["moe"])
        moe_p["experts"] = {"wi_q": _quantize_periods(experts["wi"], mat),
                            "wo_q": _quantize_periods(experts["wo"], mat)}
        blk["moe"] = moe_p
        new_blocks[f"pos{i}"] = blk

    new_params = dict(params)
    new_params["blocks"] = new_blocks
    return new_params, ExpertSliceStore.for_config(cfg, mat), layer_map
