"""Mixture-of-Experts layer with capacity-based dispatch.

Two expert-weight representations share the same dispatch/combine path:

* ``float``     — plain bf16/f32 expert weights, used for training and the
                  train/prefill dry-runs.
* ``quantized`` — AMAT (G32 asymmetric) codes + scales + zero-points, with a
                  per-expert ``use_lsb`` mask selecting MSB+LSB (high-bit) or
                  MSB-only (low-bit) dequantization.  This is the jittable
                  compute path behind DBSC: the cache simulator flips
                  ``use_lsb`` bits; the math stays pure.

Dispatch is the classic capacity-based scheme (Switch/GShard): per-k-slot
one-hot position ranking, scatter into an ``[E, C, d]`` buffer, batched
expert matmuls, gather+combine.  Experts shard over the ``model`` mesh axis;
the scatter/gather lower to all-to-all under GSPMD.

The router also exposes the *raw* probabilities so the SliceMoE engine can
apply cache-aware policies (Cache-Prior boost, Cumsum, DBSC criticality)
outside or inside the jitted step.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.amat import MatConfig
from repro.quant.groupquant import QuantizedTensor


@dataclasses.dataclass(frozen=True)
class RoutingPolicy:
    """Static cache-aware routing policy (SliceMoE engine; paper §2.1/§4.1).

    kind:        'topk' | 'cache_prior' | 'cumsum'
    slice_mode:  'dbsc'       — per-token dynamic precision (DBSC)
                 'highbit'    — every selected expert computes MSB+LSB
                 'lowbit'     — MSB-only for everyone
                 'amat_static'— MSB-only during decode (high-bit prefill)
    fetch_lsb_on_miss: if False, an LSB miss degrades the expert to
                 MSB-only compute instead of fetching (needs cached_lsb).
    quant_execution: run the expert FFN *directly on packed AMAT codes*
                 via the batched-expert Pallas kernel (per-expert
                 ``use_lsb`` becomes a per-expert dequant shift inside
                 the kernel) instead of materializing dense f32/bf16
                 expert weights each step.  Numerically equivalent to
                 the dense-dequant path; see docs/kernels.md.
    """

    kind: str = "topk"
    slice_mode: str = "dbsc"
    theta: float = 0.5
    cumsum_tau: float = 0.9
    cumsum_kmax: int = 8
    fetch_lsb_on_miss: bool = True
    quant_execution: bool = False


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_ff: int                      # per-expert FFN width
    n_shared_experts: int = 0
    d_ff_shared: int = 0           # total shared-expert width
    capacity_factor: float = 1.25
    mlp_type: str = "swiglu"
    router_noise: float = 0.0      # jitter for load-balance during training
    aux_loss_weight: float = 0.01


# --------------------------------------------------------------------------
# Routing
# --------------------------------------------------------------------------
def router_probs(x: jax.Array, w_router: jax.Array) -> jax.Array:
    """[T, d] @ [d, E] -> softmax probs [T, E] (f32)."""
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)
    return jax.nn.softmax(logits, axis=-1)


def topk_select(probs: jax.Array, k: int, *, renormalize: bool = True):
    """Top-k routing: returns (gates [T,k], ids [T,k])."""
    gates, ids = jax.lax.top_k(probs, k)
    if renormalize:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, ids


def load_balance_loss(probs: jax.Array, ids: jax.Array, n_experts: int):
    """Switch-style auxiliary loss: E * <f_e> . <p_e>."""
    sel = jax.nn.one_hot(ids, n_experts, dtype=jnp.float32)   # [T, k, E]
    frac_tokens = jnp.mean(jnp.sum(sel, axis=1), axis=0)      # [E]
    mean_probs = jnp.mean(probs, axis=0)                      # [E]
    return n_experts * jnp.sum(frac_tokens * mean_probs)


# --------------------------------------------------------------------------
# Dispatch / combine
# --------------------------------------------------------------------------
def capacity(n_tokens: int, k: int, n_experts: int, factor: float) -> int:
    c = int(n_tokens * k * factor / n_experts) + 1
    # keep the MXU happy and bound the tiny-T case
    return max(8, min(c, n_tokens))


def dispatch_indices(ids: jax.Array, gates: jax.Array, n_experts: int,
                     cap: int):
    """Compute per-(token, slot) expert positions under a capacity limit.

    Returns (positions [T,k] int32, keep [T,k] bool).  Slot priority follows
    k order (top-1 assignments never dropped before top-2's), matching
    GShard semantics.
    """
    T, k = ids.shape
    positions = []
    keeps = []
    counts = jnp.zeros((n_experts,), jnp.int32)
    for kk in range(k):
        onehot = jax.nn.one_hot(ids[:, kk], n_experts, dtype=jnp.int32)
        pos_in_e = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        pos = jnp.sum(pos_in_e * onehot, axis=-1)
        keep = pos < cap
        positions.append(pos)
        keeps.append(keep)
        counts = counts + jnp.sum(onehot * keep[:, None].astype(jnp.int32),
                                  axis=0)
    return jnp.stack(positions, 1), jnp.stack(keeps, 1)


def dispatch(x: jax.Array, ids: jax.Array, positions: jax.Array,
             keep: jax.Array, n_experts: int, cap: int) -> jax.Array:
    """Scatter tokens into the [E, C, d] expert buffer."""
    T, k = ids.shape
    d = x.shape[-1]
    flat_ids = ids.reshape(-1)
    flat_pos = jnp.where(keep.reshape(-1), positions.reshape(-1), cap)
    xk = jnp.broadcast_to(x[:, None, :], (T, k, d)).reshape(-1, d)
    buf = jnp.zeros((n_experts, cap + 1, d), x.dtype)
    buf = buf.at[flat_ids, flat_pos].add(xk, mode="drop",
                                         unique_indices=False)
    return buf[:, :cap]


def combine(y_buf: jax.Array, ids: jax.Array, positions: jax.Array,
            keep: jax.Array, gates: jax.Array) -> jax.Array:
    """Gather expert outputs back to tokens and mix with gates."""
    T, k = ids.shape
    flat_ids = ids.reshape(-1)
    flat_pos = jnp.clip(positions.reshape(-1), 0, y_buf.shape[1] - 1)
    y = y_buf[flat_ids, flat_pos].reshape(T, k, -1)
    w = (gates * keep.astype(gates.dtype))[..., None]
    return jnp.sum(y * w.astype(y.dtype), axis=1)


# --------------------------------------------------------------------------
# Expert compute
# --------------------------------------------------------------------------
def _ffn_activation(h: jax.Array, mlp_type: str, dtype) -> jax.Array:
    """The FFN nonlinearity in f32, result cast to ``dtype``."""
    if mlp_type in ("swiglu", "geglu"):
        act = jax.nn.silu if mlp_type == "swiglu" else \
            (lambda u: jax.nn.gelu(u, approximate=True))
        g, u = jnp.split(h, 2, axis=-1)
        return act(g.astype(jnp.float32)).astype(dtype) * u
    if mlp_type == "relu2":
        return jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(dtype)
    if mlp_type == "gelu":
        return jax.nn.gelu(h.astype(jnp.float32),
                           approximate=True).astype(dtype)
    raise ValueError(mlp_type)


def _expert_ffn(xe: jax.Array, wi: jax.Array, wo: jax.Array,
                mlp_type: str) -> jax.Array:
    """Batched per-expert FFN. xe: [E, C, d]; wi: [E, d, F(|2F)]; wo: [E, F, d]."""
    h = jnp.einsum("ecd,edf->ecf", xe, wi.astype(xe.dtype))
    h = _ffn_activation(h, mlp_type, xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wo.astype(xe.dtype))


def _expert_ffn_quant(xe: jax.Array, wi_q: QuantizedTensor,
                      wo_q: QuantizedTensor,
                      use_lsb: Optional[jax.Array], shift: int,
                      mlp_type: str, layer) -> jax.Array:
    """Expert FFN computed *directly on packed AMAT codes* (no dense
    weight tensor is ever materialized — the paper's in-front-of-compute
    dequantization, here fused into the Pallas matmul).

    ``use_lsb`` [E] selects the per-expert dequant shift inside the
    kernel.  Both projections read their codes in the canonical K-major
    layout; stacked ``[P, E, K, N]`` tensors are read at period
    ``layer`` inside the kernel.
    """
    from repro.kernels.amat_matmul.ops import amat_expert_matmul_qt

    ul = use_lsb if use_lsb is not None \
        else jnp.ones((xe.shape[0],), bool)
    h = amat_expert_matmul_qt(xe, wi_q, ul, layer,
                              shift=shift).astype(xe.dtype)
    h = _ffn_activation(h, mlp_type, xe.dtype)
    y = amat_expert_matmul_qt(h, wo_q, ul, layer, shift=shift)
    return y.astype(xe.dtype)


def _dequant_experts(qt: QuantizedTensor, use_lsb: Optional[jax.Array],
                     shift: int, dtype) -> jax.Array:
    """Dequantize stacked expert weights [E, K, N] with per-expert precision."""
    from repro.core.amat import dequant_mixed
    from repro.quant.groupquant import dequantize

    if use_lsb is None or shift == 0:
        w = dequantize(qt)
    else:
        w = dequant_mixed(qt, use_lsb, shift)
    return w.astype(dtype)


def moe_apply(
    params: dict,
    x: jax.Array,                       # [T, d] flat tokens
    cfg: MoECfg,
    *,
    use_lsb: Optional[jax.Array] = None,   # [E] bool (quantized path only)
    mat: Optional[MatConfig] = None,
    gate_override: Optional[tuple] = None,  # (gates [T,k], ids [T,k])
    policy: Optional[RoutingPolicy] = None,
    policy_state: Optional[dict] = None,   # {'alpha': (), 'cached_msb': [E],
                                           #  'cached_lsb': [E]}
    token_mask: Optional[jax.Array] = None,  # [T] bool; False = padding row
    deterministic: bool = True,
    rng: Optional[jax.Array] = None,
    quant_execution: Optional[bool] = None,  # None -> policy decides
    force_high_bit: bool = False,  # prefill: policy routes, compute hi-bit
    layer: Optional[jax.Array] = None,  # period of stacked quantized experts
):
    """Full MoE layer.  Returns (y [T, d], aux: dict).

    params:
      w_router: [d, E]
      experts:  {'wi': [E, d, F(|2F)] float}  OR
                {'wi_q': QuantizedTensor, 'wo_q': QuantizedTensor}
      shared:   optional dense-MLP params applied to every token

    ``layer``: the quantized expert tensors hold every period of the
    model's layer scan (``[P, E, K, N]``) and this layer is period
    ``layer``.  The kernel picks the period in its index maps, so no
    per-period copy of the codes is made; the dense-dequant path indexes
    the same leaves.  Float expert weights are always the layer's own.

    ``token_mask`` excludes padding rows (retired/empty batch slots in
    continuous-batching decode) from routing entirely: their ids are
    redirected out of range so they occupy no expert capacity, never
    appear in the slice-demand trace, and cannot evict a live token's
    expert assignment under the capacity limit.
    """
    T, d = x.shape
    probs = router_probs(x, params["w_router"])
    active = None
    critical = None

    def mask_routing(gates, ids, active):
        if token_mask is None:
            return gates, ids, active
        tm = token_mask.astype(bool)
        ids = jnp.where(tm[:, None], ids, cfg.n_experts)   # out-of-range
        gates = gates * tm[:, None].astype(gates.dtype)
        active = jnp.broadcast_to(tm[:, None], ids.shape) \
            if active is None else (active & tm[:, None])
        return gates, ids, active

    if gate_override is not None:
        gates, ids = gate_override
        gates, ids, active = mask_routing(gates, ids, active)
        k_eff = ids.shape[-1]
    elif policy is not None:
        from repro.core import routing as R

        if policy.kind == "cache_prior":
            gates, ids = R.cache_prior_routing(
                probs, policy_state["cached_msb"],
                policy_state["alpha"], cfg.top_k)
        elif policy.kind == "buddy":
            gates, ids = R.buddy_routing(
                probs, policy_state["cached_msb"],
                policy_state["buddies"], cfg.top_k)
        elif policy.kind == "cumsum":
            kmax = min(policy.cumsum_kmax, cfg.n_experts)
            gates, ids, active = R.cumsum_routing(
                probs, policy.cumsum_tau, kmax)
        else:
            gates, ids = R.topk_routing(probs, cfg.top_k)
        gates, ids, active = mask_routing(gates, ids, active)
        gates = gates.astype(x.dtype)
        k_eff = ids.shape[-1]

        critical = R.criticality(gates.astype(jnp.float32), policy.theta)
        if active is not None:
            critical = critical & active
        msb_needed, lsb_needed = R.expert_demand(
            ids, critical if active is None else critical & active,
            cfg.n_experts)
        if active is not None:
            sel = jax.nn.one_hot(ids, cfg.n_experts, dtype=jnp.bool_)
            msb_needed = jnp.any(sel & active[..., None], axis=(0, 1))
        if policy.slice_mode == "highbit":
            use_lsb = jnp.ones((cfg.n_experts,), bool)
            lsb_needed = msb_needed
        elif policy.slice_mode in ("lowbit", "amat_static"):
            use_lsb = jnp.zeros((cfg.n_experts,), bool)
            lsb_needed = jnp.zeros((cfg.n_experts,), bool)
        else:  # dbsc
            use_lsb = lsb_needed
            # Prefill threads a state-free policy with no policy_state;
            # the residency intersection only applies during decode.
            if not policy.fetch_lsb_on_miss and policy_state is not None:
                use_lsb = lsb_needed & policy_state["cached_lsb"]
        if force_high_bit:
            # Prefill discipline: the configured policy picks *which*
            # experts run (and emits the active/critical trace), but
            # every routed expert computes MSB+LSB.  use_lsb=None takes
            # the exact full-dequant path the policy-free prefill took.
            use_lsb = None
    else:
        p = probs
        if not deterministic and cfg.router_noise > 0 and rng is not None:
            p = p * jax.random.uniform(
                rng, probs.shape, minval=1.0 - cfg.router_noise,
                maxval=1.0 + cfg.router_noise)
        gates, ids = topk_select(p, cfg.top_k)
        gates, ids, active = mask_routing(gates, ids, active)
        gates = gates.astype(x.dtype)
        k_eff = cfg.top_k

    from repro.launch.sharding import shard_hint

    cap = capacity(T, k_eff, cfg.n_experts, cfg.capacity_factor)
    positions, keep = dispatch_indices(ids, gates, cfg.n_experts, cap)
    xe = dispatch(x, ids, positions, keep, cfg.n_experts, cap)
    xe = shard_hint(xe, "model", None, None)   # expert parallelism

    experts = params["experts"]
    quant_exec = quant_execution if quant_execution is not None else \
        (policy.quant_execution if policy is not None else False)
    wi_qt = wo_qt = None
    if "wi_q" in experts:
        assert mat is not None
        wi_qt, wo_qt = experts["wi_q"], experts["wo_q"]
    elif "wi_codes" in experts:
        # flat-dict quantized form (quantized_serve dry-run / serve path)
        assert mat is not None
        wi_qt = QuantizedTensor(experts["wi_codes"], experts["wi_scales"],
                                experts["wi_zps"], mat.high_bits,
                                mat.group_size, True)
        wo_qt = QuantizedTensor(experts["wo_codes"], experts["wo_scales"],
                                experts["wo_zps"], mat.high_bits,
                                mat.group_size, True)

    with jax.named_scope("experts"):
        if wi_qt is not None and quant_exec:
            # Quantized execution: the packed codes ARE the compute format.
            # No dense expert tensor is materialized (and hence no
            # dequant-tile shard_hint workaround is needed — the kernel
            # reads the codes at their native sharding).
            ye = _expert_ffn_quant(xe, wi_qt, wo_qt, use_lsb, mat.shift,
                                   cfg.mlp_type,
                                   0 if layer is None else layer)
        elif wi_qt is not None:
            # Dense-dequant reference path: materialize per-expert f32/bf16
            # weights each step (gather-then-dequantize).
            if layer is not None:
                wi_qt, wo_qt = jax.tree.map(lambda a: a[layer],
                                            (wi_qt, wo_qt))
            wi = _dequant_experts(wi_qt, use_lsb, mat.shift, x.dtype)
            wo = _dequant_experts(wo_qt, use_lsb, mat.shift, x.dtype)
            if "wi_codes" in experts:
                # Pin the dequantized tiles to the codes' sharding: without
                # this GSPMD replicates them (a 66 GB/step all-gather on
                # maverick — EXPERIMENTS.md §Perf hillclimb 1).
                wi = shard_hint(wi, "model", None, "data")
                wo = shard_hint(wo, "model", "data", None)
            ye = _expert_ffn(xe, wi, wo, cfg.mlp_type)
        else:
            ye = _expert_ffn(xe, experts["wi"], experts["wo"], cfg.mlp_type)
    ye = shard_hint(ye, "model", None, None)
    y = combine(ye, ids, positions, keep, gates)
    y = shard_hint(y, ("pod", "data"), None)

    if cfg.n_shared_experts > 0:
        from repro.models.layers import mlp_apply
        y = y + mlp_apply(params["shared"], x, cfg.mlp_type)

    aux = {
        "ids": ids,
        "gates": gates,
        "aux_loss": load_balance_loss(probs, ids, cfg.n_experts),
        "dropped_frac": 1.0 - jnp.mean(keep.astype(jnp.float32)),
    }
    if policy is not None:
        aux["critical"] = critical
        aux["msb_needed"] = msb_needed
        aux["lsb_needed"] = lsb_needed
        # force_high_bit clears use_lsb to None for compute; the trace
        # reports what actually ran (all experts high-bit).
        aux["use_lsb"] = use_lsb if use_lsb is not None \
            else jnp.ones((cfg.n_experts,), bool)
        aux["active"] = active if active is not None \
            else jnp.ones(ids.shape, bool)
    return y, aux


def quantize_params_for_serve(params: dict, cfg, mat: MatConfig) -> dict:
    """Replace float expert weights by flat-dict AMAT tensors (serve path).

    The flat-dict form ({wi_codes, wi_scales, wi_zps, ...}) keeps the
    param tree plain-dict so spec builders and sharding-rule path
    matching treat the quantized leaves like any other parameter.
    """
    from repro.core.amat import amat_quantize

    new_blocks = {}
    for pos, blk in params["blocks"].items():
        if "moe" in blk:
            blk = dict(blk)
            moe = dict(blk["moe"])
            e = moe["experts"]
            out = {}
            for name in ("wi", "wo"):
                qt = amat_quantize(e[name].astype(jnp.float32), mat)
                out[f"{name}_codes"] = qt.codes
                out[f"{name}_scales"] = qt.scales
                out[f"{name}_zps"] = qt.zero_points
            moe["experts"] = out
            blk["moe"] = moe
        new_blocks[pos] = blk
    new_params = dict(params)
    new_params["blocks"] = new_blocks
    return new_params


def quantized_expert_shapes(d_model: int, cfg: MoECfg,
                            group_size: int = 32) -> dict:
    wi_cols = 2 * cfg.d_ff if cfg.mlp_type in ("swiglu", "geglu") else cfg.d_ff
    E = cfg.n_experts
    return {
        "wi_codes": (E, d_model, wi_cols),
        "wi_scales": (E, d_model // group_size, wi_cols),
        "wi_zps": (E, d_model // group_size, wi_cols),
        "wo_codes": (E, cfg.d_ff, d_model),
        "wo_scales": (E, cfg.d_ff // group_size, d_model),
        "wo_zps": (E, cfg.d_ff // group_size, d_model),
    }


def moe_param_shapes(d_model: int, cfg: MoECfg) -> dict:
    wi_cols = 2 * cfg.d_ff if cfg.mlp_type in ("swiglu", "geglu") else cfg.d_ff
    shapes = {
        "w_router": (d_model, cfg.n_experts),
        "experts": {
            "wi": (cfg.n_experts, d_model, wi_cols),
            "wo": (cfg.n_experts, cfg.d_ff, d_model),
        },
    }
    if cfg.n_shared_experts > 0:
        from repro.models.layers import mlp_param_shapes
        shapes["shared"] = mlp_param_shapes(
            d_model, cfg.d_ff_shared or cfg.d_ff, cfg.mlp_type)
    return shapes
