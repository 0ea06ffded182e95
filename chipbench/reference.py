"""Plain float32 reference of a served cell, and the comparison that
decides ``correct``.

The reference is the published decoder in plain ``jax.numpy``.  What
depends on the decoder family (attention, the layer stack) is the
family's ``forward`` (``chipbench/families/<family>.py``); this module
holds what every family shares: RMSNorm, the MoE block (a softmax
router, SwiGLU experts quantized as the configuration states (AMAT:
8-bit asymmetric codes in groups of 32 along the input axis; an MSB-only
slice keeps the top 4 bits of code and zero-point and scales by 16), an
ungated shared SwiGLU expert), the untied unembedding, the routing plan
and the comparison.  Every matmul runs at ``Precision.HIGHEST``.  It
imports nothing of the program: it draws its own weights from the seed
(``chipbench.weights``) and quantizes them itself.

The served decode is not a plain top-k model: which experts a token uses
follows the cache-prior policy (top-k of the router probabilities, each
expert resident in the host cache simulation boosted by ``1 + alpha``),
and DBSC picks each expert's precision per step from the whole batch.
The reference takes the routing choices from the step traces the engine
hands its recorder hook (``ids``, ``active``, ``critical``), with the
step's ``alpha`` and resident set, and holds each of its own sequence's
selections to the configuration's rules with its own router:

* routing: a served expert's boosted score may lie below the reference's
  k-th best by at most a small share (``route_gap``: bf16 serving swaps
  near ties, a wrong expert lies far below); prefill is plain top-k;
* precision: a selection whose own renormalised gate reaches ``theta``
  is critical and needs MSB+LSB; ``msb_gate`` is the largest such gate
  that the trace says ran MSB-only.  The reference computes every
  selection at MSB+LSB where the trace or its own gate asks for it, and
  prefill at MSB+LSB;
* the gates: its own router probabilities at the served ids,
  renormalised where the configuration's ``norm_topk`` says so, raw
  otherwise (criticality reads the renormalised ones either way);
* capacity dispatch: per expert, at most ``capacity(T)`` token slots over
  the step's whole batch, filled in k-slot order then token order; the
  rest contribute nothing.

It runs one sequence at a time (prompt plus served tokens, causal), layer
by layer, and returns the logits at the positions that produced a served
token.  The number compared is the widest gap by which a judged token's
logit lies below the reference's best at its position.  The judged
tokens are the served ones; for the control they are the tokens that the
reference computed with every matmul operand rounded to float8 (e4m3),
the step below the configuration's bf16, puts first at each position of
the same prompt and served tokens.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights
from chipbench.cellconfig import family

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ plan
def capacity(n_tokens: int, k: int, n_experts: int, factor: float) -> int:
    """Token slots per expert under capacity dispatch."""
    c = int(n_tokens * k * factor / n_experts) + 1
    return max(8, min(c, n_tokens))


def keep_mask(ids: np.ndarray, n_experts: int, cap: int) -> np.ndarray:
    """[T, k] bool: which selections fit under the capacity, filling
    k-slot 0 over every token first, then slot 1, and so on.  Ids out of
    range (padding rows) take no slot."""
    T, k = ids.shape
    counts = np.zeros(n_experts, np.int64)
    keep = np.zeros((T, k), bool)
    e = np.arange(n_experts)
    for kk in range(k):
        onehot = (ids[:, kk, None] == e[None, :]).astype(np.int64)
        pos = np.sum((np.cumsum(onehot, 0) - 1 + counts) * onehot, -1)
        keep[:, kk] = pos < cap
        counts += np.sum(onehot * keep[:, kk, None], 0)
    return keep


def high_bit(ids, active, critical, n_experts: int) -> np.ndarray:
    """[E] bool: experts some active critical selection asks at MSB+LSB."""
    sel = (ids[..., None] == np.arange(n_experts)) & \
        (critical & active)[..., None]
    return sel.reshape(-1, n_experts).any(0)


def sequence_plan(dm: dict, prefill_ids: np.ndarray, steps: list,
                  max_batch: int):
    """Routing plan of one request over its prompt and decode positions.

    ``L`` counts the MoE layers (``dm["moe_layers"]``): a dense layer
    takes no routing.  ``prefill_ids``: [L, 1, P, k]; ``steps``: one
    ``(ids, active, critical, slot, boost)`` per decode step (arrays
    [L, 1, B, k]; ``boost`` [L, E], the Cache-Prior factor of each
    expert).  Returns ``ids, keep, hi`` of shape [L, P + len(steps), k],
    ``boost`` of shape [L, P + len(steps), E] (1 in prefill), and the
    number of the request's own selections that the trace reports out of
    range or inactive.  Under top-k and Cache-Prior routing every
    selection of a live sequence is a real, active expert, so that count
    must be 0: a slot the program left out of a step shows up here, not
    as a plan the reference would follow.
    """
    E, k, f = dm["experts"], dm["top_k"], dm["capacity_factor"]
    L, P = len(dm["moe_layers"]), prefill_ids.shape[2]
    if prefill_ids.shape[0] != L:
        raise ValueError(f"routing of {prefill_ids.shape[0]} layers for "
                         f"{L} MoE layers")
    n = P + len(steps)
    ids = np.zeros((L, n, k), np.int32)
    keep = np.zeros((L, n, k), bool)
    hi = np.ones((L, n, k), bool)
    boost = np.ones((L, n, E), np.float32)
    faults = 0
    cap_p, cap_d = capacity(P, k, E, f), capacity(max_batch, k, E, f)
    for li in range(L):
        ids[li, :P] = prefill_ids[li, 0]
        keep[li, :P] = keep_mask(prefill_ids[li, 0], E, cap_p)
        for s, (sid, act, crit, slot, bst) in enumerate(steps):
            b = sid[li, 0]
            ids[li, P + s] = b[slot]
            faults += int(np.sum(~act[li, 0, slot]))
            keep[li, P + s] = keep_mask(b, E, cap_d)[slot]
            hi[li, P + s] = high_bit(b, act[li, 0], crit[li, 0],
                                     E + 1)[b[slot]]
            boost[li, P + s] = bst[li]
    bad = ids >= E
    faults += int(bad.sum())
    ids[bad] = 0
    keep &= ~bad
    return ids, keep, hi, boost, faults


# ------------------------------------------------------------- arithmetic
def mm(a, b, lowp: bool):
    """``a @ b`` in float32 at HIGHEST; with ``lowp`` both operands are
    first rounded to float8 (the control)."""
    if lowp:
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HI)


def rms(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _quant(w, dm):
    """AMAT: (high-bit weights, MSB-only weights) of one [K, N] matrix."""
    g, qmax = dm["group_size"], 2 ** dm["high_bits"] - 1
    shift = dm["high_bits"] - dm["low_bits"]
    K, N = w.shape
    wg = w.astype(jnp.float32).reshape(K // g, g, N)
    lo = jnp.minimum(wg.min(1, keepdims=True), 0.0)
    hi = jnp.maximum(wg.max(1, keepdims=True), 0.0)
    s = (hi - lo) / qmax
    s = jnp.where(s <= 0, 1.0, s)
    zp = jnp.clip(jnp.round(-lo / s), 0, qmax)
    q = jnp.clip(jnp.round(wg / s) + zp, 0, qmax)
    m = 2.0 ** shift
    w_hi = (q - zp) * s
    w_lo = (jnp.floor(q / m) - jnp.floor(zp / m)) * (s * m)
    return w_hi.reshape(K, N), w_lo.reshape(K, N)


def swiglu(h, wi, wo, lowp):
    g, u = jnp.split(mm(h, wi, lowp), 2, -1)
    return mm(jax.nn.silu(g) * u, wo, lowp)


def moe_block(x, p, ids, keep, hi, boost, lo_at, *, dm, lowp, lo_rows):
    """The MoE half of one layer over a padded sequence, with its norm:
    ``p`` holds the layer's ``moe_norm`` and ``moe`` leaves.  Returns the
    block's output (without the residual) and, for each position, the
    widest ``route_gap`` and ``msb_gate`` of its selections.  Only the
    ``lo_rows`` rows from ``lo_at`` on (the decode positions) may run
    MSB-only.  Called inside each family's jitted layer."""
    f32 = jnp.float32
    h = rms(x, p["moe_norm"], dm["eps"])
    probs = jax.nn.softmax(mm(h, p["moe"]["w_router"].astype(f32), lowp), -1)
    boosted = probs * boost
    kth = jax.lax.top_k(boosted, dm["top_k"])[0][:, -1:]
    route_gap = jnp.max(jnp.maximum(
        0.0, 1.0 - jnp.take_along_axis(boosted, ids, -1) / kth), -1)
    raw = jnp.take_along_axis(probs, ids, -1)
    g = raw / jnp.maximum(raw.sum(-1, keepdims=True), 1e-9)
    # Criticality reads the renormalised gates whatever weights the
    # outputs (the paper's rule).
    msb_gate = jnp.max(jnp.where(hi > 0, 0.0, g), -1)
    hi = jnp.maximum(hi, (g >= dm["theta"]).astype(f32))
    if not dm["norm_topk"]:
        g = raw
    g = g * keep
    onehot = jax.nn.one_hot(ids, dm["experts"], dtype=f32)      # [S, k, E]
    c_hi = jnp.einsum("sk,ske->es", g * hi, onehot)
    c_lo = jnp.einsum("sk,ske->es", g * (1 - hi), onehot)

    # Every expert runs over every row at MSB+LSB and over the decode rows
    # MSB-only, and the gate weights pick which one each row takes.
    # Shapes depend on the cell alone, so one program serves every
    # sequence and seed.  (Gathering only each expert's rows gave a shape
    # per sequence, and on the chip one of those shapes crashed the
    # process.)
    h_lo = jax.lax.dynamic_slice_in_dim(h, lo_at, lo_rows)
    c_lo = jax.lax.dynamic_slice_in_dim(c_lo, lo_at, lo_rows, axis=1)

    def expert(ys, xs):
        y, y_lo = ys
        wi, wo, ch, cl = xs
        wi_h, wi_l = _quant(wi, dm)
        wo_h, wo_l = _quant(wo, dm)
        y = y + ch[:, None] * swiglu(h, wi_h, wo_h, lowp)
        y_lo = y_lo + cl[:, None] * swiglu(h_lo, wi_l, wo_l, lowp)
        return (y, y_lo), None

    ex = p["moe"]["experts"]
    (y, y_lo), _ = jax.lax.scan(
        expert, (jnp.zeros_like(x), jnp.zeros_like(h_lo)),
        (ex["wi"], ex["wo"], c_hi, c_lo))
    y = jax.lax.dynamic_update_slice_in_dim(
        y, jax.lax.dynamic_slice_in_dim(y, lo_at, lo_rows) + y_lo, lo_at, 0)
    if "shared" in p["moe"]:
        sh = p["moe"]["shared"]
        y = y + swiglu(h, sh["wi"].astype(f32), sh["wo"].astype(f32), lowp)
    return y, route_gap, msb_gate


@partial(jax.jit, static_argnames=("dm", "lowp"))
def _head(x, params, qpos, *, dm, lowp):
    dm = dict(dm)
    h = rms(x[qpos], params["final_norm"], dm["eps"])
    return mm(h, params["unembed"].astype(jnp.float32), lowp)


class Reference:
    """The reference of one cell and seed, run on padded sequences."""

    def __init__(self, dm: dict, seed: int, seq_pad: int, query_pad: int):
        self.dm = dm
        self.frozen = tuple(sorted(dm.items()))
        self.family = family(dm["family"])
        self.params = weights.draw(dm, seed)
        self.seq_pad, self.query_pad = seq_pad, query_pad

    def _run(self, prompt, served, plan, lowp: bool):
        """Logits [Q, V] at the positions that produced ``served`` (the
        n tokens the program gave: the prefill's first, then each decode
        step's), fed the prompt and ``served[:-1]``; and the widest
        ``route_gap`` and ``msb_gate`` over the sequence.  ``plan``: the
        ``sequence_plan`` arrays over those n + P - 1 positions."""
        P, n = len(prompt), len(served)
        fed = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S, Q = self.seq_pad, self.query_pad
        assert len(fed) <= S and n <= Q and P + Q <= S, (len(fed), S, n, Q)
        tokens = np.zeros(S, np.int32)
        tokens[:len(fed)] = fed
        padded = []
        for a in plan:
            dst = np.zeros((a.shape[0], S) + a.shape[2:], a.dtype)
            dst[:, :a.shape[1]] = a
            padded.append(jnp.asarray(dst))
        ids, keep, hi, boost = padded
        qpos = np.full(Q, P - 1, np.int32)
        qpos[:n] = np.arange(P - 1, P - 1 + n)
        p = self.params
        x = p["embed"][jnp.asarray(tokens)].astype(jnp.float32)
        x, route, msb = self.family.forward(
            p["blocks"], x, (ids, keep, hi, boost), jnp.int32(len(fed)),
            jnp.int32(P), dm=self.frozen, lowp=lowp, lo_rows=Q)
        return _head(x, p, jnp.asarray(qpos), dm=self.frozen, lowp=lowp), \
            route, msb

    def control_tokens(self, prompt, served, plan) -> np.ndarray:
        """The control in the program's place: at each position that
        produced a served token, the token that the float8 reference puts
        first, fed the same prompt and served tokens."""
        lg, _, _ = self._run(prompt, served, plan, lowp=True)
        return np.asarray(jnp.argmax(lg, -1))[:len(served)].astype(np.int32)

    def compare(self, prompt, served, judged, plan) -> dict:
        """The numbers of one sequence: the widest gap by which a judged
        token's logit lies below the reference's best at its position,
        and the widest ``route_gap`` and ``msb_gate``."""
        lg, route, msb = self._run(prompt, served, plan, lowp=False)
        tgt = np.zeros(self.query_pad, np.int32)
        tgt[:len(judged)] = judged
        gaps = np.asarray(_gaps(lg, jnp.asarray(tgt)))[:len(judged)]
        return {"logit_gap": float(gaps.max()), "route_gap": route,
                "msb_gate": msb}


@jax.jit
def _gaps(ref, tokens):
    return ref.max(-1) - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]
