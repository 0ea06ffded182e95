"""The comparison that decides ``correct``, driven through a whole run at
the tiny size on CPU (the harness's look for a chip skipped): a sound run
passes; the float8 control in the program's place fails; and so does the
timed path broken each way a served cell can be: a token altered where it
is produced, the KV cache handed back unchanged, half of the batch left
out, every selection run MSB-only, a router that skips its top choice,
and the q/k/v biases dropped."""

import chipbench_tiny as tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, run
from chipbench.reference import capacity, high_bit, keep_mask
from repro.core import routing


def _run(control=False):
    return run.run_cell(tiny.WORKLOAD, tiny.conf(), tiny.mix(), tiny.SEED,
                        2.0, False, tiny.bench(), require_tpu=False,
                        control=control)


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    assert sound["correct"]
    assert sound["failed"] == 0 and sound["attempted"] == 12
    for name, c in sound["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name
    assert set(sound["metrics"]) == {"ttft_p90_ms", "itl_p95_ms", "tpot_ms",
                                     "tokens_per_s", "setup_s"}
    assert list(sound)[-1] == "checks"


def test_float8_control_reads_far_above_the_program(sound):
    control = _run(control=True)
    assert not control["correct"]
    gap = control["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"] > \
        sound["checks"]["logit_gap_max"]["value"]


def _token_altered(orig, self, token, kv_cache, **kw):
    """One step's tokens altered where they are produced."""
    logits, cache, charge = orig(self, token, kv_cache, **kw)
    if len(self.steps) == 3:
        wrong = (logits.argmax(-1) + 7) % logits.shape[-1]
        logits = logits.at[np.arange(logits.shape[0]), wrong].add(1e4)
    return logits, cache, charge


def _state_unchanged(orig, self, token, kv_cache, **kw):
    """From the third step on, the step hands back the KV cache it was
    given (no write, no position advanced)."""
    logits, cache, charge = orig(self, token, kv_cache, **kw)
    return logits, (kv_cache if len(self.steps) >= 3 else cache), charge


def _half_batch(orig, self, token, kv_cache, **kw):
    """Every other live slot left out of each step's expert layer."""
    act = np.asarray(kw["slot_active"], bool).copy()
    live = np.flatnonzero(act)
    act[live[1::2]] = False
    return orig(self, token, kv_cache, **dict(kw, slot_active=act))


def _critical_cleared(monkeypatch):
    """The program marks no selection critical: every expert of a decode
    step runs MSB-only."""
    monkeypatch.setattr(routing, "criticality",
                        lambda gates, theta=0.5: jnp.zeros(gates.shape, bool))


def _router_skips_top(monkeypatch):
    """Cache-Prior routing that takes the 2nd to (k+1)-th best experts."""
    def skip(probs, cached, alpha, k):
        boosted = probs * (1.0 + alpha * cached.astype(probs.dtype))
        ids = jax.lax.top_k(boosted, k + 1)[1][:, 1:]
        gates = jnp.take_along_axis(probs, ids, axis=-1)
        return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9), ids
    monkeypatch.setattr(routing, "cache_prior_routing", skip)


def _bias_dropped(monkeypatch):
    """The program serves with its q/k/v biases zeroed."""
    setup = harness.Cell.setup

    def dropped(self):
        setup(self)
        blk = self.engine.qparams["blocks"]["pos0"]
        for b in ("bq", "bk", "bv"):
            blk[b] = jnp.zeros_like(blk[b])
    monkeypatch.setattr(harness.Cell, "setup", dropped)


def _in_decode(fault):
    def plant(monkeypatch):
        orig = harness.BenchEngine.decode_batch

        def broken(self, token, kv_cache, **kw):
            return fault(orig, self, token, kv_cache, **kw)
        monkeypatch.setattr(harness.BenchEngine, "decode_batch", broken)
    return plant


@pytest.mark.parametrize("fault", [
    _in_decode(_token_altered), _in_decode(_state_unchanged),
    _in_decode(_half_batch), _critical_cleared, _router_skips_top,
    _bias_dropped],
    ids=["token_altered", "state_unchanged", "half_batch",
         "critical_cleared", "router_skips_top", "bias_dropped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    res = _run()
    assert not res["correct"]
    print({k: c["value"] for k, c in res["checks"].items()})
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_capacity_rule_matches_the_program():
    import jax.numpy as jnp
    from repro.models.moe import capacity as p_cap, dispatch_indices

    rng = np.random.default_rng(0)
    for T, k, E in ((16, 4, 60), (40, 2, 4), (5, 6, 64)):
        ids = rng.integers(0, 3, size=(T, k))        # heavy collisions
        ids[-1] = E                                  # a padding row
        cap = capacity(T, k, E, 2.0)
        assert cap == p_cap(T, k, E, 2.0)
        _, keep = dispatch_indices(jnp.asarray(ids), None, E, cap)
        want = np.asarray(keep)
        got = keep_mask(ids, E, cap)
        assert np.array_equal(got[:-1], want[:-1])


def test_high_bit_follows_active_critical_selections():
    ids = np.array([[0, 1], [1, 2], [3, 3]])
    act = np.array([[1, 1], [1, 1], [0, 0]], bool)
    crit = np.array([[0, 1], [0, 0], [1, 1]], bool)
    assert high_bit(ids, act, crit, 4).tolist() == [False, True, False, False]
