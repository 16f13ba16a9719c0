"""MoE router/dispatch invariants + properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest  # noqa: F401

from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.models.moe import _slot_maps, capacity, init_moe, moe_apply, \
    moe_serve, router_topk

CFG = get_config("qwen3-moe-235b-a22b").reduced()


def test_capacity_formula():
    c = capacity(CFG, 64)
    m = CFG.moe
    assert c >= 64 * m.top_k / m.n_experts
    assert c % 4 == 0


def test_router_gates_normalised():
    w = init_moe(CFG, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, CFG.d_model),
                          jnp.bfloat16)
    gates, idx, aux = router_topk(CFG, w["router"], x)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-5)
    assert idx.shape == (2, 32, CFG.moe.top_k)
    assert float(aux) > 0


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_slot_maps_consistent(seed):
    """For every kept assignment, src[slot] maps back to the assignment."""
    rng = np.random.RandomState(seed)
    G, A, E = 2, 48, CFG.moe.n_experts
    C = 8
    idx = jnp.asarray(rng.randint(0, E, (G, A)), jnp.int32)
    pos, keep, src, used = _slot_maps(idx, E, C)
    pos, keep, src, used = map(np.asarray, (pos, keep, src, used))
    for g in range(G):
        for a in range(A):
            if keep[g, a]:
                slot = idx[g, a] * C + pos[g, a]
                assert used[g, slot]
                assert src[g, slot] == a
    # positions within an expert are unique and dense from 0
    for g in range(G):
        for e in range(E):
            ps = sorted(pos[g, (np.asarray(idx[g]) == e) & keep[g]])
            assert ps == list(range(len(ps)))


def test_moe_uniform_experts_equals_dense():
    """If every expert has IDENTICAL weights and capacity is ample, the MoE
    output equals a single dense expert MLP (gates sum to 1)."""
    import dataclasses
    cfg = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, capacity_factor=8.0))
    w = init_moe(cfg, jax.random.PRNGKey(0))
    w = dict(w)
    for k in ("we_g", "we_i", "we_o"):
        w[k] = jnp.broadcast_to(w[k][:1], w[k].shape)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model),
                          jnp.bfloat16) * 0.5
    y, _ = moe_apply(cfg, w, x)
    # dense single-expert reference
    h = jax.nn.silu(x.astype(jnp.float32) @ w["we_g"][0].astype(jnp.float32)) \
        * (x.astype(jnp.float32) @ w["we_i"][0].astype(jnp.float32))
    y_ref = h @ w["we_o"][0].astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), rtol=6e-2,
                               atol=6e-2)


def test_moe_capacity_drops_tokens():
    """With capacity_factor tiny, some assignments are dropped and the
    output norm shrinks (never NaN)."""
    import dataclasses
    cfg = dataclasses.replace(
        CFG, moe=dataclasses.replace(CFG.moe, capacity_factor=0.05))
    w = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, cfg.d_model),
                          jnp.bfloat16)
    y, aux = moe_apply(cfg, w, x)
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert np.isfinite(float(aux))


# DeepSeek-V2-Lite's router at a small size: 8 experts, top-3, softmax
# scores NOT renormalised, two shared experts; shares of 2 experts each
import dataclasses  # noqa: E402

DS = get_config("deepseek-v2-lite-16b").reduced()
DS = dataclasses.replace(DS, dtype="float32", moe=dataclasses.replace(
    DS.moe, n_experts=8, top_k=3))


def _share(cfg, w, n_held, offset, n_shared=None):
    """One device's share: the held experts' weights, everything else
    (router, shared experts) as every device holds it; ``n_shared=0``
    leaves the shared experts out."""
    m = dataclasses.replace(cfg.moe, n_held=n_held, held_offset=offset)
    if n_shared is not None:
        m = dataclasses.replace(m, n_shared=n_shared)
    ws = dict(w)
    for k in ("we_g", "we_i", "we_o"):
        ws[k] = w[k][offset:offset + n_held]
    return dataclasses.replace(cfg, moe=m), ws


def test_gates_follow_norm_topk():
    w = init_moe(DS, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, DS.d_model))
    assert not DS.moe.norm_topk                    # published: false
    gates, _, _ = router_topk(DS, w["router"], x)
    logits = np.einsum("gsd,de->gse", np.asarray(x, np.float64),
                       np.asarray(w["router"], np.float64))
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = -np.sort(-p, -1)[..., :DS.moe.top_k]
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-5)
    assert (np.asarray(gates.sum(-1)) < 1).all()


def test_expert_shares_add_up_to_the_whole_layer():
    """Four shares of two experts each: their routed parts, with the
    shared experts (which every device computes alike) counted once,
    equal the uncut layer's output."""
    w = init_moe(DS, jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, DS.d_model))
    whole, rows = moe_serve(DS, w, x)
    assert int(rows) == 2 * 24 * DS.moe.top_k
    routed = moe_serve(*_share(DS, w, 8, 0, n_shared=0), x)[0]
    total = whole - routed                          # the shared experts
    n_rows = 0
    for off in range(0, 8, 2):
        cfg, ws = _share(DS, w, 2, off, n_shared=0)
        y, r = moe_serve(cfg, ws, x)
        total = total + y
        n_rows += int(r)
    assert n_rows == int(rows)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,S", [(1, 1), (16, 1), (1, 37), (3, 64)])
def test_serving_drops_no_assignment(B, S):
    """Whatever the batch or chunk, every assignment to a held expert is
    computed: the count equals the router's, and the output equals each
    token's held experts applied one by one."""
    cfg, ws = _share(DS, init_moe(DS, jax.random.PRNGKey(5)), 2, 2,
                     n_shared=0)
    # make every token prefer the held experts, the worst case for a
    # capacity: all B*S tokens reach both of them
    ws["router"] = ws["router"].at[:, 2:4].add(5.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (B, S, cfg.d_model)))
    y, rows = moe_serve(cfg, ws, x)
    gates, idx, _ = router_topk(cfg, ws["router"], x)
    idx, gates = np.asarray(idx), np.asarray(gates)
    held = (idx >= 2) & (idx < 4)
    assert int(rows) == held.sum() == 2 * B * S
    xf = np.asarray(x, np.float64)
    want = np.zeros_like(xf)
    for b in range(B):
        for s in range(S):
            for k in np.flatnonzero(held[b, s]):
                e = idx[b, s, k] - 2
                g = xf[b, s] @ np.asarray(ws["we_g"][e], np.float64)
                u = xf[b, s] @ np.asarray(ws["we_i"][e], np.float64)
                h = g / (1 + np.exp(-g)) * u
                want[b, s] += gates[b, s, k] * (
                    h @ np.asarray(ws["we_o"][e], np.float64))
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
