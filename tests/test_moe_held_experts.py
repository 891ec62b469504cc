"""The MoE layer at one chip's expert-parallel share: the published
group-limited router (DeepSeek-V3's noaux_tc), the held experts' part of
the result, and dropless serving under skewed routing."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models import layers as L

D, F = 32, 16


def _cfg(E=64, k=8, n_group=8, topk_group=4, held_from=0, num_held=0,
         scale=2.5):
    moe = MoEConfig(num_experts=E, top_k=k, d_expert=F, num_shared=1,
                    router="noaux_tc", n_group=n_group,
                    topk_group=topk_group, routed_scaling=scale,
                    held_from=held_from, num_held=num_held,
                    capacity_factor=1.25)
    return ArchConfig(name="moe-tiny", family="moe", num_layers=1,
                      d_model=D, num_heads=4, num_kv_heads=4, d_ff=64,
                      vocab_size=64, layer_pattern=("attn_moe",), moe=moe,
                      param_dtype="float32")


def _params(cfg, seed=0, bias_scale=0.05):
    p = L.init_moe(jax.random.key(seed), cfg)
    b = jax.random.normal(jax.random.key(seed + 1), p["router"]["b"].shape)
    p["router"]["b"] = bias_scale * b
    return p


def _hand_router(x, w, b, E, k, G, kg, scale):
    """Published noaux_tc selection, token by token, in numpy."""
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ w.astype(np.float64))))
    gates = np.zeros_like(s)
    for t in range(s.shape[0]):
        sb = s[t] + b
        grp = np.sort(sb.reshape(G, E // G), axis=1)[:, -2:].sum(1)
        keep = np.argsort(-grp)[:kg]
        cand = np.full(E, -np.inf)
        for g in keep:
            cand[g * (E // G):(g + 1) * (E // G)] = sb[g * (E // G):
                                                      (g + 1) * (E // G)]
        chosen = np.argsort(-cand)[:k]
        gates[t, chosen] = s[t, chosen] / s[t, chosen].sum() * scale
    return gates


def _dense_gates(cfg, p, x2d):
    g, idx, _ = L._router_gates(p, cfg.moe, x2d)
    out = np.zeros((x2d.shape[0], cfg.moe.num_experts))
    np.put_along_axis(out, np.asarray(idx), np.asarray(g), axis=1)
    return out


@pytest.mark.parametrize("bias_scale", [0.0, 0.05, 0.5])
def test_noaux_tc_router_matches_hand_selection(bias_scale):
    """Sigmoid scores; the bias picks (groups by their best two, the best 4
    of 8 groups searched, the top 8 in them) but never weighs; gates
    normalized and scaled by 2.5."""
    cfg = _cfg()
    p = _params(cfg, bias_scale=bias_scale)
    x = jax.random.normal(jax.random.key(3), (40, D))
    got = _dense_gates(cfg, p, x)
    want = _hand_router(np.asarray(x), np.asarray(p["router"]["w"]),
                        np.asarray(p["router"]["b"]), 64, 8, 8, 4, 2.5)
    assert ((got > 0) == (want > 0)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.sum(1), 2.5, rtol=1e-5)


def test_router_bias_selects_but_does_not_weigh():
    """A large bias on one expert puts it in every token's choice, at the
    weight of its unbiased score; chosen experts come from 4 groups."""
    cfg = _cfg()
    p = _params(cfg, bias_scale=0.0)
    p["router"]["b"] = p["router"]["b"].at[37].set(10.0)
    x = jax.random.normal(jax.random.key(4), (24, D))
    got = _dense_gates(cfg, p, x)
    s = np.asarray(jax.nn.sigmoid(x @ p["router"]["w"]))
    assert (got[:, 37] > 0).all()
    chosen = got > 0
    np.testing.assert_allclose(
        got[:, 37], s[:, 37] / np.where(chosen, s, 0).sum(1) * 2.5,
        rtol=1e-5)
    groups = chosen.reshape(24, 8, 8).any(-1).sum(-1)
    assert (groups <= 4).all()


def _share_params(full, lo, n):
    p = dict(full)
    for name in ("w_gate", "w_up", "w_down"):
        p[name] = {"w": full[name]["w"][lo:lo + n]}
    return p


def test_expert_shares_sum_to_the_uncut_layer():
    """64 experts over 32 chips, 2 held on each: the 32 shares' results,
    the shared expert counted once, add up to the uncut layer's; and the
    routing counters of the shares are the uncut layer's, cut."""
    full_cfg = _cfg()
    p = _params(full_cfg, seed=5)
    x = jax.random.normal(jax.random.key(6), (2, 12, D))
    y_full, _, routed_full = L.apply_moe(p, full_cfg, x, dropless=True)
    shared = L.apply_mlp(p["shared"], full_cfg, x.reshape(-1, D)
                         ).reshape(x.shape)
    total = -31 * shared
    counts = []
    for i in range(32):
        cfg = dataclasses.replace(full_cfg, moe=dataclasses.replace(
            full_cfg.moe, held_from=2 * i, num_held=2))
        y, _, r = L.apply_moe(_share_params(p, 2 * i, 2), cfg, x,
                              dropless=True)
        total = total + y
        counts.append(np.asarray(r))
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               rtol=1e-4, atol=1e-5)
    assert np.array_equal(np.concatenate(counts), np.asarray(routed_full))
    assert int(np.asarray(routed_full).sum()) == 2 * 12 * 8


def test_skewed_256_token_chunk_drops_no_token():
    """A prefill chunk of 256 tokens all routed to one held expert: the
    serving path gives every token its full part (per-token sum of gate
    x expert), where the training dispatch's capacity
    ceil(256 * 8 / 64 * 1.25) = 40 slots drops most of them."""
    cfg = _cfg(held_from=8, num_held=8)
    p = _params(cfg, seed=7, bias_scale=0.0)
    p["router"]["b"] = p["router"]["b"].at[9].set(10.0)     # held: 9 - 8
    x = jax.random.normal(jax.random.key(8), (1, 256, D))
    y, _, routed = L.apply_moe(p, cfg, x, dropless=True)
    assert int(np.asarray(routed)[1]) == 256

    gates = _dense_gates(cfg, p, x[0])[:, 8:16]
    act = jax.nn.silu
    want = np.asarray(L.apply_mlp(p["shared"], cfg, x[0]), np.float64)
    for e in range(8):
        h = act(x[0] @ p["w_gate"]["w"][e]) * (x[0] @ p["w_up"]["w"][e])
        want = want + gates[:, e:e + 1] * np.asarray(h @ p["w_down"]["w"][e])
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-4, atol=1e-5)

    y_cap, _, _ = L.apply_moe(p, cfg, x, dropless=False)
    dropped = np.abs(np.asarray(y_cap[0]) - want).max(1) > 1e-3
    assert dropped.sum() > 100


def test_capacity_path_unchanged_when_all_experts_held():
    """Training keeps its capacity semantics: with every expert held and a
    capacity no group can overflow, it equals the dropless result."""
    cfg = _cfg()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=64.0))
    p = _params(cfg, seed=9)
    x = jax.random.normal(jax.random.key(10), (2, 16, D))
    y_cap, aux, _ = L.apply_moe(p, cfg, x)
    y_dl, aux2, _ = L.apply_moe(p, cfg, x, dropless=True)
    np.testing.assert_allclose(np.asarray(y_cap), np.asarray(y_dl),
                               rtol=1e-4, atol=1e-5)
    assert float(aux) == pytest.approx(float(aux2))
