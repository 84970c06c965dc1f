"""Model math: SSD oracle, decode parity, MoE dispatch equivalence, rope."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import decode_step, forward, init_cache, init_model
from repro.models.config import ModelConfig
from repro.models.layers import apply_rope
from repro.models.moe import apply_moe, capacity, moe_defs
from repro.models.params import init_params
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(7)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

def _naive_ssd(x, Bm, Cm, dt, A, D):
    B, L, H, P = x.shape
    N = Bm.shape[-1]
    h = np.zeros((B, H, P, N), np.float64)
    ys = []
    for t in range(L):
        dA = np.exp(np.asarray(dt[:, t]) * np.asarray(A))
        h = dA[:, :, None, None] * h + np.einsum(
            "bh,bn,bhp->bhpn", np.asarray(dt[:, t]), np.asarray(Bm[:, t]),
            np.asarray(x[:, t]))
        y = np.einsum("bn,bhpn->bhp", np.asarray(Cm[:, t]), h) \
            + np.asarray(D)[:, None] * np.asarray(x[:, t])
        ys.append(y)
    return np.stack(ys, 1), h


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_ssd_chunked_equals_recurrence(chunk):
    B, L, H, P, N = 2, 64, 3, 8, 4
    x = jnp.asarray(RNG.normal(size=(B, L, H, P)), jnp.float32)
    Bm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    Cm = jnp.asarray(RNG.normal(size=(B, L, N)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = -jnp.asarray(RNG.uniform(0.5, 2.0, (H,)), jnp.float32)
    D = jnp.asarray(RNG.normal(size=(H,)), jnp.float32)
    y, hT = ssd_chunked(x, Bm, Cm, dt, A, D, chunk)
    y_ref, h_ref = _naive_ssd(x, Bm, Cm, dt, A, D)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), h_ref, rtol=1e-4, atol=1e-4)


def test_ssd_initial_state_carried():
    B, L, H, P, N = 1, 32, 2, 4, 4
    mk = lambda s: jnp.asarray(RNG.normal(size=s), jnp.float32)
    x, Bm, Cm = mk((B, L, H, P)), mk((B, L, N)), mk((B, L, N))
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    D = jnp.zeros((H,), jnp.float32)
    # split into halves with state handoff == full run
    y_full, h_full = ssd_chunked(x, Bm, Cm, dt, A, D, 8)
    y1, h1 = ssd_chunked(x[:, :16], Bm[:, :16], Cm[:, :16], dt[:, :16],
                         A, D, 8)
    y2, h2 = ssd_chunked(x[:, 16:], Bm[:, 16:], Cm[:, 16:], dt[:, 16:],
                         A, D, 8, h0=h1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode parity: stepwise decode reproduces full-sequence forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["granite-3-2b", "mamba2-130m",
                                  "zamba2-7b", "deepseek-v3-671b"])
def test_decode_matches_forward(arch):
    import dataclasses
    # f32 params: checks *structural* parity tightly — bf16 drifts ~5% by
    # position 16 through stacked SSD recurrences (expected accumulation).
    # capacity_factor high enough that the MoE drops no tokens: capacity
    # dropping legitimately differs between batched forward (per-sequence
    # capacity) and one-token decode.
    cfg = dataclasses.replace(get_config(arch, smoke=True),
                              param_dtype="float32", capacity_factor=8.0)
    params = init_model(cfg, jax.random.PRNGKey(1))
    B, S = 2, 16
    toks = jnp.asarray(RNG.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    logits_full, _ = forward(cfg, params, {"tokens": toks})
    cache = init_cache(cfg, B, S + 4)
    outs = []
    step = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))
    for pos in range(S):
        lg, cache = step(params, cache, toks[:, pos:pos + 1], pos)
        outs.append(lg)
    logits_dec = jnp.stack(outs, axis=1)
    a = np.asarray(logits_full, np.float32)
    b = np.asarray(logits_dec, np.float32)
    scale = np.abs(a).max()
    assert np.abs(a - b).max() / scale < 1e-4, arch


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def _moe_cfg(cf=4.0):
    return ModelConfig(
        name="moe-test", family="moe", num_layers=1, d_model=32,
        vocab_size=64, num_heads=2, num_kv_heads=2, head_dim=16,
        num_experts=4, experts_per_token=2, moe_d_ff=16,
        capacity_factor=cf, router_impl="softmax")


def test_moe_dispatch_impls_agree():
    """scatter (push), gather (pull) and onehot (einsum) dispatch agree."""
    cfg = _moe_cfg()
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.asarray(RNG.normal(size=(2, 16, 32)) * 0.3, jnp.float32)
    out_s, aux_s = apply_moe(cfg, p, x, impl="scatter")
    for impl in ("onehot", "gather"):
        out_o, aux_o = apply_moe(cfg, p, x, impl=impl)
        np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_o),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(aux_s), float(aux_o), rtol=1e-5)


def test_moe_capacity_drops_tokens():
    """Tiny capacity factor must drop tokens (outputs differ from cf=4)."""
    cfg_big = _moe_cfg(cf=4.0)
    cfg_small = _moe_cfg(cf=0.25)
    p = init_params(moe_defs(cfg_big), jax.random.PRNGKey(0), "float32")
    x = jnp.asarray(RNG.normal(size=(1, 32, 32)) * 0.3, jnp.float32)
    out_big, _ = apply_moe(cfg_big, p, x, impl="scatter")
    out_small, _ = apply_moe(cfg_small, p, x, impl="scatter")
    assert capacity(cfg_small, 32) < capacity(cfg_big, 32)
    assert not np.allclose(np.asarray(out_big), np.asarray(out_small))


def test_moe_shared_expert_contributes():
    cfg = ModelConfig(
        name="moe-shared", family="moe", num_layers=1, d_model=32,
        vocab_size=64, num_heads=2, num_kv_heads=2, head_dim=16,
        num_experts=4, experts_per_token=2, moe_d_ff=16,
        num_shared_experts=1)
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(0), "float32")
    x = jnp.asarray(RNG.normal(size=(1, 8, 32)) * 0.3, jnp.float32)
    out, _ = apply_moe(cfg, p, x)
    p0 = jax.tree_util.tree_map(jnp.zeros_like, p["shared"])
    out0, _ = apply_moe(cfg, {**p, "shared": p0}, x)
    assert not np.allclose(np.asarray(out), np.asarray(out0))


def _noaux_cfg(**kw):
    base = dict(name="noaux-test", family="moe", num_layers=1, d_model=32,
                vocab_size=64, num_heads=2, num_kv_heads=2, head_dim=16,
                num_experts=16, experts_per_token=4, moe_d_ff=16,
                router_impl="sigmoid", topk_method="noaux_tc", n_group=4,
                topk_group=2, routed_scaling_factor=2.5)
    base.update(kw)
    return ModelConfig(**base)


def _plain_noaux_tc(logits, bias, n_group, topk_group, k, factor):
    """DeepSeek-V3's router, token by token in numpy."""
    ids, weights = [], []
    for row in np.asarray(logits, np.float64).reshape(-1, logits.shape[-1]):
        s = 1.0 / (1.0 + np.exp(-row))
        select = s + np.asarray(bias, np.float64)
        groups = select.reshape(n_group, -1)
        group_score = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-group_score, kind="stable")[:topk_group]
        size = groups.shape[1]
        candidates = [e for g in kept for e in range(g * size, (g + 1) * size)]
        chosen = sorted(candidates, key=lambda e: -select[e])[:k]
        w = s[chosen]
        ids.append(chosen)
        weights.append(w / w.sum() * factor)
    return np.array(ids), np.array(weights)


def test_noaux_tc_routing_matches_plain_implementation():
    """s = sigmoid(x W_r); groups by the sum of their top 2 of s + b; the top
    k of s + b within the kept groups; weights s, normalised, times 2.5."""
    from repro.models.moe import _router

    cfg = _noaux_cfg()
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(3), "float32")
    p["router_bias"] = jnp.asarray(RNG.normal(size=16) * 0.05, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 24, 32)), jnp.float32)
    topv, topi, logits = _router(cfg, p, x)
    ids, weights = _plain_noaux_tc(logits, p["router_bias"], 4, 2, 4, 2.5)
    got = np.asarray(topi).reshape(-1, 4)
    order = np.argsort(got, axis=1)
    np.testing.assert_array_equal(np.take_along_axis(got, order, 1),
                                  np.sort(ids, axis=1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(topv).reshape(-1, 4), order, 1),
        np.take_along_axis(weights, np.argsort(ids, axis=1), 1), rtol=1e-5)
    # the bias moves the choice: without it, some token chooses otherwise
    ids0, _ = _plain_noaux_tc(logits, np.zeros(16), 4, 2, 4, 2.5)
    assert not np.array_equal(np.sort(ids0, 1), np.sort(ids, 1))


def _router_before(cfg, p, x):
    """The router as it was before noaux_tc, the reference for the default
    fields."""
    from jax import lax

    logits = jnp.einsum("bsd,de->bse", x, p["router"]).astype(jnp.float32)
    if cfg.router_impl == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        topv, topi = lax.top_k(scores, cfg.experts_per_token)
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, cfg.experts_per_token)
    return topv, topi, logits


@pytest.mark.parametrize("router_impl", ["sigmoid", "softmax"])
def test_router_defaults_route_as_before(router_impl):
    """No groups, no bias and a factor of 1.0: every other configuration's
    routing is bit for bit what it was."""
    from repro.models.moe import _router

    cfg = dataclasses.replace(_moe_cfg(), router_impl=router_impl)
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(4), "float32")
    assert "router_bias" not in p
    x = jnp.asarray(RNG.normal(size=(2, 16, 32)), jnp.float32)
    for got, want in zip(jax.jit(lambda x: _router(cfg, p, x))(x),
                         jax.jit(lambda x: _router_before(cfg, p, x))(x)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("router_impl", ["softmax", "noaux_tc"])
def test_moe_grouped_matches_scatter_without_drops(router_impl):
    """The dropless grouped op against scatter dispatch, at a capacity no
    expert overflows."""
    cfg = (_moe_cfg(cf=8.0) if router_impl == "softmax"
           else _noaux_cfg(capacity_factor=16.0))
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(5), "float32")
    x = jnp.asarray(RNG.normal(size=(2, 16, 32)) * 0.3, jnp.float32)
    out_s, aux_s = apply_moe(cfg, p, x, impl="scatter")
    out_g, aux_g = apply_moe(cfg, p, x, impl="grouped")
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_s),
                               rtol=1e-5, atol=1e-6)
    assert float(aux_g) == float(aux_s)


def test_moe_expert_shares_add_up_to_the_layer():
    """Expert parallelism's cut: each share holds E_held of the E experts,
    routes over all E and computes its own experts' part; the shares'
    parts, with the shared expert (which every share computes alike)
    counted once, add up to the uncut layer."""
    from repro.models.layers import apply_mlp

    cfg = _noaux_cfg(num_shared_experts=1, capacity_factor=16.0)
    p = init_params(moe_defs(cfg), jax.random.PRNGKey(6), "float32")
    p["router_bias"] = jnp.asarray(RNG.normal(size=16) * 0.05, jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 16, 32)) * 0.3, jnp.float32)
    whole, _ = apply_moe(cfg, p, x, impl="scatter")
    shared = apply_mlp(p["shared"], x)
    held = 4
    total = shared
    for offset in range(0, 16, held):
        share = {**p, **{w: p[w][offset:offset + held]
                         for w in ("wg", "wi", "wo")}}
        out, _ = apply_moe(cfg, share, x, impl="grouped",
                           expert_offset=offset)
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="grouped"):
        apply_moe(cfg, share, x, impl="scatter")


def test_model_runs_one_share_of_its_experts():
    """RunConfig states the experts a device holds: the parameter tree holds
    only those, and the model's MoE layers compute their part."""
    from repro.models import forward, init_model, model_defs
    from repro.models.model import RunConfig

    cfg = get_config("deepseek-v3-671b", smoke=True)
    run = RunConfig(moe_impl="grouped", moe_expert_offset=4,
                    moe_experts_held=4)
    defs = model_defs(cfg, run)
    assert defs["moe_blocks"]["moe"]["wg"].shape[1] == 4
    assert defs["moe_blocks"]["moe"]["router"].shape[-1] == cfg.num_experts
    params = init_model(cfg, jax.random.PRNGKey(7), run)
    tokens = jnp.asarray(RNG.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    logits, _ = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t}, run))(
        params, tokens)
    assert logits.shape == (1, 8, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ---------------------------------------------------------------------------
# rope
# ---------------------------------------------------------------------------

def test_rope_relative_property():
    """<rope(q,i), rope(k,j)> depends only on i - j."""
    d = 32
    q = jnp.asarray(RNG.normal(size=(1, 1, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(1, 1, d)), jnp.float32)

    def dot_at(i, j):
        pi = jnp.full((1, 1), i, jnp.int32)
        pj = jnp.full((1, 1), j, jnp.int32)
        qr = apply_rope(q, pi, 10_000.0)
        kr = apply_rope(k, pj, 10_000.0)
        return float(jnp.sum(qr * kr))

    np.testing.assert_allclose(dot_at(5, 3), dot_at(12, 10), rtol=1e-4)
    np.testing.assert_allclose(dot_at(7, 7), dot_at(0, 0), rtol=1e-4)
