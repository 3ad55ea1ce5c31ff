"""The expert layer the server runs: DeepSeek-V3's router against a plain
NumPy statement of the published rule, the grouped dropless layer over a
held share of the experts, and YaRN's frequencies and attention scale
against the published formulas."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import layers as L
from repro.models import moe as M
from repro.models.config import MoEConfig


def _np_gate(logits, bias, *, n_group, topk_group, top_k, scale):
    """``MoEGate.forward`` of the published DeepSeek-V3, token by token."""
    out_i, out_w = [], []
    for lg, in zip(logits):
        scores = 1.0 / (1.0 + np.exp(-lg.astype(np.float64)))
        biased = scores + bias
        e = len(scores)
        groups = biased.reshape(n_group, e // n_group)
        gscore = np.sort(groups, axis=1)[:, -2:].sum(axis=1)
        kept = np.argsort(-gscore, kind="stable")[:topk_group]
        mask = np.repeat(np.isin(np.arange(n_group), kept), e // n_group)
        cand = np.where(mask, biased, -np.inf)
        idx = np.argsort(-cand, kind="stable")[:top_k]
        w = scores[idx] / (scores[idx].sum() + 1e-20) * scale
        out_i.append(idx)
        out_w.append(w)
    return np.array(out_i), np.array(out_w)


def _router_case():
    """16 experts in 4 groups of 4, top 2 groups, top 3 experts; logits
    built so that the bias, the group limit and near-ties decide."""
    lg = np.zeros((4, 16), np.float32) - 3.0
    # token 0: experts 8 and 9 lead; for the last place expert 5 scores a
    # hair below expert 4 in the same group, and the bias lifts 5 over 4
    # (selection) while its weight stays unbiased
    lg[0, [8, 9, 4, 5]] = [3.0, 2.9, 2.0, 1.9999]
    # token 1: expert 0 alone is the best expert, but its group's second
    # best is poor: groups 2 and 3 (two good experts each) win, 0 is out
    lg[1, 0] = 4.0
    lg[1, [8, 9, 12, 13]] = [2.0, 1.9, 1.8, 1.7]
    # token 2: near-tie between groups decided by their second best
    lg[2, [0, 1, 4, 5]] = [1.0, 0.5001, 1.0, 0.5]
    # token 3: random
    lg[3] = np.random.default_rng(3).normal(size=16)
    bias = np.zeros(16, np.float32)
    bias[5] = 0.01
    bias[7] = -0.02
    return lg, bias


def test_sigmoid_router_matches_the_published_rule():
    lg, bias = _router_case()
    mo = MoEConfig(num_experts=16, top_k=3, d_expert=4,
                   scoring_func="sigmoid", n_group=4, topk_group=2,
                   routed_scaling_factor=2.5)
    # x = logits and an identity router: the logits are exactly the
    # hand-built ones (float32 at full precision)
    p = {"router": jnp.eye(16, dtype=jnp.float32),
         "router_bias": jnp.asarray(bias)}
    w, idx, _ = M.route(p, mo, jnp.asarray(lg))
    want_i, want_w = _np_gate(lg, bias, n_group=4, topk_group=2, top_k=3,
                              scale=2.5)
    got_i, got_w = np.asarray(idx), np.asarray(w)
    for t in range(len(lg)):
        order = np.argsort(got_i[t])
        worder = np.argsort(want_i[t])
        np.testing.assert_array_equal(got_i[t][order], want_i[t][worder])
        # float32 sigmoid against float64: a few ulp of 2.5
        np.testing.assert_allclose(got_w[t][order], want_w[t][worder],
                                   rtol=1e-6)
    assert 5 in got_i[0] and 4 not in got_i[0]      # the bias selected 5
    assert 0 not in got_i[1]                        # its group was dropped
    np.testing.assert_allclose(got_w.sum(axis=1), 2.5, rtol=1e-6)
    # the weight of the bias-selected expert is its unbiased score's share
    s = 1 / (1 + np.exp(-lg[0, got_i[0]].astype(np.float64)))
    np.testing.assert_allclose(got_w[0], s / s.sum() * 2.5, rtol=1e-6)


def _close(got, want):
    """float32 throughout, the sums in another order: a few ulp of the
    largest term."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=4e-6 * np.abs(want).max())


def _moe_cfg(**kw):
    cfg = C.reduced("deepseek-v3-671b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **kw))


def _layer_params(cfg, seed=0):
    p, _ = M.moe_init(jax.random.PRNGKey(seed), cfg)
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                                p["router_bias"].shape)
    return p


def _share(p, cfg, start, count, shared):
    mo = dataclasses.replace(cfg.moe, held_start=start, held=count,
                             num_shared=cfg.moe.num_shared if shared else 0)
    q = {k: v for k, v in p.items() if k != "shared" or shared}
    for k in ("wi_gate", "wi_up", "wo"):
        q[k] = p[k][start:start + count]
    return q, dataclasses.replace(cfg, moe=mo)


@pytest.mark.parametrize("held", [2, 4, 8])
def test_held_shares_sum_to_the_whole_layer(held):
    """Every share computes its own experts' part; the parts of all
    shares, with the shared expert counted once, are the whole layer."""
    cfg = _moe_cfg(top_k=2)
    p = _layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 7, cfg.d_model))
    whole, _, counts_whole = M.moe_apply_grouped(p, cfg, x)
    total = jnp.zeros_like(whole)
    pairs = 0
    for i, start in enumerate(range(0, cfg.moe.num_experts, held)):
        q, c = _share(p, cfg, start, held, shared=(i == 0))
        y, _, counts = M.moe_apply_grouped(q, c, x)
        total = total + y
        pairs += int(counts[0])
    _close(total, whole)
    assert pairs == int(counts_whole[0]) == 3 * 7 * cfg.moe.top_k


def _dense_layer(p, mo, x):
    """Every held expert on every token, weighted by the routing weight
    (0 where not chosen), plus the shared expert: the plain statement."""
    t = x.reshape(-1, x.shape[-1])
    w, idx, _ = M.route(p, mo, t)
    y = M._shared_out(p["shared"], t) if mo.num_shared else 0.0
    for j in range(mo.n_held):
        wj = jnp.sum(jnp.where(idx == mo.held_start + j, w, 0.0), axis=-1)
        h = jax.nn.silu(t @ p["wi_gate"][j]) * (t @ p["wi_up"][j])
        y = y + wj[:, None] * (h @ p["wo"][j])
    return y.reshape(x.shape)


def test_every_token_on_one_held_expert_drops_nothing():
    """The bias routes every token to expert 0: its group holds every
    token (a capacity-factor layer would drop most of them), and the
    result is the plain per-expert one."""
    cfg = _moe_cfg(held=2)
    p = _layer_params(cfg)
    p = dict(p, router_bias=p["router_bias"].at[0].set(10.0))
    for k in ("wi_gate", "wi_up", "wo"):
        p[k] = p[k][:2]
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16, cfg.d_model))
    y, _, counts = M.moe_apply_grouped(p, cfg, x)
    _, idx, _ = M.route(p, cfg.moe, x.reshape(-1, cfg.d_model))
    assert bool(jnp.all(jnp.any(idx == 0, axis=1)))
    _close(y, _dense_layer(p, cfg.moe, x))
    held_pairs = int(jnp.sum((idx >= 0) & (idx < 2)))
    assert int(counts[0]) == held_pairs >= 64
    assert int(counts[1]) == len(np.unique(np.asarray(idx)[np.asarray(
        idx) < 2]))


def test_long_inputs_run_in_chunks_without_a_drop(monkeypatch):
    """A buffer smaller than the input: the tokens run in chunks, padded
    to whole chunks, and none is dropped; masked tokens route nowhere."""
    cfg = _moe_cfg(held=4)
    p = _layer_params(cfg)
    for k in ("wi_gate", "wi_up", "wo"):
        p[k] = p[k][:4]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 37, cfg.d_model))
    valid = jnp.arange(37)[None] < jnp.asarray([[37], [20]])
    whole, _, counts = M.moe_apply_grouped(p, cfg, x, valid)
    monkeypatch.setattr(M, "DISPATCH_ROWS", 16)        # 8 tokens a chunk
    chunked, _, counts_c = M.moe_apply_grouped(p, cfg, x, valid)
    _close(chunked, whole)
    np.testing.assert_array_equal(np.asarray(counts_c)[0],
                                  np.asarray(counts)[0])
    # a masked token gets the shared expert only
    shared = M._shared_out(p["shared"], x[1, 20:])
    _close(whole[1, 20:], shared)


@pytest.mark.parametrize("stacked", [True, False])
@pytest.mark.parametrize("spec", [None, "bsr,rhk->bshk"])
def test_bfloat16_weights_give_float32_products(monkeypatch, stacked, spec):
    """bfloat16 weights under float32 activations: ``mm`` splits the
    activations into exact bfloat16 pieces, one stacked product (few
    rows) or one product per piece (many), and gets the float32 product
    of the stored weights -- 1e-6 of the largest term (sums in another
    order) where one bfloat16 pass of the activations is 1e-3 off."""
    monkeypatch.setattr(L, "STACK_ELEMS", 1 << 30 if stacked else 0)
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    if spec is None:
        x = jax.random.normal(ks[0], (3, 5, 96))
        w = jax.random.normal(ks[1], (96, 40)).astype(jnp.bfloat16)
        want = jnp.einsum("bsk,kn->bsn", x, w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
        once = jnp.einsum("bsk,kn->bsn", x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32)
    else:
        x = jax.random.normal(ks[0], (2, 3, 96))
        w = jax.random.normal(ks[1], (96, 4, 10)).astype(jnp.bfloat16)
        want = jnp.einsum(spec, x, w.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST)
        once = jnp.einsum(spec, x.astype(jnp.bfloat16), w,
                          preferred_element_type=jnp.float32)
    got = L.mm(x, w, spec)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 1e-6 * top
    assert float(jnp.abs(once - want).max()) >= 1e-4 * top
    parts = L.pieces(x, jnp.bfloat16)
    assert all(p.dtype == jnp.bfloat16 for p in parts)
    np.testing.assert_array_equal(
        np.asarray(sum(p.astype(jnp.float32) for p in parts[::-1])),
        np.asarray(x))


def test_bfloat16_experts_run_as_exact_pieces(monkeypatch):
    """Stored in bfloat16, the held experts take each pair as three
    bfloat16 rows of its group and sum them: the layer is the plain one on
    the widened weights, in chunks of a third of the pairs' rows too."""
    cfg = _moe_cfg(held=4, top_k=2)
    p = _layer_params(cfg)
    for k in ("wi_gate", "wi_up", "wo"):
        p[k] = p[k][:4].astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 11, cfg.d_model))
    wide = dict(p, **{k: p[k].astype(jnp.float32)
                      for k in ("wi_gate", "wi_up", "wo")})
    want = _dense_layer(wide, cfg.moe, x)
    y, _, counts = M.moe_apply_grouped(p, cfg, x)
    _close(y, want)
    monkeypatch.setattr(M, "DISPATCH_ROWS", 12)     # 2 tokens a chunk
    yc, _, counts_c = M.moe_apply_grouped(p, cfg, x)
    _close(yc, want)
    # the same pairs; experts touched are counted per chunk
    assert int(counts_c[0]) == int(counts[0]) == 2 * 11 * 2 - int(
        jnp.sum(M.route(p, cfg.moe, x.reshape(-1, cfg.d_model))[1] >= 4))
    assert int(counts_c[1]) >= int(counts[1])


def _source_inv_freq(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV3YarnRotaryEmbedding._set_cos_sin_cache``'s inv_freq."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def test_yarn_frequencies_and_attention_scale_match_the_source():
    cfg = C.get("deepseek-v3-671b")
    rs = cfg.rope_scaling
    got = np.asarray(L.rope_inv_freq(64, cfg.rope_theta, rs), np.float64)
    want = _source_inv_freq(64, 10000.0, 40.0, 4096, 32, 1)
    # float32 against float64
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # below the correction range the frequency is extrapolated, above it
    # interpolated by the factor
    base = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(got[:10], base[:10], rtol=2e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=2e-6)
    # 192^-0.5 x (0.1 ln 40 + 1)^2
    assert cfg.mla_scale == pytest.approx(0.135234, abs=5e-7)
    assert cfg.mla_scale == pytest.approx(
        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2, rel=1e-12)
    # without scaling the rope is the plain one
    plain = dataclasses.replace(cfg.mla, rope_scaling=None)
    assert dataclasses.replace(cfg, mla=plain).mla_scale == 1 / np.sqrt(192)
    np.testing.assert_array_equal(np.asarray(L.rope_inv_freq(64, 1e4)),
                                  np.asarray(1e4 ** (-jnp.arange(
                                      0, 32, dtype=jnp.float32) / 32)))
