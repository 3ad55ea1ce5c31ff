"""DeepSeek-V3 at a tiny size: the served program against the plain
reference (``bench/reference/deepseek_v3.py``) on seeded random weights,
the rope permutation, the configuration's refusals and the kernels' cost
functions.

Tolerances: the program computes the reference's function in float32,
with its leaves widened to float32 or as served in bfloat16 (its
products split the activations into exact bfloat16 pieces), and agrees
to 1e-4: sums in another order, at logits of about one.  The reference
with float8 operands (the control) reads above 0.5."""
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import correct as K
from bench import flops, harness
from bench.arch import deepseek_v3 as A
from bench.reference import deepseek_v3 as R

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 5


def _tiny():
    conf = json.loads((ROOT / "bench" / "configs"
                       / "deepseek-v3-tp4ep32.json").read_text())
    conf.update(hidden_size=64, intermediate_size=96, kv_lora_rank=16,
                q_lora_rank=24, qk_nope_head_dim=8, qk_rope_head_dim=8,
                v_head_dim=8, moe_intermediate_size=16,
                num_attention_heads=4, num_key_value_heads=4,
                num_hidden_layers=3, vocab_size=128, n_group=4,
                topk_group=2, num_experts_per_tok=4, n_routed_experts=4)
    conf["reduced"] = dict(conf["reduced"], n_routed_experts={
        "published": 16, "here": 4, "first": 4, "why": "tiny"})
    return conf


@pytest.fixture(scope="module")
def tiny():
    conf = _tiny()
    return conf, A.model_config(conf), A.make_params(conf, SEED)


def test_leaves_are_bfloat16_with_the_rope_columns_permuted(tiny):
    conf, cfg, params = tiny
    dt = {str(a.dtype) for a in jax.tree.leaves(params)}
    assert dt == {"bfloat16", "float32"}
    moe = params["segments"][1][0]["moe"]
    assert moe["router_bias"].dtype == jnp.float32
    assert moe["router"].shape == (2, 64, 16)          # the whole router
    assert moe["wi_gate"].shape == (2, 4, 64, 16)      # the held experts
    assert cfg.moe.held_start == 4 and cfg.moe.n_held == 4
    key = jax.random.PRNGKey(SEED)
    raw = A.layer_leaves(key, conf, 0, ("w_kr", "w_uq"))
    perm = A.rope_perm(8)
    np.testing.assert_array_equal(
        np.asarray(params["segments"][0][0]["attn"]["w_kr"][0]),
        np.asarray(raw["w_kr"][:, perm].astype(jnp.bfloat16)))
    np.testing.assert_array_equal(
        np.asarray(params["segments"][0][0]["attn"]["w_uq"][0][..., 8:]),
        np.asarray(raw["w_uq"][..., 8:][..., perm].astype(jnp.bfloat16)))


def test_permuted_half_rope_is_the_published_interleaved_rope(tiny):
    """The program's rope on the permuted columns gives what the published
    rope gives on the interleaved ones (de-interleaved): the same values
    in the same order."""
    from repro.models import layers as L
    conf, cfg, _ = tiny
    x = jax.random.normal(jax.random.PRNGKey(1), (300, 4, 64))
    cos, sin = R._yarn_cos_sin(dict(conf, qk_rope_head_dim=64), 300)
    want = R._rope(x, cos, sin)
    got = L.rope(x[..., A.rope_perm(64)], jnp.arange(300),
                 float(conf["rope_theta"]), cfg.rope_scaling)
    # the same float32 frequencies and angles, so the same tables; the
    # rotation then rounds the same products (1e-6: an addition's order)
    ang = jnp.arange(300, dtype=jnp.float32)[:, None] * L.rope_inv_freq(
        64, float(conf["rope_theta"]), cfg.rope_scaling)
    np.testing.assert_array_equal(np.asarray(jnp.cos(ang)),
                                  np.asarray(cos[:, :32]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6)


def _serve(params, cfg, rng):
    """Three requests through the served path (packed prefill, paged macro
    decode): (prompt, served tokens) each, and the recorder's events."""
    from repro.obs import telemetry
    prev = telemetry.get()
    rec = telemetry.install(telemetry.Recorder())
    try:
        server = harness.Server(params, cfg, dict(
            max_active=4, page_size=16, max_len=96, hbm_pages=32,
            host_pages=64, default_period=4, profile_steps=8,
            trial_steps=4))
        b = server.batcher
        reqs = [server.request(i, rng.integers(0, 128, 20 + 9 * i).astype(
            np.int32), 12) for i in range(3)]
        for r in reqs:
            b.submit(r)
        served = {r.rid: [] for r in reqs}
        while b.active or b.queue:
            for rid, tok in b.step():
                served[rid].append(tok)
        server.close()
        return [(r.prompt, served[r.rid]) for r in reqs], rec.events()
    finally:
        telemetry.install(prev)


def test_prefill_and_macro_decode_agree_with_the_reference(tiny):
    from repro.models import model as mdl
    conf, cfg, params = tiny
    wide = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(0)
    lens = [37, 20]
    toks = np.zeros((2, 64), np.int32)
    seqs = []
    for i, n in enumerate(lens):
        seqs.append(rng.integers(0, 128, n).astype(np.int32))
        toks[i, :n] = seqs[-1]
    ref = R.served_logits(conf, SEED, seqs, [0, 0])
    for leaves in (wide, params):
        logits, cache = jax.jit(mdl.prefill_batched, static_argnums=1)(
            leaves, cfg, jnp.asarray(toks), jnp.asarray(lens, np.int32))
        for i, n in enumerate(lens):
            np.testing.assert_allclose(np.asarray(logits[i, 0]),
                                       ref[i][n - 1], atol=1e-4)
    full, _ = mdl.forward(wide, cfg, jnp.asarray(toks[:1, :37]))
    np.testing.assert_allclose(np.asarray(full[0]), ref[0], atol=1e-4)
    # the packed prefill counts its real tokens' held pairs: each token
    # has 4 of 16 experts, of which 4 are held
    pairs, touched = (int(v) for v in cache["moe"])
    assert 0 < pairs <= 57 * 4 * 2 and 0 < touched <= 4 * 2
    fp8 = R.served_logits(conf, SEED, seqs[:1], [0], "fp8")[0]
    assert np.abs(fp8 - ref[0]).max() > 0.5

    # paged macro decode: every served token is the reference's best, with
    # float32 leaves and as served
    for leaves in (wide, params):
        fin, events = _serve(leaves, cfg, np.random.default_rng(1))
        gap, n = K.served_gap(conf, SEED, fin)
        assert n == 36 and gap <= 1e-4
    macro = [e for e in events if e["type"] == "serve.macro"]
    assert macro and sum(e["moe_pairs_held"] for e in macro) > 0
    assert all(e["moe_experts_touched"] <= 4 * 2 * e["n_steps"]
               for e in macro)
    admit = [e for e in events if e["type"] == "serve.admit"]
    assert admit and all(e["moe_pairs_held"] > 0 for e in admit)


@pytest.mark.parametrize("key,value", [("scoring_func", "softmax"),
                                       ("topk_method", "greedy"),
                                       ("n_shared_experts", 2),
                                       ("norm_topk_prob", False)])
def test_model_config_refuses_what_the_program_does_not_serve(key, value):
    conf = dict(_tiny(), **{key: value})
    with pytest.raises(ValueError, match=key):
        A.model_config(conf)


def test_costs_count_the_published_work():
    # two rows over 10 and 0 keys: 10 keys of 576 float32 latents, one
    # live row's query (32 heads x 1088) and context
    f, b = A.paged_attention_mla_cost([10, 0], heads=32, kv_lora=512,
                                      rope=64, kv_itemsize=4, q_itemsize=4)
    assert f == 2.0 * 32 * (1024 + 64) * 10
    assert b == 576 * 4 * 10 + 32 * 1088 * 4
    f, b = A.expert_gmm_cost(3, 2, hidden=7168, expert_width=2048)
    assert f == 6.0 * 3 * 7168 * 2048
    assert b == 2 * 3 * 7168 * 2048 * 2 + 3 * ((2 * 7168 + 2048) * 4
                                               + (2 * 2048 + 7168) * 4)
    conf = _tiny()
    one = flops.model_flops(conf, [1], [])
    d, h = 64, 4
    mla = d * 24 + 24 * h * 16 + d * 24 + 16 * h * 16 + h * 8 * d
    moe = d * 16 + 3 * d * 16 * (4 * 4 / 16) + 3 * d * 16
    want = 2.0 * (3 * mla + 3 * d * 96 + 2 * moe) + 2.0 * d * 128 \
        + 2.0 * h * 24 * 3
    assert one == pytest.approx(want)


def test_the_parent_program_is_refused_before_any_weight(monkeypatch):
    """Without the router's fields the configuration fails at once."""
    import dataclasses

    from repro.models import config as MC
    fields = [f for f in dataclasses.fields(MC.MoEConfig)
              if f.name not in ("scoring_func", "n_group", "topk_group",
                                "routed_scaling_factor", "held_start",
                                "held")]
    old = dataclasses.make_dataclass(
        "MoEConfig", [(f.name, f.type, f) for f in fields], frozen=True)
    monkeypatch.setattr(MC, "MoEConfig", old)
    monkeypatch.setattr(A, "make_params", None)         # never reached
    with pytest.raises(TypeError):
        A.model_config(_tiny())


class _Trace:
    def __init__(self, op_s):
        self.op_s = op_s

    def op_time(self, op):
        return self.op_s.get(op, 0.0)


def _ctx(conf, events, op_s):
    from bench import clientmetrics as CM
    log = [CM.Delivery(0.0, 3, 100, [1.0, 1.5, 2.0])]
    return harness.Context(conf=conf, cfg=None, serving=conf["serving"],
                           log=log, window_s=51.0, setup_s=0.0,
                           events=events, trace=_Trace(op_s),
                           device_kind="TPU v5 lite", pool_itemsize=4)


def test_kernel_readers_count_the_cost_over_the_op_time():
    """The two kernels' roofline readers: the architecture's cost of what
    the window did over the op's device time; nothing where the op, the
    counters (a program without them) or the architecture's kernel is
    missing."""
    from bench import peaks
    from bench.metrics import expert_gmm_roofline as G
    from bench.metrics import paged_attention_mla_roofline as P
    conf = json.loads((ROOT / "bench" / "configs"
                       / "deepseek-v3-tp4ep32.json").read_text())
    pk = peaks.peaks("TPU v5 lite")
    ev = [{"type": "serve.macro", "moe_pairs_held": 5,
           "moe_experts_touched": 4},
          {"type": "serve.admit", "moe_pairs_held": 30,
           "moe_experts_touched": 8}]
    ctx = _ctx(conf, ev, {"paged_attention_mla": 2e-3, "gmm": 1e-2})
    f, b = A.paged_attention_mla_cost([101, 102], heads=32, kv_lora=512,
                                      rope=64, kv_itemsize=4, q_itemsize=4)
    want = 5 * max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert P.read(ctx) == pytest.approx(100 * want / 2e-3)
    f, b = A.expert_gmm_cost(35, 12, hidden=7168, expert_width=2048)
    want = max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
    assert G.read(ctx) == pytest.approx(100 * want / 1e-2)
    bare = _ctx(conf, [{"type": "serve.macro"}], {})
    assert P.read(bare) is None and G.read(bare) is None
    assert G.read(_ctx(conf, [{"type": "serve.macro"}], {"gmm": 1.0})) is None
    qwen = json.loads((ROOT / "bench" / "configs"
                       / "qwen3-14b-tp4.json").read_text())
    assert P.read(_ctx(qwen, ev, {"paged_attention_mla": 1.0})) is None
    assert G.read(_ctx(qwen, ev, {"gmm": 1.0})) is None
