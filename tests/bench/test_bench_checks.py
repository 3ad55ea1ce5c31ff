"""The comparison that decides ``correct``, driven through whole runs of a
tiny cell on the CPU (the harness's look for a chip skipped): a sound run
passes; a served token altered where it is produced, or a prefill step
that leaves the page pool unchanged, makes ``correct`` false; and the
float8 control, checked by the harness in the program's place, comes out
not correct."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch
from bench import correct as K
from bench import harness
from bench import weights as W
from bench.arch import qwen3

SEED = 2 ** 31 + 1234


@pytest.fixture
def small_cell(tiny_cell):
    # a short window: only the metrics that need no tail
    tiny_cell.cell["rate_per_s"] = 20.0
    tiny_cell.end_to_end = [m for m in tiny_cell.end_to_end
                            if m["name"] in ("output_tok_s", "setup_s")]
    return tiny_cell


@pytest.fixture(autouse=True)
def _keep_jax_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def _run(cell):
    return harness.run(cell, seed=SEED, seconds=2.5, trace=False,
                       t_start=time.monotonic(), require_chip=False)


def test_sound_run_is_correct(small_cell):
    out = _run(small_cell)
    assert out["correct"] is True
    assert out["checks"]["tokens_compared"]["value"] > 100
    assert out["checks"]["max_logit_gap"]["value"] \
        <= out["checks"]["max_logit_gap"]["limit"]
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


def test_altered_token_is_caught(small_cell, monkeypatch):
    from repro.serve import sched as S
    real = S.decode_macro
    vocab = small_cell.conf["vocab_size"]

    def altered(*a, **kw):
        toks, kv, st = real(*a, **kw)
        return jnp.where(toks >= 0, (toks + 1) % vocab, toks), kv, st

    monkeypatch.setattr(S, "decode_macro", altered)
    out = _run(small_cell)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > 0.5


def test_prefill_that_leaves_the_pool_unchanged_is_caught(small_cell,
                                                          monkeypatch):
    from repro.serve import sched as S
    monkeypatch.setattr(S, "write_pages_batched",
                        lambda kv, *a, **kw: kv)
    out = _run(small_cell)
    assert out["correct"] is False


def test_control_fails_the_limit_sound_runs_meet(small_cell):
    from bench import control
    out = control.readings(small_cell, SEED, 2.5, require_chip=False)
    served, ctl = out["served"], out["control"]
    assert served["tokens_compared"]["value"] \
        == ctl["tokens_compared"]["value"] > 100
    assert out["served_correct"] is True
    assert out["control_correct"] is False
    assert served["max_logit_gap"]["value"] <= served["max_logit_gap"][
        "limit"] < ctl["max_logit_gap"]["value"]
    assert out["kv_bf16_gap"] >= 0.0


def test_sample_holds_the_longest_and_enough_tokens():
    rng = np.random.default_rng(0)
    fin = [(np.zeros(int(rng.integers(5, 50)), np.int32),
            list(range(int(rng.integers(1, 30))))) for _ in range(40)]
    picks = K.sample(fin, SEED, min_tokens=100)
    longest = max(fin, key=lambda f: len(f[0]) + len(f[1]))
    assert picks[0] is longest
    assert sum(len(t) for _, t in picks) >= 100
    assert [id(p) for p in picks] == [id(p) for p in
                                      K.sample(fin, SEED, min_tokens=100)]


def test_one_layer_draw_equals_the_stacked_draw():
    from conftest import TINY_CONF
    params = arch.make_params(TINY_CONF, SEED)
    lw = W.layer(W.key_of(SEED), qwen3, TINY_CONF, 1)
    slot = params["segments"][0][0]
    # the same draws; jit and eager may round the scaling differently
    np.testing.assert_allclose(slot["attn"]["wq"][1], lw["wq"], rtol=1e-6)
    np.testing.assert_allclose(slot["mlp"]["wo"][1], lw["w_down"],
                               rtol=1e-6)
    g = W.globals_(W.key_of(SEED), qwen3, TINY_CONF)
    np.testing.assert_allclose(params["embed"]["tok"] * 8.0, g["embed"],
                               rtol=1e-6)
