"""The Qwen3 architecture module (``bench/arch/qwen3.py``) builds the same
program configuration, draws the same weights and counts the same FLOPs
as the benchmark did before architectures were found by name: literals
recorded from the earlier code on the CPU.  And an architecture with no
module fails when its cell is loaded, before any weight is drawn."""
import dataclasses
import json
import pathlib

import jax
import numpy as np
import pytest

from bench import arch, flops, harness
from bench import weights as W
from bench.arch import qwen3

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 1234


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


def _fields(name, d_model, heads, d_ff, layers):
    return {
        "name": name, "family": "dense", "d_model": d_model,
        "num_heads": heads, "num_kv_heads": 2, "head_dim": 128, "d_ff": d_ff,
        "vocab_size": 37984, "segments": ((("attn",), layers),),
        "window_size": 0, "qk_norm": True, "rope_theta": 1000000.0,
        "softcap": 0.0, "mlp_kind": "swiglu", "moe": None, "mla": None,
        "lru_width": 0, "tie_embeddings": False, "prefix_len": 0,
        "cond_len": 0, "cond_dim": 0, "max_seq_len": 40960,
        "dtype": "bfloat16", "param_dtype": "float32",
        "attention_impl": "reference", "moe_impl": "dense", "moe_chunk": 0,
        "remat": True, "unroll_layers": False,
        "supports_long_context": False}


@pytest.mark.parametrize("name,fields", [
    ("qwen3-14b-tp4", _fields("qwen3-14b-tp4", 5120, 10, 17408, 3)),
    ("qwen3-8b-tp4", _fields("qwen3-8b-tp4", 4096, 8, 12288, 4))])
def test_model_config_of_each_configuration_file(name, fields):
    cfg = arch.model_config(_conf(name))
    assert dataclasses.asdict(cfg) == fields


@pytest.mark.parametrize("name,want", [("qwen3-14b-tp4", 12357135503360.0),
                                       ("qwen3-8b-tp4", 9449482682368.0)])
def test_model_flops_of_each_configuration_file(name, want):
    got = flops.model_flops(_conf(name), [1, 1024, 2048, 4096],
                            [2, 1025, 3000, 4352])
    assert got == want


#: (float64 sum, sum of squares) of every leaf the reference draws for
#: ``TINY_CONF`` at ``SEED``: leaf.layer
DRAWS = {
    "embed": (-122.86086936829815, 16375.421855764853),
    "final_norm": (63.85107624530792, 64.33291993184383),
    "unembed": (38.18825931916945, 254.67800799921116),
    "k_norm.0": (15.875805914402008, 15.802712625921867),
    "norm1.0": (63.06573957204819, 62.65508551662909),
    "norm2.0": (62.7595431804657, 62.15757240172184),
    "q_norm.0": (15.655795931816101, 15.455767931737235),
    "w_down.0": (-2.1620432239105867, 66.31729238895036),
    "w_gate.0": (-6.840492316356176, 128.0079652496391),
    "w_up.0": (-13.207103060234658, 128.5742207433684),
    "wk.0": (-5.237448594751186, 33.001596716442464),
    "wo.0": (-1.6459992986929137, 65.83551525681204),
    "wq.0": (7.29423576705085, 62.93785917373793),
    "wv.0": (8.108010987398302, 32.278985372274434),
    "k_norm.1": (16.531676292419434, 17.28365387869139),
    "norm1.1": (63.50164973735809, 63.65458982204335),
    "norm2.1": (64.04968094825745, 64.58287273416434),
    "q_norm.1": (15.843418598175049, 15.8037212843401),
    "w_down.1": (10.724864289636571, 63.494252064105204),
    "w_gate.1": (5.241514357050619, 132.31603209807372),
    "w_up.1": (-9.966265488614226, 129.6595843837069),
    "wk.1": (2.6178623179293936, 30.699611970411922),
    "wo.1": (-8.908769578411011, 63.10479351665983),
    "wq.1": (-5.340522485139445, 64.37215937564106),
    "wv.1": (-14.644641903672891, 32.48789196344657),
}

#: (shape, sum, sum of squares) of every leaf of the served parameter
#: tree for ``TINY_CONF`` at ``SEED``
PARAMS = {
    "['embed']['tok']": ((256, 64), -15.35760867103727, 255.86596649632583),
    "['embed']['unembed']": ((64, 256), 38.18825931916945,
                             254.67800799921116),
    "['final_norm']": ((64,), 63.85107624530792, 64.33291993184383),
    "['segments'][0][0]['attn']['k_norm']": ((2, 16), 32.40748220682144,
                                             33.08636650461325),
    "['segments'][0][0]['attn']['q_norm']": ((2, 16), 31.49921452999115,
                                             31.259489216077334),
    "['segments'][0][0]['attn']['wk']": ((2, 64, 2, 16), -2.619586276821792,
                                         63.701208686854386),
    "['segments'][0][0]['attn']['wo']": ((2, 4, 16, 64), -10.554768877103925,
                                         128.94030877347188),
    "['segments'][0][0]['attn']['wq']": ((2, 64, 4, 16), 1.953713281911405,
                                         127.31001854937898),
    "['segments'][0][0]['attn']['wv']": ((2, 64, 2, 16), -6.536630916274589,
                                         64.766877335721),
    "['segments'][0][0]['mlp']['wi_gate']": ((2, 64, 128),
                                             -1.5989779593055573,
                                             260.32399734771286),
    "['segments'][0][0]['mlp']['wi_up']": ((2, 64, 128), -23.173368548848885,
                                           258.2338051270753),
    "['segments'][0][0]['mlp']['wo']": ((2, 128, 64), 8.562821065725984,
                                        129.81154445305557),
    "['segments'][0][0]['norm1']": ((2, 64), 126.56738930940628,
                                    126.30967533867243),
    "['segments'][0][0]['norm2']": ((2, 64), 126.80922412872314,
                                    126.74044513588619),
}


def _sums(a):
    a = np.asarray(a, np.float64)
    return pytest.approx((float(a.sum()), float((a * a).sum())),
                         rel=1e-6, abs=1e-6)


def test_every_leaf_the_reference_draws():
    from conftest import TINY_CONF
    key = W.key_of(SEED)
    got = dict(jax.jit(lambda k: W.globals_(k, qwen3, TINY_CONF))(key))
    for l in range(TINY_CONF["num_hidden_layers"]):
        lw = jax.jit(lambda k, l: W.layer(k, qwen3, TINY_CONF, l))(key, l)
        got.update({f"{n}.{l}": v for n, v in lw.items()})
    assert sorted(got) == sorted(DRAWS)
    for n, (s, ss) in DRAWS.items():
        assert (s, ss) == _sums(got[n]), n


def test_every_leaf_of_the_served_parameters():
    from conftest import TINY_CONF
    flat = jax.tree_util.tree_flatten_with_path(
        arch.make_params(TINY_CONF, SEED))[0]
    got = {jax.tree_util.keystr(k): v for k, v in flat}
    assert sorted(got) == sorted(PARAMS)
    for n, (shape, s, ss) in PARAMS.items():
        assert got[n].shape == shape and got[n].dtype == np.float32, n
        assert (s, ss) == _sums(got[n]), n


def test_leaf_order_is_the_fold_in_order():
    # the index a leaf's key folds in: reordering the table redraws
    # every weight
    assert qwen3.GLOBAL + qwen3.LAYER == (
        "embed", "unembed", "final_norm", "norm1", "wq", "wk", "wv",
        "q_norm", "k_norm", "wo", "norm2", "w_gate", "w_up", "w_down")


def test_unknown_architecture_fails_at_load(tmp_path, monkeypatch):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = bench["configs"][0]
    conf = dict(_conf(base["name"]), name="other", reference="nosuch")
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "configs" / "other.json").write_text(
        json.dumps(conf))
    bench["configs"].append(dict(base, name="other",
                                 file="bench/configs/other.json"))
    wl = dict(bench["workloads"][0], name="other.long", config="other")
    bench["workloads"].append(wl)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    drawn = []
    monkeypatch.setattr(W, "key_of", lambda seed: drawn.append(seed))
    with pytest.raises(ValueError, match=r"'nosuch'.*known: .*qwen3"):
        harness.load("other.long", root=tmp_path)
    assert drawn == []
