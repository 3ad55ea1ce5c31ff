"""An architecture other than Qwen3 joins the benchmark as new files plus
entries: a tiny MLA + sparse-expert model, the program's own reduced
DeepSeek-V3, gets its architecture module, reference, configuration file,
traffic mix and cell, all written under a temporary directory, with
entries in a copy of ``BENCHMARK.json``.  The harness finds it by name and
drives a whole run of it on the CPU, and no file of the benchmark changes.

The reference here is the program's own unpaged forward, so the run
checks the harness's seams (lookup, weights, pool, check), not the
model: a real architecture brings a plain reference that imports
nothing of the program."""
import hashlib
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from bench import flops, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = "mla_moe_stub"
CELL = "mla-moe-tiny.tiny"

ARCH = '''
"""The program's reduced DeepSeek-V3 (MLA attention, routed and shared
experts) at the vocabulary and context the configuration file gives."""
import dataclasses

import jax

import repro.configs as C
from repro.models import model as M


def model_config(conf):
    return dataclasses.replace(
        C.reduced(conf["registry_base"]), name=conf["name"],
        vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"])


def make_params(conf, seed):
    cfg = model_config(conf)
    return jax.jit(lambda k: M.init(k, cfg)[0])(jax.random.PRNGKey(seed))


def model_flops(conf, prompts, decode_contexts):
    tokens = sum(prompts) + len(list(decode_contexts))
    return 2.0 * model_config(conf).active_param_count() * tokens
'''

REFERENCE = '''
"""The program's own unpaged forward over each whole sequence, right-padded
to one length (causality keeps the real rows exact): a stand-in that
checks the harness, not the model."""
import jax
import numpy as np

from bench import arch
from repro.models import model as M

PAD = 64


def served_logits(conf, seed, seqs, starts, precision="f32"):
    a = arch.of(conf)
    cfg, params = a.model_config(conf), a.make_params(conf, seed)
    fwd = jax.jit(lambda p, t: M.forward(p, cfg, t)[0])
    out = []
    for s, st in zip(seqs, starts):
        ids = np.zeros((1, PAD), np.int32)
        ids[0, :len(s)] = s
        out.append(np.asarray(fwd(params, ids)[0, st:len(s)], np.float32))
    return out
'''

CONF = {
    "name": "mla-moe-tiny", "reference": NAME,
    "registry_base": "deepseek-v3-671b", "vocab_size": 256,
    "max_position_embeddings": 256,
    "serving": {"max_active": 4, "page_size": 16, "max_len": 96,
                "hbm_pages": 32, "host_pages": 64, "default_period": 4,
                "profile_steps": 8, "trial_steps": 4,
                "warm_joiners": [1, 2, 4], "warm_macro_steps": [1, 2, 4],
                "warm_moves": 32},
}
MIX = {
    "name": "tiny", "arrivals": {"kind": "jittered", "jitter": 1.0},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 16,
               "max": 48},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
}

#: 110 requests due in 2.5 s: a p90 needs 100
CELL_FILE = {"rate_per_s": 44.0, "limits": {"max_logit_gap": 0.05}}


def _digest():
    """Every file of the benchmark, by content."""
    h = hashlib.sha256()
    files = [ROOT / "BENCHMARK.json"] + sorted(
        p for d in ("bench", "tests/bench") for p in (ROOT / d).rglob("*")
        if p.is_file() and "__pycache__" not in p.parts)
    for p in files:
        h.update(str(p.relative_to(ROOT)).encode() + p.read_bytes())
    return h.hexdigest()


@pytest.fixture
def new_arch(tmp_path, monkeypatch):
    """The new architecture's files under ``tmp_path``, found through the
    packages' search paths as files under ``bench/`` would be."""
    import bench.arch
    import bench.reference
    for pkg, src in ((bench.arch, ARCH), (bench.reference, REFERENCE)):
        d = tmp_path / pkg.__name__.split(".")[-1]
        d.mkdir()
        (d / f"{NAME}.py").write_text(src)
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(d)])
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    for rel, obj in [("bench/configs/mla-moe-tiny.json", CONF),
                     ("bench/traffic/tiny.json", MIX),
                     (f"bench/cells/{CELL}.json", CELL_FILE)]:
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(json.dumps(obj))
    bench_json["configs"].append({
        "name": "mla-moe-tiny", "source": "https://arxiv.org/abs/2412.19437",
        "file": "bench/configs/mla-moe-tiny.json", "reduced": [],
        "why": "x"})
    bench_json["workloads"].append({"name": CELL, "config": "mla-moe-tiny",
                                    "traffic": "tiny", "chips": 1,
                                    "why": "x"})
    for m in bench_json["end_to_end"]:
        if m["name"] in ("output_tok_s", "ttft_p90_ms"):
            m["workloads"] = m["workloads"] + [CELL]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))
    yield tmp_path
    for m in (f"bench.arch.{NAME}", f"bench.reference.{NAME}"):
        sys.modules.pop(m, None)


@pytest.fixture(autouse=True)
def _keep_jax_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("fault", [None, "altered_token"])
def test_mla_moe_architecture_joins_with_new_files_only(new_arch, fault,
                                                        monkeypatch):
    before = _digest()
    cell = harness.load(CELL, root=new_arch)
    assert cell.conf["reference"] == NAME
    assert [m["name"] for m in cell.end_to_end] == [
        "output_tok_s", "ttft_p90_ms", "setup_s"]
    assert flops.model_flops(cell.conf, [3], [4, 5]) > 0
    if fault:
        # a served token altered where it is produced: the check sees it
        from repro.serve import sched as S
        real, vocab = S.decode_macro, CONF["vocab_size"]

        def altered(*a, **kw):
            toks, kv, st = real(*a, **kw)
            return jnp.where(toks >= 0, (toks + 1) % vocab, toks), kv, st

        monkeypatch.setattr(S, "decode_macro", altered)
    out = harness.run(cell, seed=2 ** 31 + 77, seconds=2.5, trace=False,
                      t_start=time.monotonic(), require_chip=False)
    assert out["checks"]["tokens_compared"]["value"] > 50
    assert out["correct"] is (fault is None), out["checks"]
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p90_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert _digest() == before
