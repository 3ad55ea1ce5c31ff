"""The harness finds every configuration, traffic mix, cell, metric,
arrival process and length distribution by its name: adding one is new
files plus entries."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_loads(name):
    cell = harness.load(name)
    wl = {w["name"]: w for w in _bench()["workloads"]}[name]
    assert cell.conf["name"] == wl["config"]
    assert cell.mix["name"] == wl["traffic"]
    assert cell.cell["rate_per_s"] > 0
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    e2e = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in e2e
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.reader(m["name"]))


def test_configuration_files_match_their_entries():
    for entry in _bench()["configs"]:
        conf = json.loads((ROOT / entry["file"]).read_text())
        assert conf["name"] == entry["name"]
        assert sorted(conf["reduced"]) == sorted(entry["reduced"])
        for key, cut in conf["reduced"].items():
            assert conf[key] == cut["here"] != cut["published"]


def test_new_config_mix_and_cell_are_found_by_name(tmp_path):
    bench = _bench()
    base = bench["configs"][0]
    conf = json.loads((ROOT / base["file"]).read_text())
    conf["name"] = "newconf"
    for rel, obj in [("bench/configs/newconf.json", conf),
                     ("bench/traffic/newmix.json",
                      dict(json.loads((ROOT / "bench/traffic/long.json")
                                      .read_text()), name="newmix")),
                     ("bench/cells/newconf.newmix.json",
                      {"rate_per_s": 1.0, "limits": {"max_logit_gap": 1}})]:
        (tmp_path / rel).parent.mkdir(parents=True, exist_ok=True)
        (tmp_path / rel).write_text(json.dumps(obj))
    bench["configs"].append(dict(base, name="newconf",
                                 file="bench/configs/newconf.json"))
    bench["workloads"].append({"name": "newconf.newmix", "config": "newconf",
                               "traffic": "newmix", "chips": 1, "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + ["newconf.newmix"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load("newconf.newmix", root=tmp_path)
    assert cell.conf["name"] == "newconf" and cell.mix["name"] == "newmix"
    assert cell.cell["rate_per_s"] == 1.0
    assert len(cell.per_layer) == len(bench["per_layer"])
    assert len(cell.end_to_end) == len(bench["end_to_end"])


def test_setup_s_is_every_cells_metric():
    bench = _bench()
    setup = {m["name"]: m for m in bench["end_to_end"]}["setup_s"]
    assert "workloads" not in setup
    for w in bench["workloads"]:
        names = {m["name"] for m in harness.load(w["name"]).end_to_end}
        assert "setup_s" in names
    listed = dict(bench["end_to_end"][0], workloads=["other.cell"])
    assert not harness._reports(listed, "newconf.newmix")
    assert harness._reports(setup, "newconf.newmix")


def test_new_metric_reader_is_found_by_name(tmp_path, monkeypatch):
    import bench.metrics
    (tmp_path / "new_share.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    monkeypatch.setattr(bench.metrics, "__path__",
                        list(bench.metrics.__path__) + [str(tmp_path)])
    assert harness.reader("new_share")(None) == 42.0


CLOSED = """
import math


class Process:
    \"\"\"A closed backlog: ``depth`` requests in the system at all times.\"\"\"
    def __init__(self, spec, rate, seconds, rng):
        self.depth, self.n, self._next = spec["depth"], spec["n"], 0

    def release(self, now, in_system):
        k = min(self.depth - in_system, self.n - self._next)
        self._next += max(0, k)
        return [now] * max(0, k)

    def wake(self, now):
        return now if self._next < self.n else math.inf
"""

UNIFORM = """
def quantile(spec, q):
    return spec["min"] + (spec["max"] - spec["min"]) * q
"""


@pytest.fixture
def new_modules(tmp_path, monkeypatch):
    import bench.arrivals
    import bench.lengths
    (tmp_path / "arr").mkdir()
    (tmp_path / "len").mkdir()
    (tmp_path / "arr" / "closed_stub.py").write_text(CLOSED)
    (tmp_path / "len" / "uniform_stub.py").write_text(UNIFORM)
    monkeypatch.setattr(bench.arrivals, "__path__",
                        list(bench.arrivals.__path__)
                        + [str(tmp_path / "arr")])
    monkeypatch.setattr(bench.lengths, "__path__",
                        list(bench.lengths.__path__)
                        + [str(tmp_path / "len")])


def test_new_arrival_kind_and_length_distribution_drive_a_window(
        new_modules, tiny_cell):
    tiny_cell.mix["arrivals"] = {"kind": "closed_stub", "depth": 6, "n": 40}
    tiny_cell.mix["prompt"] = {"dist": "uniform_stub", "min": 32, "max": 96}
    cfg, params, _ = harness.setup(tiny_cell, 7)
    w = harness.window(tiny_cell, cfg, params, seed=7, seconds=2.0,
                       rate=1.0)
    assert len(w.log) > 6               # released as others finished
    assert len(w.finished()) >= len(w.log) - 6
    assert all(32 <= d.prompt_len <= 96 for d in w.log.values())


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_chip_means_no_result():
    name = _bench()["workloads"][0]["name"]
    p = _run(ROOT, "--workload", name, "--seed", str(2 ** 31 + 5),
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "accelerator" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in _bench()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    name = _bench()["workloads"][0]["name"]
    p = _run(tmp_path, "--workload", name, "--seed", "1", "--seconds", "1")
    assert p.returncode != 0 and p.stdout.strip() == ""
