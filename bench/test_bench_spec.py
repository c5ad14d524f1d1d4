"""BENCHMARK.json's shape, and cells, mixes and metrics found by name."""
import json
import os
import re
import shutil

import pytest

from bench.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"]
    assert b["paths"] == ["bench"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"]: w for w in b["workloads"]}
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(
        cells)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
        for cell in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]
    spec = Spec(ROOT)
    for cell in cells:
        names = {m["name"] for m in spec.end_to_end(cell)}
        assert "setup_s" in names and len(names) >= 2
        assert spec.per_layer(cell)
    everything = (list(cfgs) + list(cells) + list(e2e)
                  + [m["name"] for m in b["per_layer"]])
    assert len(everything) == len(set(everything))
    for name in everything:
        assert NAME.match(name), name
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for text in ([c["why"] for c in b["configs"]]
                 + [w["why"] for w in b["workloads"]]
                 + [m["layer"] for m in b["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert len(json.dumps(b)) < 64 * 1024


def test_each_config_file_states_its_source_and_limits():
    spec = Spec(ROOT)
    for name, entry in spec.configs.items():
        cfg = spec.config(name)
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
        assert cfg["name"] == name and "param_bytes_gap" in cfg["limits"]


def test_a_new_config_traffic_and_metric_are_files_and_entries(tmp_path):
    """Adding a cell edits no file that is there: copy the benchmark, add
    files and entries, and the loader finds all of them."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "configs" / "extra-model.json").write_text(json.dumps({
        "name": "extra-model", "arch": "tinyllama-1.1b",
        "source": "https://example.org/extra", "reduced": [],
        "model": {"kind": "transformer"}, "limits": {"param_bytes_gap": 1}}))
    (root / "bench" / "traffic" / "extra-mix.json").write_text(json.dumps({
        "algorithm": "random", "samples": 8, "limits": {"failed": 0}}))
    (root / "bench" / "metrics" / "extra_metric.py").write_text(
        "def read(run):\n    return 42.0 if run else None\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "extra-model", "source": "https://x",
                         "file": "bench/configs/extra-model.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "extra-model.extra-mix",
                           "config": "extra-model", "traffic": "extra-mix",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "extra_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "search", "moves": "setup_s",
                           "workloads": ["extra-model.extra-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    spec = Spec(str(root))
    assert spec.config("extra-model")["arch"] == "tinyllama-1.1b"
    assert spec.traffic("extra-mix")["samples"] == 8
    assert [m["name"] for m in spec.per_layer("extra-model.extra-mix")] == [
        "extra_metric"]
    assert spec.readers("extra-model.extra-mix")["extra_metric"](True) == 42.0
    assert {m["name"] for m in spec.end_to_end("extra-model.extra-mix")} == {
        "setup_s"}
    for path, data in before.items():
        assert path.read_bytes() == data


@pytest.mark.parametrize("metric", [m["name"] for m in load()["per_layer"]])
def test_readers_find_nothing_in_an_empty_run(metric):
    """A reader with nothing to read returns None, never 0."""
    from bench.harness import Recorder
    from bench.metrics_io import RunData

    rec = Recorder(deadline=10.0, traced=True)
    rec.t0, rec.t1 = 0.0, 10.0
    run = RunData(rec=rec, trace=None, lo=0.0, hi=10.0,
                  peaks={"flops_bf16": 197e12}, traffic={}, config={})
    assert Spec(ROOT).reader(metric)(run) is None
