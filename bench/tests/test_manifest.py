"""BENCHMARK.json and the files it names: names and units use the
allowed characters, every cell finds its configuration, mix, limits and
readers by name, each per-layer metric's `moves` metric is reported in
every cell it lists, and a new cell is added by adding files only."""
import json
import shutil

import pytest

from bench.lib import manifest

ROOT = manifest.ROOT


@pytest.fixture(scope="module")
def man():
    return manifest.load_manifest(ROOT)


def test_manifest_is_sound(man):
    assert manifest.check_manifest(man, ROOT) == []


def test_contract_keys(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    for c in man["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in man["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


@pytest.mark.parametrize("cell", [w["name"] for w in manifest.load_manifest(
    ROOT)["workloads"]])
def test_cell_files_load(man, cell):
    c = manifest.load_cell(cell, ROOT, man)
    assert c.config["name"] == c.config_name
    assert c.traffic["loop"] in ("open", "closed")
    assert {"bad_ids", "unfinished"} <= set(c.limits)
    for m in c.per_layer:
        assert callable(manifest.load_reader(m["name"], ROOT))


def test_bad_names_are_caught(man):
    bad = json.loads(json.dumps(man))
    bad["per_layer"][0]["name"] = "has space"
    bad["end_to_end"][0]["unit"] = "queries per second"
    errs = manifest.check_manifest(bad, ROOT)
    assert any("bad name" in e for e in errs)
    assert any("bad unit" in e for e in errs)


def test_moves_must_be_reported(man):
    bad = json.loads(json.dumps(man))
    cell = bad["workloads"][0]["name"]
    qps = next(x for x in bad["end_to_end"] if x["name"] == "qps")
    qps["workloads"] = []
    m = next(x for x in bad["per_layer"] if x["moves"] == "qps")
    m["workloads"] = [cell]
    assert any(f"cell {cell} does not report qps" in e
               for e in manifest.check_manifest(bad, ROOT))


def test_new_cell_by_adding_files_only(man, tmp_path):
    """A throwaway configuration, mix, limits and metric, added as files
    and manifest entries, with no file edited."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    new = json.loads(json.dumps(man))
    cfg = json.loads((ROOT / "bench/configs/sift1m-eq12-int8.json").read_text())
    cfg["name"] = "tiny-2k"
    cfg["data"]["n"] = 2048
    (tmp_path / "bench/configs/tiny-2k.json").write_text(json.dumps(cfg))
    mix = {"loop": "closed", "outstanding": 4, "pool_qps": 10,
           "warmup": [[4, 4]], "query_noise": 0.05,
           "filters": [{"kind": "equal", "share": 1.0}]}
    (tmp_path / "bench/traffic/eq4.closed.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/tiny-2k.eq4.closed.json").write_text(
        json.dumps({"bad_ids": 0, "unfinished": 0}))
    (tmp_path / "bench/metrics/completed.tiny.py").write_text(
        "def read(ctx):\n    return ctx['n_completed']\n")
    new["configs"].append({"name": "tiny-2k", "source": "test",
                           "file": "bench/configs/tiny-2k.json",
                           "reduced": ["n"], "why": "test"})
    new["workloads"].append({"name": "tiny-2k.eq4.closed",
                             "config": "tiny-2k", "traffic": "eq4.closed",
                             "chips": 1, "why": "test"})
    new["per_layer"].append({"name": "completed.tiny", "unit": "requests",
                             "better": "higher", "source": "host_clock",
                             "layer": "serving", "moves": "qps",
                             "workloads": ["tiny-2k.eq4.closed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    assert manifest.check_manifest(new, tmp_path) == []
    c = manifest.load_cell("tiny-2k.eq4.closed", tmp_path)
    assert c.config["data"]["n"] == 2048
    assert [m["name"] for m in c.per_layer] == ["completed.tiny"]
    read = manifest.load_reader("completed.tiny", tmp_path)
    assert read({"n_completed": 7}) == 7
