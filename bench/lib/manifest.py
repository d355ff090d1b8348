"""`BENCHMARK.json` and the files it names, found by name.

A cell names a configuration and a traffic mix; each lives in a file of
its own (`bench/configs/<config>.json`, `bench/traffic/<traffic>.json`),
each per-layer metric has a reader `bench/metrics/<metric>.py`, and each
cell's correctness limits sit in `bench/limits/<cell>.json`. A later
change adds a cell, a mix, a configuration or a metric by adding files
and entries, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list       # metric entries this cell reports, --trace 0
    per_layer: list        # metric entries this cell reports, --trace 1


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def metrics_of(manifest: dict, section: str, cell: str) -> list:
    """Metrics of `section` a cell reports: those without a `workloads`
    key everywhere, the others where they list the cell."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def load_cell(name: str, root: Path = ROOT, manifest: dict | None = None,
              ) -> Cell:
    manifest = load_manifest(root) if manifest is None else manifest
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    bench = root / "bench"
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=_load_json(root / configs[w["config"]]["file"]),
        traffic=_load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(bench / "limits" / f"{name}.json"),
        end_to_end=metrics_of(manifest, "end_to_end", name),
        per_layer=metrics_of(manifest, "per_layer", name),
    )


def load_reader(metric: str, root: Path = ROOT):
    """The `read(ctx)` function of `bench/metrics/<metric>.py`."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_manifest(manifest: dict, root: Path = ROOT) -> list[str]:
    """Problems with the manifest's names, units and cross references
    (empty when it is sound)."""
    errs = []
    sections = ("configs", "workloads", "end_to_end", "per_layer")
    for sec in sections:
        seen = set()
        for e in manifest[sec]:
            if not NAME_RE.match(e["name"]):
                errs.append(f"{sec}: bad name {e['name']!r}")
            if e["name"] in seen:
                errs.append(f"{sec}: duplicate name {e['name']!r}")
            seen.add(e["name"])
    metric_names = [m["name"] for s in ("end_to_end", "per_layer")
                    for m in manifest[s]]
    if len(set(metric_names)) != len(metric_names):
        errs.append("a metric name is used twice")
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    for c in manifest["configs"]:
        for k in c["reduced"]:
            if not NAME_RE.match(k):
                errs.append(f"config {c['name']}: bad reduced key {k!r}")
        if not (root / c["file"]).is_file():
            errs.append(f"config {c['name']}: no file {c['file']}")
        if not any(w["config"] == c["name"] for w in cells.values()):
            errs.append(f"config {c['name']} has no cell")
    pairs = set()
    for w in cells.values():
        for key in ("config", "traffic"):
            if not NAME_RE.match(w[key]):
                errs.append(f"cell {w['name']}: bad {key} {w[key]!r}")
        if w["config"] not in configs:
            errs.append(f"cell {w['name']}: unknown config {w['config']}")
        if not (root / "bench" / "traffic" / f"{w['traffic']}.json").is_file():
            errs.append(f"cell {w['name']}: no traffic file")
        if not (root / "bench" / "limits" / f"{w['name']}.json").is_file():
            errs.append(f"cell {w['name']}: no limits file")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"cell {w['name']}: config and traffic repeat")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            errs.append(f"cell {w['name']}: chips must be 1 or 4")
    for sec in ("end_to_end", "per_layer"):
        for m in manifest[sec]:
            if not UNIT_RE.match(m["unit"]):
                errs.append(f"{m['name']}: bad unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                errs.append(f"{m['name']}: better must be lower|higher")
            for c in m.get("workloads", []):
                if c not in cells:
                    errs.append(f"{m['name']}: unknown cell {c}")
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("no setup_s metric")
    for m in manifest["per_layer"]:
        if m["moves"] not in e2e:
            errs.append(f"{m['name']}: moves unknown metric {m['moves']}")
            continue
        if not (root / "bench" / "metrics" / f"{m['name']}.py").is_file():
            errs.append(f"{m['name']}: no reader file")
        for c in m.get("workloads", list(cells)):
            reported = {x["name"] for x in metrics_of(manifest,
                                                      "end_to_end", c)}
            if m["moves"] not in reported:
                errs.append(f"{m['name']}: cell {c} does not report "
                            f"{m['moves']}")
    for c in cells:
        e = {x["name"] for x in metrics_of(manifest, "end_to_end", c)}
        if "setup_s" not in e or len(e) < 2:
            errs.append(f"cell {c}: needs setup_s and another metric")
        if not metrics_of(manifest, "per_layer", c):
            errs.append(f"cell {c}: no per-layer metric")
    return errs
