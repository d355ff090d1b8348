"""The whole run, at a tiny size on the CPU, through the test-only entry
(`harness.run(allow_cpu=True)`; the command refuses the CPU): each cell
comes out correct; its control and a truncated reference fail its
limits; and with the timed path broken underneath (an answer altered
where the scheduler produces it, half of every answer dropped, the
traversal stopped after the probe) the run comes out not correct. A
closed loop that completes more than its pool replays it, a slow one
still serves its whole recall set, and a recall set that is not whole
blocks within the pool is refused. The command itself exits non-zero,
printing no result, without a TPU."""
import dataclasses
import json
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import control
from bench.lib import harness, manifest

CELL = "sift1m-eq12-int8.eq.closed"
TINY = {"config": {"data": {"n": 2048},
                   "training": {"estimator_queries": 32,
                                "planner_queries": 32, "planner_trees": 8,
                                "chunk": 32}},
        "traffic": {"warmup": [[8, 12], [1, 2]], "outstanding": 8,
                    "pool_qps": 16, "order_block": 8, "recall_requests": 16}}
# a second, selective mix over the same rows: one label AND a value
# window holding 20-60 of its rows
SCAN = harness.deep_merge(TINY, {"traffic": {"filters": [{
    "kind": "contain_and_range", "share": 1.0, "passing_rows": [20, 60],
    "hard_fraction": 0.5}]}})


def tiny_cell(over):
    cell = manifest.load_cell(CELL)
    return dataclasses.replace(
        cell, config=harness.deep_merge(cell.config, over["config"]),
        traffic=harness.deep_merge(cell.traffic, over["traffic"]))


@pytest.mark.parametrize("over", [TINY, SCAN], ids=["eq", "selective"])
def test_sound_run_is_correct(over):
    out = harness.run(CELL, 2**31 + 11, 1.0, False, allow_cpu=True,
                      overrides=over)
    assert out["correct"], out["checks"]
    cell = manifest.load_cell(CELL)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) >= {"bad_ids", "dist_err_max", "recall_loss"}
    assert out["attempted"] >= 8 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"


def test_open_loop_mix_is_correct():
    """A mix file with `"loop": "open"`: requests submitted as they fall
    due, each timed from its due time."""
    over = harness.deep_merge(TINY, {"traffic": {
        "loop": "open", "rate_qps": 12.0, "warmup_open_seconds": 0.5}})
    out = harness.run(CELL, 7, 1.0, False, allow_cpu=True, overrides=over)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0


@pytest.mark.parametrize("over", [TINY, SCAN], ids=["eq", "selective"])
def test_control_and_truncation_fail(over):
    out = control.readings(tiny_cell(over), 2**31 + 12, 1.0, 40)
    assert not out["control"]["passes_limits"], out["control"]
    assert not out["truncated"]["passes_limits"], out["truncated"]
    assert out["truncated"]["recall_loss"] > 0.4


def _altered(finish):
    def fault(self, req, res_idx, res_dist, ndc, at):
        res_idx = np.array(res_idx)
        if req.rid % 3 == 0 and res_idx[0] >= 0:
            res_idx[0] = (res_idx[0] + 1) % self.engine.base_vectors.shape[0]
        return finish(self, req, res_idx, res_dist, ndc, at)
    return fault


def _truncated(finish):
    def fault(self, req, res_idx, res_dist, ndc, at):
        res_idx, res_dist = np.array(res_idx), np.array(res_dist)
        res_idx[len(res_idx) // 2:] = -1
        res_dist[len(res_dist) // 2:] = np.inf
        return finish(self, req, res_idx, res_dist, ndc, at)
    return fault


@pytest.mark.parametrize("fault,number", [(_altered, "dist_err_max"),
                                          (_truncated, "recall_loss")])
def test_broken_answers_are_caught(monkeypatch, fault, number):
    from repro.serve import scheduler

    monkeypatch.setattr(scheduler.CostAwareScheduler, "_finish",
                        fault(scheduler.CostAwareScheduler._finish))
    out = harness.run(CELL, 5, 1.0, True, allow_cpu=True, overrides=TINY)
    assert not out["correct"]
    check = out["checks"][number]
    assert check["value"] > check["limit"]
    assert "busy_s" in out["device"] and "breakdown" in out


def test_probe_only_traversal_is_caught():
    """Every budget cut to the probe's: the traversal stops after the
    probe and returns the passing rows it met, at their exact
    distances."""
    over = harness.deep_merge(TINY, {"config": {"serve": {
        "max_budget": 64}}})
    out = harness.run(CELL, 6, 1.0, False, allow_cpu=True, overrides=over)
    assert not out["correct"]
    check = out["checks"]["recall_loss"]
    assert check["value"] > check["limit"]


def closed_loop_log(err: str) -> dict:
    """The numbers of a run's `closed loop:` log line."""
    m = re.search(r"closed loop: pool of (\d+) distinct requests, (\d+) "
                  r"submissions, (\d+) replays; recall set: the first "
                  r"(\d+), (\d+) of them submitted after the close, (\d+) "
                  r"never submitted", err)
    keys = ("pool", "submissions", "replays", "recall_requests",
            "recall_after_close", "never_submitted")
    return dict(zip(keys, map(int, m.groups())))


def test_closed_loop_replays_its_pool(capsys):
    """outstanding 8 and pool_qps 0.5 over 1 s: a pool of P = 9 distinct
    requests, which the loop replays until the window closes."""
    over = harness.deep_merge(TINY, {"traffic": {
        "pool_qps": 0.5, "recall_requests": 8}})
    out = harness.run(CELL, 2**31 + 13, 1.0, False, allow_cpu=True,
                      overrides=over)
    assert out["correct"], out["checks"]
    win = closed_loop_log(capsys.readouterr().err)
    assert win["pool"] == 9 and out["attempted"] > 9
    assert win["submissions"] == out["attempted"]
    assert win["replays"] == out["attempted"] - 9
    assert win["recall_requests"] == 8 and out["failed"] == 0


def test_recall_set_is_served_after_a_slow_close(monkeypatch, capsys):
    """Every pump takes longer than the window: the close comes with only
    the first `outstanding` of the 16-request recall set submitted. The
    drain submits the rest of the set and nothing more; none of it counts
    toward `qps`, all of it toward recall and `correct`."""
    from repro.serve import scheduler

    pump = scheduler.CostAwareScheduler.pump

    def slow_pump(self, now):
        time.sleep(1.05)
        return pump(self, now)

    monkeypatch.setattr(scheduler.CostAwareScheduler, "pump", slow_pump)
    over = harness.deep_merge(TINY, {"traffic": {"warmup": [[8, 8]]}})
    out = harness.run(CELL, 2**31 + 14, 1.0, False, allow_cpu=True,
                      overrides=over)
    assert out["correct"], out["checks"]
    win = closed_loop_log(capsys.readouterr().err)
    assert win["recall_requests"] == 16 and win["recall_after_close"] == 8
    assert out["attempted"] == 16 and out["failed"] == 0
    assert win["replays"] == 0 and win["never_submitted"] == 0
    assert out["metrics"]["qps"]["value"] == 0.0
    assert 0.0 < out["metrics"]["recall_at_10"]["value"] <= 1.0


@pytest.mark.parametrize("over,match", [
    ({"traffic": {"recall_requests": 12}}, "recall_requests"),
    ({"traffic": {"recall_requests": 32}}, "recall_requests"),
    ({"traffic": {"recall_requests": 0}}, "recall_requests"),
    ({"config": {"serve": {"cache_capacity": 64}}}, "cache"),
], ids=["not_whole_blocks", "above_pool", "empty", "query_cache"])
def test_mix_is_refused_before_set_up(over, match):
    """TINY's pool over 1 s is 8 + 16 = 24 requests in blocks of 8; a
    replayed request would hit a result cache keyed on the query."""
    with pytest.raises(ValueError, match=match):
        harness.run(CELL, 2**31 + 15, 1.0, False, allow_cpu=True,
                    overrides=harness.deep_merge(TINY, over))


def test_command_refuses_the_cpu(tmp_path):
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run(
        [sys.executable, str(manifest.ROOT / "bench" / "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout or "x")
