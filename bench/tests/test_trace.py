"""The trace reduction gives known numbers on a small trace kept beside
this test (`data/trace_small.json`, in the form `trace.load` returns,
with op names written as a v5e trace writes them): one TPU plane with
five ops around a 10 us window, and the harness's window, pump and
submit spans."""
import json
from pathlib import Path

import pytest

from bench.lib import trace

EVENTS = json.loads((Path(__file__).parent / "data" /
                     "trace_small.json").read_text())
NS = 1e-9


def test_window_busy_and_idle():
    s = trace.reduce(EVENTS)
    assert s.window_s == pytest.approx(10000 * NS)
    # union of the clipped ops: 500 + 4000 + 1000 + 500 ns
    assert s.busy_s == pytest.approx(6000 * NS)


def test_kernel_and_op_seconds():
    s = trace.reduce(EVENTS)
    # the fusion that reads the kernel's result is not kernel time
    assert s.kernel_s(["persistent_multi_step"]) == pytest.approx(3000 * NS)
    assert s.kernel_s(["sqdist_masked"]) == pytest.approx(1000 * NS)
    fusions = {k.split(" ")[0]: v for k, v in s.op_s.items()}
    assert fusions["%fusion.1"] == pytest.approx(500 * NS)   # clipped
    assert fusions["%fusion.3"] == pytest.approx(500 * NS)


def test_host_time_outside_device_work():
    s = trace.reduce(EVENTS)
    # pump/submit cover 6000 ns, 3500 of them with an op running
    assert s.host_outside_device_s == pytest.approx(2500 * NS)


def test_idle_gaps_labelled_by_host_span():
    s = trace.reduce(EVENTS)
    assert [g[0] for g in s.idle_gaps] == ["bench.submit", "bench.idle",
                                          "bench.pump"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [2000 * NS, 1500 * NS, 500 * NS])


def test_window_cut_to_seconds():
    s = trace.reduce(EVENTS, seconds=5000 * NS)
    assert s.window_s == pytest.approx(5000 * NS)
    assert s.busy_s == pytest.approx(4500 * NS)


def test_breakdown_lists_longest_first():
    b = trace.breakdown(trace.reduce(EVENTS))
    assert b["device_ops"][0][0].startswith("%persistent_multi_step.1")
    assert len(b["idle_gaps"]) == 3
