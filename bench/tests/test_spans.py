"""The program's spans on the device trace's clock (`bench/lib/spans.py`).

On a recorded fixture (`data/trace_program.json`: `trace_small.json`'s
device ops and harness spans, plus "repro.*" program spans as the xplane
holds them and a tracer ring on a clock 5 s apart, a few ns of jitter a
span) the reduction gives hand-computed numbers and leaves the existing
per-layer readings as they were; and on a real JAX profiler trace taken
here, a `Tracer` span comes back nested in the harness's
span, with its ring twin mapped to within 1 ms of it."""
import json
import time
from pathlib import Path

import pytest

from bench.lib import manifest, peaks, spans, trace

DATA = Path(__file__).parent / "data"
SMALL = json.loads((DATA / "trace_small.json").read_text())
PROGRAM = json.loads((DATA / "trace_program.json").read_text())
NS = 1e-9


def _summary():
    return spans.reduce(PROGRAM, PROGRAM["program"], PROGRAM["ring"])


def _ctx(events):
    reqs = [dict(plan="traverse", ndc=900, probe_ndc=64,
                 trace_id="req-000001"),
            dict(plan="scan", ndc=300, probe_ndc=0, trace_id="req-000004")]
    return dict(requests=reqs + [dict(plan="traverse", ndc=600, probe_ndc=64,
                                      trace_id="req-000007")],
                n_completed=3, traced_requests=reqs, window_s=10000 * NS,
                trace=trace.reduce(events), bodies={"persistent": 5},
                engine=dict(dim=128, label_words=1, value_attrs=1, degree=32,
                            precision="int8"),
                peaks=peaks.peaks_for("TPU v5 lite"),
                program=_summary(), n_compiles=PROGRAM["n_compiles"])


def test_existing_readings_unchanged_by_program_spans():
    for m in manifest.load_manifest()["per_layer"]:
        read = manifest.load_reader(m["name"])
        assert read(_ctx(PROGRAM)) == read(_ctx(SMALL)), m["name"]
        assert read(_ctx(PROGRAM)) is not None, m["name"]


def test_offset_fitted_from_spans_in_both_clocks():
    # ring t0 - 5 s is the xplane start plus 10, -10, 0 and 30 ns
    assert spans.fit_offset(PROGRAM["program"], PROGRAM["ring"]) == \
        pytest.approx(-5e9 - 5, abs=1e-3)


def test_idle_gaps_labelled_by_innermost_program_span():
    s = _summary()
    # [6000, 8000]: a compile mapped from the ring to [6895, 7195] beats
    # the harness's submit; [9000, 10500]: no span, as trace.reduce says;
    # [1500, 2000]: the filter bitmap nested in plan-stage0
    assert [g[0] for g in s.idle_gaps] == ["repro.compile", "bench.idle",
                                          "repro.filter-bitmap"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx(
        [v for _, v in trace.reduce(SMALL).idle_gaps])


def test_program_readings_by_hand():
    s = _summary()
    ctx = _ctx(PROGRAM)
    # plan-stage0 [1200, 1900], device busy over [1200, 1500]: 400 ns of
    # host time over the two requests completed in the window
    assert s.stage0_host_s == pytest.approx(400 * NS)
    assert spans.stage0_host_ms_per_query(ctx) == pytest.approx(200 * NS * 1e3)
    # req-1 waited 1200 + 5700 ns, req-4 1200 ns; req-7 finished later
    assert spans.queue_wait_ms(ctx) == pytest.approx(4050 * NS * 1e3)
    assert spans.compiles_in_window(ctx) == 3
    # the compile at 11495 ns falls after the window's close
    assert s.compiles_by_span == {"lanes": 2, "-": 1}
    # idle inside pumps: [1500, 2000], [7600, 8000], [9000, 9600]; program
    # spans cover 400 + 400 + 500 ns of those 1500
    assert s.pump_idle_covered == pytest.approx(1300 / 1500)


def test_readers_silent_without_program():
    ctx = dict(traced_requests=[], n_completed=0)
    for read in (spans.stage0_host_ms_per_query, spans.queue_wait_ms,
                 spans.compiles_in_window):
        assert read(ctx) is None


def test_bridged_span_on_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer

    tr = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.pump"):
            with tr.span("resume", "bucket-000001"):
                for _ in range(3):
                    with tr.span("lanes", "bucket-000001"):
                        jnp.arange(8).sum().block_until_ready()
                        time.sleep(0.005)
            tr.emit("queued", "req-000001", t0=tr.clock() - 0.01,
                    t1=tr.clock(), queue="ingress")
    jax.profiler.stop_trace()

    prog = spans.load(str(tmp_path))
    assert [(n, t) for n, _, _, t in prog] == (
        [("repro.resume", "bucket-000001")]
        + [("repro.lanes", "bucket-000001")] * 3)      # instants stay out
    (pump,) = [(s, s + d) for n, s, d in trace.load(str(tmp_path))["host"]
               if n == "bench.pump"]
    for _, s, d, _ in prog:
        assert pump[0] <= s and s + d <= pump[1]        # nested in the pump

    ring = [json.loads(sp.to_json()) for sp in tr.spans()]
    mapped = spans.on_device_clock(ring, spans.fit_offset(prog, ring),
                                   ("resume", "lanes"))
    for (n, s, d, _), (m, ms, md, _) in zip(prog, sorted(mapped,
                                                         key=lambda x: x[1])):
        assert n == m
        assert abs(s - ms) < 1e6 and abs((s + d) - (ms + md)) < 1e6
