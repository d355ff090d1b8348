"""The program's own spans on the device trace's clock.

A window served with a `repro.obs.Tracer` under a JAX profiler trace
leaves its spans in two places: in the xplane, as host events named
"repro.<span>" with a `trace` stat (the span's trace id), and in the
tracer's ring, with times on the tracer's clock. The xplane's times
count from the trace's start, so `fit_offset` pairs the spans present in
both (same name and trace id, in order) and takes the median difference;
`on_device_clock` then places ring-only events (compiles, queue waits)
on the device timeline.

`reduce` turns one window into the numbers the benchmark reads from the
program: host time of the planner's stage 0 outside device work, queue
waits per request, compiles by the span they fell in, the share of
device-idle time inside the harness's pumps that program spans cover,
and the longest idle gaps, each labelled by the innermost program span
covering its midpoint (the harness's own span where none does). A ring
is a list of dicts in `Span.to_json`'s form (`name`, `trace`, `t0`, `t1`
in seconds, then the span's attributes).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import statistics

from bench.lib import trace

PREFIX = "repro."
# ring-only spans that are host work, and so may name an idle gap
HOST_WORK = ("compile",)


def load(trace_dir: str) -> list:
    """(name, start_ns, duration_ns, trace id) of every "repro.*" host
    event of the trace under `trace_dir`, in start order."""
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    tid = next((str(v) for k, v in e.stats if k == "trace"),
                               "")
                    out.append((e.name, int(e.start_ns),
                                int(e.duration_ns), tid))
    return sorted(out, key=lambda ev: ev[1])


def fit_offset(program: list, ring: list) -> float:
    """Nanoseconds to add to a ring time (in ns) to place it on the
    xplane's clock: the median over spans found in both."""
    xs = collections.defaultdict(list)
    for name, start, _, tid in program:
        xs[(name[len(PREFIX):], tid)].append(start)
    rs = collections.defaultdict(list)
    for sp in ring:
        if (sp["name"], sp["trace"]) in xs:
            rs[(sp["name"], sp["trace"])].append(sp["t0"])
    diffs = [x - 1e9 * r for key, r_t0 in rs.items()
             for x, r in zip(sorted(xs[key]), sorted(r_t0))]
    if not diffs:
        raise ValueError("no span is both in the ring and in the trace")
    return statistics.median(diffs)


def on_device_clock(ring: list, offset_ns: float, names) -> list:
    """The ring's spans named in `names` as (name, start_ns, duration_ns,
    trace id) on the xplane's clock."""
    return [(PREFIX + sp["name"], int(round(1e9 * sp["t0"] + offset_ns)),
             int(round(1e9 * (sp["t1"] - sp["t0"]))), sp["trace"])
            for sp in ring if sp["name"] in names]


@dataclasses.dataclass
class Summary:
    offset_ns: float            # ring time (ns) + offset = xplane time
    stage0_host_s: float        # plan-stage0 time with the device idle
    queue_wait_s: dict          # trace id -> seconds waited in queues
    compiles_by_span: dict      # innermost open span -> programs compiled
                                # in the window
    pump_idle_covered: float    # share of idle time inside the harness's
                                # pumps that program spans cover
    idle_gaps: list             # [(label, seconds)], longest first


def _window(events: dict, seconds, window_span: str):
    win = [(s, s + d) for n, s, d in events["host"] if n == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    t0, t1 = win[0]
    if seconds is not None:
        t1 = min(t1, t0 + int(seconds * 1e9))
    return t0, t1


def _meet(a: list, b: list) -> list:
    """The intervals where two sorted disjoint interval lists overlap."""
    i = j = 0
    out = []
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _innermost(spans: list, starts: list, at) -> str | None:
    """Name of the latest-starting span of `spans` (sorted by start)
    that covers `at`: the innermost one where spans nest."""
    i = bisect.bisect_right(starts, at) - 1
    while i >= 0:
        if spans[i][1] > at:
            return spans[i][2]
        i -= 1
    return None


def reduce(events: dict, program: list, ring: list,
           seconds: float | None = None, window_span: str = "bench.window",
           pump_span: str = "bench.pump", n_gaps: int = 10) -> Summary:
    """Program numbers of the window (`trace.reduce`'s window, cut to its
    first `seconds` where given). `events` is what `trace.load` returns,
    `program` what `load` returns, `ring` the tracer's spans."""
    t0, t1 = _window(events, seconds, window_span)
    offset = fit_offset(program, ring)
    work = program + on_device_clock(ring, offset, HOST_WORK)
    dev = trace.union(trace._clip(
        [(s, s + d) for ops in events["device"].values() for _, s, d in ops],
        t0, t1))

    stage0 = trace.union(trace._clip(
        [(s, s + d) for n, s, d, _ in program
         if n == PREFIX + "plan-stage0"], t0, t1))
    stage0_host = trace.length(stage0) - trace.intersect(stage0, dev)

    idle, prev = [], t0
    for s, e in dev + [(t1, t1)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    pumps = trace.union(trace._clip(
        [(s, s + d) for n, s, d in events["host"] if n == pump_span],
        t0, t1))
    pump_idle = _meet(idle, pumps)
    covered = trace.intersect(pump_idle, trace.union(
        [(s, s + d) for _, s, d, _ in work]))

    prog_spans = sorted((s, s + d, n) for n, s, d, _ in work)
    bench_spans = sorted((s, s + d, n) for n, s, d in events["host"]
                         if n != window_span)
    prog_starts = [x[0] for x in prog_spans]
    bench_starts = [x[0] for x in bench_spans]
    gaps = []
    for a, b in sorted(idle, key=lambda g: g[1] - g[0],
                       reverse=True)[:n_gaps]:
        mid = (a + b) // 2
        label = (_innermost(prog_spans, prog_starts, mid)
                 or _innermost(bench_spans, bench_starts, mid)
                 or "bench.idle")
        gaps.append((label, (b - a) / 1e9))

    waits = collections.defaultdict(float)
    compiles = collections.Counter()
    for sp in ring:
        if sp["name"] == "queued":
            waits[sp["trace"]] += sp["t1"] - sp["t0"]
        elif (sp["name"] == "compile"
              and t0 <= 1e9 * sp["t0"] + offset < t1):
            compiles[sp["inside"] or "-"] += 1
    return Summary(offset_ns=offset, stage0_host_s=stage0_host / 1e9,
                   queue_wait_s=dict(waits),
                   compiles_by_span=dict(compiles.most_common()),
                   pump_idle_covered=(covered / trace.length(pump_idle)
                                      if pump_idle else 0.0),
                   idle_gaps=gaps)


# Readers of the program's numbers, given a run's context (`layer.py`'s,
# with `program`: this module's Summary of the traced window or None,
# `n_compiles`: the window tracer's count, and each traced request's
# `trace_id`). Each returns None where the run holds nothing to read.

def stage0_host_ms_per_query(ctx):
    pr, reqs = ctx.get("program"), ctx["traced_requests"]
    if pr is None or not reqs:
        return None
    return 1e3 * pr.stage0_host_s / len(reqs)


def queue_wait_ms(ctx):
    pr, reqs = ctx.get("program"), ctx["traced_requests"]
    if pr is None or not reqs:
        return None
    return 1e3 * sum(pr.queue_wait_s.get(r["trace_id"], 0.0)
                     for r in reqs) / len(reqs)


def compiles_in_window(ctx):
    return ctx.get("n_compiles")
