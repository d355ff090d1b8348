"""Reduction of a profiler trace to the benchmark's device numbers.

`load(path)` reads an `.xplane.pb` with nothing but JAX and keeps two
lists of (name, start_ns, duration_ns): the device's operations (the
"XLA Ops" line of every TPU plane; a name is the op's name joined with
its string stats, the HLO instruction first, as in
"%persistent_multi_step.1 = (f32[16,128]...) custom-call(...)") and the
harness's own host spans (names starting with "bench."). `reduce` turns
them into busy and idle time, time per operation and per kernel, host
time outside device work, and the longest idle gaps, labelled by the
host span the harness was in.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

DEVICE_LINE = "XLA Ops"
HOST_PREFIX = "bench."


def load(trace_dir: str) -> dict:
    import jax

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != DEVICE_LINE:
                    continue
                for e in line.events:
                    stats = " ".join(str(v) for _, v in e.stats
                                     if isinstance(v, str))
                    ops.append((f"{e.name} {stats}".strip(),
                                int(e.start_ns), int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    return {"device": device, "host": host}


def _clip(iv, t0, t1):
    out = []
    for s, e in iv:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((s, e))
    return out


def union(iv):
    """Sorted disjoint intervals covering the given ones."""
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(iv) -> int:
    return sum(e - s for s, e in iv)


def intersect(a, b) -> int:
    """Length of the overlap of two sorted disjoint interval lists."""
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float               # device busy, averaged over the chips
    op_s: dict                  # device seconds per op name (all chips)
    host_outside_device_s: float  # pump/submit time with the device idle
    idle_gaps: list             # [(label, seconds)], longest first

    def kernel_s(self, names) -> float:
        """Device seconds of the ops named after any of `names` (the op's
        own name, not an op that takes its result)."""
        return sum(v for k, v in self.op_s.items()
                   if k.lstrip("%").startswith(tuple(names)))


def reduce(events: dict, seconds: float | None = None,
           window_span: str = "bench.window",
           work_spans=("bench.pump", "bench.submit"),
           n_gaps: int = 10) -> Summary:
    """Numbers of the window (the `window_span` host span, cut to its
    first `seconds` where given)."""
    host = events["host"]
    win = [(s, s + d) for n, s, d in host if n == window_span]
    if not win:
        raise ValueError(f"no {window_span!r} span in the trace")
    t0, t1 = win[0]
    if seconds is not None:
        t1 = min(t1, t0 + int(seconds * 1e9))
    op_s, busy_each, merged_all = {}, [], []
    for ops in events["device"].values():
        iv = []
        for name, s, d in ops:
            c = _clip([(s, s + d)], t0, t1)
            if c:
                iv += c
                op_s[name] = op_s.get(name, 0.0) + length(c) / 1e9
        u = union(iv)
        busy_each.append(length(u))
        merged_all += u
    dev = union(merged_all)
    work = union(_clip([(s, s + d) for n, s, d in host if n in work_spans],
                       t0, t1))
    outside = (length(work) - intersect(work, dev)) / 1e9
    spans = sorted((s, s + d, n) for n, s, d in host if n != window_span)
    starts = [a for a, _, _ in spans]
    gaps = []
    prev = t0
    for s, e in dev + [(t1, t1)]:
        if s > prev:
            mid = (prev + s) // 2
            i = bisect.bisect_right(starts, mid) - 1
            label = (spans[i][2] if i >= 0 and spans[i][1] > mid
                     else "bench.idle")
            gaps.append((label, (s - prev) / 1e9))
        prev = max(prev, e)
    gaps.sort(key=lambda g: -g[1])
    n_dev = max(len(busy_each), 1)
    return Summary(window_s=(t1 - t0) / 1e9,
                   busy_s=sum(busy_each) / n_dev / 1e9,
                   op_s=op_s,
                   host_outside_device_s=outside,
                   idle_gaps=gaps[:n_gaps])


def breakdown(summary: Summary, n: int = 10) -> dict:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k[:120], v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps[:n]]}
