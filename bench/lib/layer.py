"""Reductions the per-layer readers share (`bench/metrics/<name>.py`).

Each takes the run's context: `requests` (plan, ndc, probe_ndc of every
completed request), `n_completed`, `traced_requests` (those completed
inside the traced window, whose work the trace holds), `trace` (a
`trace.Summary`, None without a trace), `bodies` (the persistent driver's launch counts per
body over the window), `engine` (dim, label words, value channels,
degree, precision) and `peaks` (the chip's, None off the chip). Each
returns None where the run holds nothing to read.
"""
from __future__ import annotations

from bench.lib import counts

# Device op names of the kernels, as a v5e trace names them: the HLO
# custom call takes the name of the jitted function around the Pallas
# call ("%persistent_multi_step.1 = ... custom-call(...)", chip run).
TRAVERSE_KERNEL = ("persistent_multi_step",)


def host_ms_per_query(ctx):
    tr = ctx["trace"]
    if tr is None or not ctx["traced_requests"]:
        return None
    return 1e3 * tr.host_outside_device_s / len(ctx["traced_requests"])


def idle_share(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def ndc_per_query(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    return sum(r["ndc"] for r in reqs) / len(reqs)


def launches_per_query(ctx):
    if not ctx["n_completed"]:
        return None
    return sum(ctx["bodies"].values()) / ctx["n_completed"]


def scan_share(ctx):
    reqs = ctx["requests"]
    if not reqs:
        return None
    return 100.0 * sum(r["plan"] == "scan" for r in reqs) / len(reqs)


def traverse_roofline(ctx):
    """The persistent kernel's device time against the bytes of the
    distance computations it made: every NDC of a traverse-plan request,
    and the probe's NDC of the others (widen resumes and scans run other
    bodies). Expansions are counted as NDC / R, a lower bound."""
    tr, pk = ctx["trace"], ctx["peaks"]
    if tr is None or pk is None:
        return None
    sec = tr.kernel_s(TRAVERSE_KERNEL)
    ndc = sum(r["ndc"] if r["plan"] == "traverse" else r["probe_ndc"]
              for r in ctx["traced_requests"])
    if sec <= 0 or ndc <= 0:
        return None
    e = ctx["engine"]
    nbytes = counts.traverse_bytes(ndc, ndc // e["degree"], e["dim"],
                                   e["precision"], e["label_words"],
                                   e["value_attrs"], e["degree"])
    peak_ops = (pk["int8_ops"] if e["precision"] == "int8"
                else pk["bf16_flops"])
    share, _ = counts.roofline_share(counts.traverse_ops(ndc, e["dim"]),
                                     nbytes, sec, peak_ops,
                                     pk["hbm_bytes_per_s"])
    return share
