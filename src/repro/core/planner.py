"""Adaptive per-query planning across filter-execution strategies.

The paper's adaptive-termination estimator answers "how long should this
traversal run?". The planner generalizes the question to "which execution
strategy should this query use at all?" — per lane, between three plans:

  scan      pre-filter: bitmap + masked exact (or ADC) distance over the
            σ_q·N passing rows (core/plans.py). Cost is closed-form
            (σ_q·N·c_dist), recall is 1.0 by construction.
  traverse  the standard E2E pipeline: probe → GBDT Ŵ_q → resume.
  widen     filtered-expansion traversal (cfg.mode="widen"): the same
            pipeline but resuming with the ACORN-style widened frontier,
            for lanes whose valid sub-graph disconnects under 1-hop.

Routing happens in two stages so that clearly-scannable lanes never pay
the probe (which would otherwise dominate their cost — the probe is "zero
overhead" only for lanes that end up traversing):

  stage 0 (pre-probe)   the filter bitmap is compiled anyway (the scan
            plan needs it and it costs 0 NDC), which makes σ_q *exact*
            before any distance work. A static GBDT head — trained on
            bitmap/program features only — predicts the traversal cost;
            lanes with σ_q·N·c ≤ Ŵ_static (or σ_q·N under the scan floor)
            route straight to scan.
  stage 1 (post-probe)  surviving lanes run the shared probe prefix once;
            per-plan GBDT heads predict Ŵ_traverse and Ŵ_widen from the
            same trajectory features, and each lane takes
            argmin{probe_cnt + σ_q·N·c, Ŵ_traverse, Ŵ_widen}. A lane the
            static head mis-kept falls back to scan here ("late scan"),
            carrying its probe counters into the scan state.

Both heads share one probe: plan choice costs zero extra NDC beyond what
the chosen plan would have spent anyway (scan-routed lanes spend the
probe prefix only when stage 0 mispredicts, which stage 1 bounds).

`force_plan` pins every lane to one plan through the identical machinery —
tests/test_planner.py asserts bitwise equality (counters included) against
`run_plan`, which composes the corresponding single-plan pipeline directly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.e2e import predict_budgets, probe_and_features
from repro.core.engine import BIG_BUDGET, SearchEngine
from repro.core.estimator import CostEstimator
from repro.core.plans import ScanStats, scan_search, scan_stats
from repro.core.search import SearchConfig, SearchState
from repro.core.state import concat_lanes, take_lanes
from repro.data.synthetic import AttributedDataset, QueryWorkload
from repro.index.bruteforce import filtered_knn_exact

PLANS = ("scan", "traverse", "widen")
PLAN_SCAN, PLAN_TRAVERSE, PLAN_WIDEN = range(3)

STATIC_FEATURE_NAMES = [
    "sigma", "log_sigma_n",
    "clause_frac_0", "clause_frac_1", "clause_frac_2", "clause_frac_3",
    "n_slots", "n_terms",
]


def static_features(stats: ScanStats, prog) -> np.ndarray:
    """Pre-probe features [B, 8]: exact bitmap selectivity + program shape.

    Everything here is available before any distance computation — the
    stage-0 head may only see what costs 0 NDC. All-finite by construction
    (match-nothing lanes give sigma=0, log1p(0)=0)."""
    sig = stats.sigma.astype(np.float32)
    return np.stack([
        sig,
        np.log1p(sig * stats.n).astype(np.float32),
        *[stats.clause_frac[:, i] for i in range(stats.clause_frac.shape[1])],
        np.asarray(prog.active).sum(axis=1).astype(np.float32),
        np.asarray(prog.term_active).sum(axis=1).astype(np.float32),
    ], axis=1)


@dataclasses.dataclass
class Planner:
    """Per-plan cost heads + the scan plan's closed-form cost model."""

    traverse: CostEstimator          # probe features → W_traverse
    widen: CostEstimator             # probe features → W_widen
    static: CostEstimator            # static_features → W_traverse (stage 0)
    scan_dist_cost: float = 1.0      # c: scan-NDC ≡ traversal-NDC exchange rate
    scan_floor: int = 128            # σ·N at/below which scan always wins
                                     # (≈ 2× probe budget: cheaper than probing)


@dataclasses.dataclass
class PlanTrainingData:
    """Dual-exhaustion labels from one shared probe per query."""

    features: np.ndarray         # [n, F] probe trajectory features
    static_feats: np.ndarray     # [n, 8]
    w_traverse: np.ndarray       # [n] exhaustion/convergence NDC, post mode
    w_widen: np.ndarray          # [n] same, widen-mode resume
    converged_t: np.ndarray      # [n] bool
    converged_w: np.ndarray      # [n] bool
    sigma: np.ndarray            # [n] exact bitmap selectivity
    gt_idx: np.ndarray           # [n, k]
    gt_dist: np.ndarray          # [n, k]


def generate_plan_training_data(
    engine: SearchEngine,
    ds: AttributedDataset,
    workload: QueryWorkload,
    cfg: SearchConfig,
    probe_budget: int = 64,
    chunk: int = 64,
    n_probes: int = 2,
) -> PlanTrainingData:
    """Per query: one probe, two exhaustion resumes (post + widen).

    Both resumes continue the *same* probe carry, so each plan's label is
    the total NDC of "probe prefix + that plan's continuation" — exactly
    the quantity the router compares at serve time. Compressed engines
    judge convergence in the compressed metric (see core.training)."""
    compressed = engine.effective_precision(cfg) != "float32"
    cfg_w = dataclasses.replace(cfg, mode="widen")
    n = workload.batch
    out = {f.name: [] for f in dataclasses.fields(PlanTrainingData)}
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        q = workload.queries[s:e]
        filt = workload.filter_slice(s, e)
        # ground truth from the dataset, not the engine's device arrays —
        # host-tiered / index-sharded engines hold placeholders or slices
        gt_idx, gt_dist = filtered_knn_exact(
            q, np.asarray(ds.vectors), filt,
            np.asarray(ds.labels_packed), np.asarray(ds.value_matrix),
            cfg.k)
        if compressed:
            from repro.index.bruteforce import valid_mask
            from repro.quant import compressed_filtered_topk

            ok = valid_mask(filt, np.asarray(ds.labels_packed),
                            np.asarray(ds.value_matrix))
            conv_dist, _ = compressed_filtered_topk(
                engine.effective_precision(cfg),
                getattr(engine, "quant_concat", None) or engine.quant,
                q, ok, cfg.k)
        else:
            conv_dist = gt_dist
        prog = engine.compile(filt)
        stats = scan_stats(engine, prog)
        st, z = probe_and_features(engine, cfg, q, prog, probe_budget,
                                   n_probes, gt_dist=conv_dist)
        labels = {}
        for key, c, carry in (("t", cfg, st), ("w", cfg_w, st)):
            # search donates the resume carry — hand each plan its own copy
            # so the shared probe state survives the first resume
            fin = engine.search(c, q, prog, BIG_BUDGET,
                                state=jax.tree.map(jnp.copy, carry),
                                gt_dist=conv_dist)
            cc = np.asarray(fin.conv_cnt)
            conv = cc > 0
            labels[key] = (np.where(conv, cc, np.asarray(fin.cnt))
                           .astype(np.int64), conv)
        out["features"].append(np.asarray(z))
        out["static_feats"].append(static_features(stats, prog))
        out["w_traverse"].append(labels["t"][0])
        out["converged_t"].append(labels["t"][1])
        out["w_widen"].append(labels["w"][0])
        out["converged_w"].append(labels["w"][1])
        out["sigma"].append(stats.sigma)
        out["gt_idx"].append(gt_idx)
        out["gt_dist"].append(gt_dist)
    return PlanTrainingData(**{k: np.concatenate(v) for k, v in out.items()})


def fit_planner(data: PlanTrainingData, probe_budget: int = 64,
                scan_dist_cost: float = 1.0, **gbdt_kwargs) -> Planner:
    """Fit the three cost heads. The static head regresses the *traverse*
    label from pre-probe features only — it exists to catch lanes where
    even a pessimistic traversal estimate exceeds the exact scan cost."""
    tr = CostEstimator.fit(data.features, data.w_traverse, **gbdt_kwargs)
    wd = CostEstimator.fit(data.features, data.w_widen, **gbdt_kwargs)
    st = CostEstimator.fit(data.static_feats, data.w_traverse, **gbdt_kwargs)
    return Planner(traverse=tr, widen=wd, static=st,
                   scan_dist_cost=scan_dist_cost,
                   scan_floor=2 * probe_budget)


# ---- routing ---------------------------------------------------------------

def stage0_scan_mask(planner: Planner, stats: ScanStats, prog, alpha: float,
                     min_budget: int, max_budget: int,
                     packed=None) -> np.ndarray:
    """[B] bool — lanes routed to scan before (instead of) the probe."""
    sf = static_features(stats, prog)
    w_static, _ = predict_budgets(planner.static, jnp.asarray(sf), alpha,
                                  min_budget, max_budget, packed=packed)
    scan_cost = stats.counts.astype(np.float64) * planner.scan_dist_cost
    return ((scan_cost <= np.asarray(w_static)) |
            (stats.counts <= planner.scan_floor))


def choose_plans(planner: Planner, feats, probe_cnt: np.ndarray,
                 counts: np.ndarray, alpha: float, min_budget: int,
                 max_budget: int, packed_t=None, packed_w=None):
    """Post-probe per-lane argmin over predicted total NDC.

    Returns (plan_ids [B] int, w_traverse [B], w_widen [B]). Ties break
    toward the earlier plan in PLANS order — scan first, because its
    recall is exact at equal predicted cost."""
    w_t, _ = predict_budgets(planner.traverse, feats, alpha, min_budget,
                             max_budget, packed=packed_t)
    w_w, _ = predict_budgets(planner.widen, feats, alpha, min_budget,
                             max_budget, packed=packed_w)
    w_t = np.asarray(w_t).astype(np.int64)
    w_w = np.asarray(w_w).astype(np.int64)
    scan_total = probe_cnt.astype(np.int64) + np.ceil(
        counts * planner.scan_dist_cost).astype(np.int64)
    table = np.stack([scan_total, w_t, w_w], axis=1)
    return np.argmin(table, axis=1).astype(np.int32), w_t, w_w


@dataclasses.dataclass
class PlanResult:
    state: SearchState
    plan: np.ndarray              # [B] i32 — index into PLANS
    sigma: np.ndarray             # [B] exact bitmap selectivity
    pre_probe: np.ndarray         # [B] bool — routed at stage 0 (no probe)
    predicted_budget: np.ndarray  # [B] — chosen plan's predicted/closed-form
                                  # total NDC (σ·N·c for scan lanes)
    reports: list | None = None   # explain=True: [B] obs.QueryReport

    def plan_names(self) -> list[str]:
        return [PLANS[p] for p in self.plan]


def planned_search(
    engine: SearchEngine,
    planner: Planner,
    cfg: SearchConfig,
    queries: np.ndarray,
    filt,
    probe_budget: int = 64,
    n_probes: int = 2,
    alpha: float = 1.0,
    min_budget: int = 32,
    max_budget: int = BIG_BUDGET,
    force_plan: str | None = None,
    stats: ScanStats | None = None,
    tracer=None,
    trace_id: str = "",
    explain: bool = False,
) -> PlanResult:
    """Route each lane to its cheapest plan and execute. Terminal state
    (rerank applied on compressed engines) in the original lane order.

    `force_plan` pins all lanes to one plan — bitwise-equal (counters
    included) to `run_plan` with the same arguments.

    `tracer` spans the router stages (stage0 routing, shared probe via
    `probe_and_features`, plan-select, per-plan execution, rerank) at host
    dispatch boundaries only; `explain=True` builds one `obs.QueryReport`
    per lane in `PlanResult.reports` with the route each lane took."""
    from repro.core.search import dispatch_counters, get_backend
    from repro.obs.trace import as_tracer

    tr = as_tracer(tracer)
    if tracer is not None and not trace_id:
        trace_id = tr.new_trace("plan")
    prog = engine.compile(filt)
    if stats is None:
        stats = scan_stats(engine, prog, tracer=tracer, trace_id=trace_id)
    queries = np.asarray(queries, np.float32)
    b = queries.shape[0]
    counts = stats.counts
    d0 = dispatch_counters()
    n_exec_calls = 0

    plan = np.full(b, -1, np.int32)
    pre_probe = np.zeros(b, bool)
    pred = np.zeros(b, np.int64)

    if force_plan is not None:
        if force_plan not in PLANS:
            raise ValueError(f"force_plan must be one of {PLANS}, "
                             f"got {force_plan!r}")
        plan[:] = PLANS.index(force_plan)

    # ---- stage 0: pre-probe routing (exact σ + static cost head) ----
    if force_plan is None:
        with tr.span("plan-stage0", trace_id, lanes=b):
            s0 = stage0_scan_mask(planner, stats, prog, alpha, min_budget,
                                  max_budget)
        plan[s0] = PLAN_SCAN
        pre_probe[:] = s0
    elif force_plan == "scan":
        pre_probe[:] = True
    scan_now = pre_probe.nonzero()[0]

    parts: list[tuple[np.ndarray, SearchState]] = []
    if scan_now.size:
        with tr.span("scan", trace_id, lanes=int(scan_now.size), late=False):
            sub = _scan_part(engine, cfg, queries, prog, stats, scan_now)
        n_exec_calls += 1
        pred[scan_now] = np.ceil(
            counts[scan_now] * planner.scan_dist_cost).astype(np.int64)
        parts.append((scan_now, sub))

    # ---- stage 1: shared probe + per-plan heads on the survivors ----
    rest = (~pre_probe).nonzero()[0]
    probe_ndc = np.zeros(b, np.int64)
    if rest.size:
        q_r = queries[rest]
        prog_r = prog.slice(rest)
        carry, feats = probe_and_features(engine, cfg, q_r, prog_r,
                                          probe_budget, n_probes,
                                          tracer=tracer, trace_id=trace_id)
        probe_cnt = np.asarray(carry.cnt)
        probe_ndc[rest] = probe_cnt
        with tr.span("plan-select", trace_id, lanes=int(rest.size),
                     forced=force_plan or ""):
            if force_plan is None:
                ids, w_t, w_w = choose_plans(planner, feats, probe_cnt,
                                             counts[rest], alpha, min_budget,
                                             max_budget)
            else:
                ids = np.full(rest.size, PLANS.index(force_plan), np.int32)
                head = (planner.traverse if force_plan == "traverse"
                        else planner.widen)
                w, _ = predict_budgets(head, feats, alpha, min_budget,
                                       max_budget)
                w_t = w_w = np.asarray(w).astype(np.int64)
        plan[rest] = ids

        late = rest[ids == PLAN_SCAN]
        if late.size:
            sel = (ids == PLAN_SCAN).nonzero()[0]
            with tr.span("scan", trace_id, lanes=int(late.size), late=True):
                sub = _scan_part(engine, cfg, queries, prog, stats, late,
                                 base_state=take_lanes(carry, sel))
            n_exec_calls += 1
            pred[late] = (probe_cnt[sel] + np.ceil(
                counts[late] * planner.scan_dist_cost)).astype(np.int64)
            parts.append((late, sub))
        for pid, mode, w in ((PLAN_TRAVERSE, cfg.mode, w_t),
                             (PLAN_WIDEN, "widen", w_w)):
            lanes = rest[ids == pid]
            if not lanes.size:
                continue
            sel = (ids == pid).nonzero()[0]
            c = cfg if mode == cfg.mode else dataclasses.replace(cfg, mode=mode)
            with tr.span("resume", trace_id, plan=PLANS[pid],
                         lanes=int(lanes.size)):
                sub = engine.search(c, q_r[sel], prog_r.slice(sel), w[sel],
                                    state=take_lanes(carry, sel),
                                    tracer=tracer, trace_id=trace_id)
            n_exec_calls += 1
            pred[lanes] = w[sel]
            parts.append((lanes, sub))

    # ---- merge back into the original lane order ----
    perm = np.concatenate([idx for idx, _ in parts])
    inv = np.argsort(perm, kind="stable")
    state = take_lanes(concat_lanes([st for _, st in parts]), inv)
    with tr.span("rerank", trace_id,
                 precision=engine.effective_precision(cfg)):
        state = engine.rerank(cfg, queries, state)

    reports = None
    if explain:
        reports = _plan_reports(engine, cfg, state, plan, pred, pre_probe,
                                probe_ndc, trace_id, d0, n_exec_calls,
                                n_probes, probe_budget, get_backend,
                                dispatch_counters)
    return PlanResult(state=state, plan=plan, sigma=stats.sigma,
                      pre_probe=pre_probe, predicted_budget=pred,
                      reports=reports)


def _plan_reports(engine, cfg, state, plan, pred, pre_probe, probe_ndc,
                  trace_id, d0, n_exec_calls, n_probes, probe_budget,
                  get_backend, dispatch_counters):
    """Per-lane EXPLAIN reports for `planned_search` (host post-processing;
    reads the final counters back once — explain mode's documented cost)."""
    from repro.obs.explain import StageReport, build_reports

    backend_name = cfg.backend or engine.backend or "dense"
    if getattr(get_backend(backend_name), "persistent", False):
        total_l = dispatch_counters()["launches"] - d0["launches"]
    else:
        probe_calls = 0 if not (~pre_probe).any() else (
            1 if n_probes <= 1 else 2)
        total_l = probe_calls + n_exec_calls
    final_cnt = np.asarray(state.cnt)
    b = final_cnt.shape[0]
    names = [PLANS[p] for p in plan]
    stages = []
    for i in range(b):
        st = [StageReport("plan-stage0",
                          attrs=dict(pre_probe=bool(pre_probe[i])))]
        if not pre_probe[i]:
            st.append(StageReport("probe", ndc=int(probe_ndc[i]),
                                  attrs=dict(budget=int(probe_budget),
                                             n_probes=int(n_probes))))
            st.append(StageReport("plan-select",
                                  attrs=dict(plan=names[i])))
        exec_name = "scan" if plan[i] == PLAN_SCAN else "resume"
        st.append(StageReport(exec_name,
                              ndc=int(final_cnt[i] - probe_ndc[i]),
                              launches=total_l,
                              attrs=dict(plan=names[i])))
        st.append(StageReport("rerank", attrs=dict(
            precision=engine.effective_precision(cfg))))
        stages.append(st)
    reports = build_reports(
        cfg, state, pred, backend=backend_name, plans=names,
        probe_ndc=probe_ndc, trace_ids=[f"{trace_id or 'plan'}:{i}"
                                        for i in range(b)], stages=stages)
    if getattr(state, "shard", None) is not None:
        from repro.obs.shard import attach_shard_sections

        attach_shard_sections(reports, cfg, state, pred)
    # scan lanes terminate by construction (the masked scan is exhaustive
    # over the σ·N valid rows), not by any traversal stop condition —
    # globally and on every shard's slice of the bitmap
    for i, r in enumerate(reports):
        if plan[i] == PLAN_SCAN:
            r.termination = "scan-exhaustive"
            for sec in r.shards:
                sec.termination = "scan-exhaustive"
    return reports


def _scan_part(engine, cfg, queries, prog, stats, lanes, base_state=None):
    return scan_search(
        engine, cfg, queries[lanes], prog.slice(lanes),
        stats=stats.rows(lanes),
        base_state=base_state)


def run_plan(
    engine: SearchEngine,
    planner: Planner,
    plan: str,
    cfg: SearchConfig,
    queries: np.ndarray,
    filt,
    probe_budget: int = 64,
    n_probes: int = 2,
    alpha: float = 1.0,
    min_budget: int = 32,
    max_budget: int = BIG_BUDGET,
) -> SearchState:
    """Execute one plan directly, bypassing the router — the structural
    reference `planned_search(force_plan=...)` is tested against."""
    prog = engine.compile(filt)
    queries = np.asarray(queries, np.float32)
    if plan == "scan":
        state = scan_search(engine, cfg, queries, prog)
    elif plan in ("traverse", "widen"):
        carry, feats = probe_and_features(engine, cfg, queries, prog,
                                          probe_budget, n_probes)
        head = planner.traverse if plan == "traverse" else planner.widen
        w, _ = predict_budgets(head, feats, alpha, min_budget, max_budget)
        c = cfg if plan == "traverse" else dataclasses.replace(cfg,
                                                               mode="widen")
        state = engine.search(c, queries, prog, w, state=carry)
    else:
        raise ValueError(f"unknown plan {plan!r} (one of {PLANS})")
    return engine.rerank(cfg, queries, state)
