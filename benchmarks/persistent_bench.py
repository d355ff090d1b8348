"""Persistent-traversal benchmark: launch amortization, dispatch floor,
state-donation savings. Recorded in BENCH_persistent.json at the repo root.

Three sections:

  throughput  end-to-end lockstep search, single-step "pallas" backend vs
              "pallas_persistent", N=100k / B=64 / heterogeneous per-lane
              NDC budgets (lognormal, median ~1200, clipped to [64, 6000] —
              the adaptive-termination regime the paper produces, where
              lanes finish at very different steps). The persistent driver
              groups steps_per_launch steps per dispatch and compacts
              finished lanes away between launches; results are asserted
              bit-identical to the single-step backend before any number is
              reported. Acceptance: ≥ 1.3× end-to-end.
  dispatch    the per-launch overhead separated from per-NDC compute. The
              dispatch floor C0 is measured by resuming a finished state
              (already-met budgets → every lane terminates on its first
              step: the call pays dispatch + state round-trip but ~no
              traversal); per-step compute is (full − C0) / steps. Launches
              per search are counted directly in the persistent driver.
  donation    run_search donates the resumed SearchState (the ~17 carry
              buffers alias in place instead of copying on every
              probe→resume / preemption slice). Measured as a chain of
              no-op resumes through the donating `run_search` vs a
              non-donating jit of the same implementation.

Honest-artifact caveats (XLA:CPU container numbers):

  * On CPU there is no persistent kernel — the driver runs the same jitted
    multi-step launch body and its win comes from (a) host-side compaction
    of terminated lanes (the CPU analogue of the TPU kernel's in-kernel
    early exit: XLA:CPU's lockstep step cost scales with batch width) and
    (b) fewer dispatch/donation round-trips. On TPU the same driver routes
    each launch to the VMEM-resident multi-step Pallas kernel
    (repro.kernels.persistent_step), where the win is launch overhead and
    HBM↔VMEM state traffic amortized over steps_per_launch steps with
    double-buffered neighbor DMA; that path's bit-parity is pinned in
    interpret mode by tests/test_persistent.py, not timed here.
  * This container's machine speed drifts by several × on a scale of
    minutes; every number is best-of-N with one untimed warmup, and the
    headline is a ratio of back-to-back measurements, not an absolute.

    PYTHONPATH=src python -m benchmarks.persistent_bench [--quick]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np

N = 100_000
DIM = 64
DEGREE = 32
BATCH = 64
QUEUE = 512
K = 10
SPL = 8            # steps_per_launch under test
MED_BUDGET = 1200  # lognormal median of the heterogeneous budgets
CLIP = (64, 6000)
REPEATS = 3
NOOP_REPS = 10     # chain length for dispatch-floor / donation timing


def _timed(fn, repeats=REPEATS):
    import jax

    jax.block_until_ready(fn())  # warmup: compile + first run
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _world(n, batch, queue, seed=0):
    import jax.numpy as jnp

    from repro.core import SearchConfig, SearchEngine
    from repro.filters.predicates import FilterSpec, PRED_RANGE

    rng = np.random.default_rng(seed)
    vectors = rng.normal(size=(n, DIM)).astype(np.float32)
    vectors /= np.linalg.norm(vectors, axis=1, keepdims=True)
    neighbors = rng.integers(0, n, size=(n, DEGREE), dtype=np.int64)
    neighbors[neighbors == np.arange(n)[:, None]] = 0
    values = rng.random(n).astype(np.float32)
    queries = vectors[rng.integers(0, n, batch)] + 0.05 * rng.normal(
        size=(batch, DIM)).astype(np.float32)
    spec = FilterSpec(PRED_RANGE, None, np.full(batch, 0.2, np.float32),
                      np.full(batch, 0.8, np.float32))
    engine = SearchEngine(
        base_vectors=jnp.asarray(vectors),
        label_attrs=jnp.zeros((n, 1), jnp.uint32),
        value_attrs=jnp.asarray(values),
        neighbors=jnp.asarray(neighbors.astype(np.int32)),
        entry_point=0,
    )
    cfg = SearchConfig(k=K, queue_size=queue, pred_kind=PRED_RANGE,
                       steps_per_launch=SPL)
    return engine, cfg, queries, spec


def _hetero_budgets(batch, med, clip, seed=7):
    rng = np.random.default_rng(seed)
    b = rng.lognormal(mean=np.log(med), sigma=1.0, size=batch)
    return np.clip(b, *clip).astype(np.int32)


def _count_launches(fn):
    """Run fn() once while counting persistent-driver launches."""
    import repro.core.search as search_mod

    orig = search_mod._persistent_launch
    count = {"n": 0}

    def counting(*a, **k):
        count["n"] += 1
        return orig(*a, **k)

    search_mod._persistent_launch = counting
    try:
        out = fn()
    finally:
        search_mod._persistent_launch = orig
    return out, count["n"]


def run(quick=False):
    import jax
    import jax.numpy as jnp

    import repro.core.search as search_mod

    n = 16_000 if quick else N
    batch = 32 if quick else BATCH
    queue = 256 if quick else QUEUE
    med = 400 if quick else MED_BUDGET
    clip = (32, 1500) if quick else CLIP

    engine, cfg, queries, spec = _world(n, batch, queue)
    budgets = _hetero_budgets(batch, med, clip)
    out = {"config": dict(n=n, dim=DIM, degree=DEGREE, batch=batch,
                          queue=queue, k=K, steps_per_launch=SPL,
                          budget_median=med, budget_clip=list(clip),
                          quick=bool(quick),
                          jax_backend=jax.default_backend())}

    # ---- throughput: single-step vs persistent, identical budgets ----
    c_single = dataclasses.replace(cfg, backend="pallas")
    c_pers = dataclasses.replace(cfg, backend="pallas_persistent")
    st_single = engine.search(c_single, queries, spec, budgets)
    (st_pers, launches) = _count_launches(
        lambda: engine.search(c_pers, queries, spec, budgets))
    for f in st_single._fields:  # parity gate before any timing is reported
        np.testing.assert_array_equal(
            np.asarray(getattr(st_single, f)), np.asarray(getattr(st_pers, f)),
            err_msg=f"persistent/pallas diverged on {f}")
    t_single = _timed(lambda: engine.search(c_single, queries, spec, budgets))
    t_pers = _timed(lambda: engine.search(c_pers, queries, spec, budgets))
    steps = int(np.asarray(st_single.hops).max())
    lane_steps = np.asarray(st_single.hops)
    out["throughput"] = dict(
        wall_s_pallas=t_single,
        wall_s_persistent=t_pers,
        speedup=t_single / t_pers,
        steps=steps,
        launches_persistent=int(launches),
        steps_per_dispatch=steps / max(launches, 1),
        early_exit_frac=float(np.mean(lane_steps < steps)),
        mean_ndc=float(np.asarray(st_single.cnt).mean()),
        topk_identical=True,  # asserted above
    )

    # ---- dispatch floor vs per-step compute ----
    # Resuming an already-finished state makes every lane terminate on its
    # first step: the call costs dispatch + carry round-trip, ~no traversal.
    disp = {}
    for name, c in (("pallas", c_single), ("persistent", c_pers)):
        done = engine.search(c, queries, spec, budgets)

        def noop(done=done, c=c):
            st = jax.tree.map(jnp.copy, done)
            return engine.search(c, queries, spec, budgets, state=st)

        c0 = _timed(noop, NOOP_REPS)
        full = out["throughput"][f"wall_s_{'pallas' if name == 'pallas' else 'persistent'}"]
        disp[name] = dict(
            noop_resume_s=c0,
            per_step_compute_s=(full - c0) / max(steps, 1),
        )
    # the noop copy inside the timed region is common to both rows; the
    # delta between them is the launch-count difference, which is the claim
    out["dispatch"] = disp

    # ---- donation: run_search(donate) vs the same impl without donation ----
    prog = engine.compile(spec)
    attrs = engine._attrs()
    budj = jnp.broadcast_to(jnp.asarray(budgets, jnp.int32), (batch,))
    nodonate = jax.jit(search_mod._run_search_impl,
                       static_argnames=("cfg", "entry_point"))

    def _chain(fn, reps=NOOP_REPS):
        base = engine.search(c_single, queries, spec, budgets)

        def once():
            return fn(c_single, queries, prog, engine.base_vectors, attrs,
                      engine.neighbors, budj, engine.entry_point,
                      state=jax.tree.map(jnp.copy, base), gt_dist=None,
                      quant=None)

        jax.block_until_ready(once())  # warmup/compile
        best = float("inf")
        for _ in range(3):
            st = jax.tree.map(jnp.copy, base)
            jax.block_until_ready(st)
            t0 = time.perf_counter()
            for _ in range(reps):
                st = fn(c_single, queries, prog, engine.base_vectors, attrs,
                        engine.neighbors, budj, engine.entry_point, state=st,
                        gt_dist=None, quant=None)
            jax.block_until_ready(st)
            best = min(best, (time.perf_counter() - t0) / reps)
        return best

    t_don = _chain(search_mod.run_search)
    t_nodon = _chain(nodonate)
    out["donation"] = dict(
        noop_resume_s_donated=t_don,
        noop_resume_s_copying=t_nodon,
        saving_frac=1.0 - t_don / t_nodon,
        note="XLA:CPU may not alias donated host buffers, so the CPU "
             "saving can be ~0; the aliasing win lands on accelerator HBM. "
             "Donation also pins the no-accidental-copy contract that "
             "test_persistent asserts (donated carry is consumed).",
    )
    return out


def main():
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small world, no artifact write (CI smoke)")
    ap.add_argument("--out", default=None,
                    help="explicit output JSON path — written even with "
                         "--quick (an explicit path never clobbers the "
                         "committed artifact)")
    args = ap.parse_args()
    out = run(quick=args.quick)
    print(json.dumps(out, indent=2))
    sp = out["throughput"]["speedup"]
    bar = 1.3
    print(f"\npersistent vs single-step: {sp:.2f}x "
          f"({'meets' if sp >= bar else 'BELOW'} the {bar}x bar)"
          + (" [quick mode: bar not enforced]" if args.quick else ""))
    path = args.out or os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_persistent.json")
    if args.out or not args.quick:  # smoke must not clobber the artifact
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
