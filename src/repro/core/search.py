"""Batched lockstep filtered beam search — facade over the traversal stack.

This is the TPU-native adaptation of the paper's Algorithm 1 (PostFiltering
Early-Termination Search). A batch of B queries traverses the graph in
lockstep inside one `jax.lax.while_loop`; per-lane `active` masks realize
per-query adaptive termination (the E2E mechanism) without breaking SPMD.

The implementation is layered (see docs/ARCHITECTURE.md):

  repro.core.state     SearchConfig / SearchState, init + resume logic
  repro.core.step      backend-agnostic per-step bookkeeping (pop, visited
                       bitset, predicate, counters, convergence tracking)
  repro.core.backends  pluggable TraversalBackend hot paths — "dense"
                       (jnp reference), "pallas" (fused kernel) and
                       "pallas_persistent" (fused kernel + multi-step launch
                       grouping); selected statically via SearchConfig.backend
  repro.core.engine    shard-aware SearchEngine facade over device meshes

`run_search` here stitches those layers into the jitted while_loop and is
*resumable*: it consumes and returns a `SearchState`, so the paper's
zero-overhead early probe is literally the same loop run with budget=f,
whose carry then seeds the adaptive-termination phase (budget=Ŵ_q).

Persistent execution (backend "pallas_persistent") adds two entry points on
top of the same carry contract:

  `_persistent_launch`     one jitted dispatch advancing a state by up to
                           cfg.steps_per_launch lockstep steps — the host
                           analogue of the VMEM-resident multi-step kernel
                           (repro.kernels.persistent_step), which it routes
                           to on TPU in post mode.
  `run_search_persistent`  eager driver looping launches until every lane
                           terminates, compacting to the active lanes
                           between launches (valid because the lockstep loop
                           has no cross-lane collectives — the same property
                           the serving scheduler's lane surgery relies on).
                           Every launch boundary is a legal step boundary:
                           the returned state is bit-identical to
                           `run_search`'s, so probe→estimate→resume and the
                           scheduler's preemption slices work unchanged.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# Re-exports: the public surface predates the layering and stays stable.
from repro.core.backends import (  # noqa: F401
    TraversalBackend,
    available_backends,
    get_backend,
    register_backend,
)
from repro.core.state import (  # noqa: F401
    INF,
    SearchConfig,
    SearchState,
    init_state,
    prepare_resume,
    put_lanes,
    take_lanes,
    topk_results,
)
from repro.core.step import make_step


def _make_qprep(cfg: SearchConfig, queries, quant):
    """Per-query ADC state for compressed-domain traversal (None at f32)."""
    precision = cfg.precision or "float32"
    if precision == "float32":
        return None
    if quant is None:
        raise ValueError(
            f"cfg.precision={precision!r} needs a quant index — build "
            "the engine with precision=... or pass quant= explicitly")
    from repro.quant.codecs import prepare_query

    return prepare_query(precision, quant, queries)


def _run_search_impl(
    cfg: SearchConfig,
    queries: jax.Array,
    prog,                          # FilterProgram (leaves [B, S, ...])
    base_vectors: jax.Array,
    attrs,                         # (labels [N, W] u32, values [N, V] f32)
    neighbors: jax.Array,
    budgets: jax.Array,            # [B] i32 NDC budgets (use big value for ∞)
    entry_point: int,
    state: SearchState | None = None,
    gt_dist: jax.Array | None = None,
    quant=None,                    # Int8Index | PQIndex for compressed mode
) -> SearchState:
    """Run (or resume) the lockstep search until all lanes terminate.

    Filters arrive pre-compiled: `prog` is a `FilterProgram` whose padded
    clause slots let a batch of heterogeneous boolean filters evaluate in
    one traced pass (the engine compiles FilterSpec / expression inputs).
    Termination per lane: queue exhausted, NDC ≥ budget, or (optional)
    greedy result-bound stop. Resuming with a larger budget continues
    exactly where the previous phase stopped — the paper's zero-overhead
    probe reuse. The traversal backend is resolved statically from
    `cfg.backend`, so dense and Pallas hot paths share this loop verbatim.

    When `cfg.precision` is "int8" or "pq", `quant` must carry the matching
    compressed index (repro.quant); the per-query ADC state is prepared
    once here and every step evaluates distances in the compressed domain.
    Probe/resume semantics are unchanged — the compressed traversal is
    bit-resumable within its precision mode.

    The jitted wrapper (`run_search`) donates `state`: a resumed carry's
    buffers are updated in place rather than copied, so callers must not
    reuse a state object after passing it here (slice lanes out with
    `take_lanes` first if a copy is needed — every in-repo caller either
    rebinds or passes a fresh slice).
    """
    backend = get_backend(cfg.backend or "dense")
    qprep = _make_qprep(cfg, queries, quant)
    if state is None:
        state = init_state(cfg, queries, prog, base_vectors, attrs, entry_point,
                           gt_dist, quant=quant, qprep=qprep)
    else:
        state = prepare_resume(state)

    step = make_step(cfg, backend, queries, prog, base_vectors, attrs,
                     neighbors, budgets, gt_dist, quant=quant, qprep=qprep)

    if getattr(backend, "persistent", False):
        # Launch-grouped form of the same loop: an inner bounded while of up
        # to cfg.steps_per_launch steps per outer trip. Bit-identical to the
        # flat loop (inactive-lane steps are no-ops, and the inner/outer
        # bounds compose to the same max_steps cutoff); the grouping is what
        # a persistent backend's dispatch amortization maps onto when this
        # traced path runs under shard_map.
        spl = max(1, cfg.steps_per_launch)

        def cond(carry):
            state, it = carry
            return jnp.any(state.active) & (it < cfg.max_steps)

        def body(carry):
            state, it = carry

            def icond(c):
                st, j = c
                return ((j < spl) & (it + j < cfg.max_steps)
                        & jnp.any(st.active))

            def ibody(c):
                st, j = c
                return step(st), j + 1

            state, j = jax.lax.while_loop(icond, ibody, (state, jnp.int32(0)))
            return state, it + j

        state, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
        return state

    def cond(carry):
        state, it = carry
        return jnp.any(state.active) & (it < cfg.max_steps)

    def body(carry):
        state, it = carry
        return step(state), it + 1

    state, _ = jax.lax.while_loop(cond, body, (state, jnp.int32(0)))
    return state


# `state` is donated: the carry is consumed by the call that resumes it, so
# XLA updates the ~17 state buffers in place instead of copying them on
# every probe→resume / preemption slice. (Donation inside a traced context —
# e.g. under the sharded engine's shard_map — is ignored by JAX, which is
# exactly the safe behavior.)
# `entry_point` is traced, not static: the shards of a sharded engine have
# different entry nodes and share one compiled program.
run_search = functools.partial(
    jax.jit,
    static_argnames=("cfg",),
    donate_argnames=("state",),
)(_run_search_impl)

# Untraced entry for callers already inside a traced context (the sharded
# engine's shard_map body runs one traversal per local index shard, with a
# traced per-shard entry point).
run_search_impl = _run_search_impl


# --------------------------------------------------------------------------
# persistent execution: multi-step launches + eager active-lane compaction
# --------------------------------------------------------------------------

# Driver-observed dispatch accounting. `_persistent_launch` is the only
# device dispatch the persistent driver makes, so counting calls here is
# ground truth for "how many launches did this search actually cost" — the
# quantity the serving metrics report (a ⌈steps/spl⌉ estimate undercounts:
# probe phases dispatch once per snapshot, and compaction relaunches split
# what a step count would merge). Lifetime counters, read via deltas.
# `bodies` counts launches per body: "<mode>:kernel" (the VMEM-resident
# multi-step kernel) or "<mode>:xla" (the jitted launch loop of steps).
_DISPATCH_COUNTERS = {"launches": 0, "compactions": 0, "steps": 0,
                      "bodies": {}}


def dispatch_counters() -> dict:
    """Snapshot of lifetime persistent-driver dispatch counters:
    `launches` (device dispatches), `compactions` (launches at reduced
    lane width), `steps` (lockstep trips actually advanced), and
    `bodies` (launches per "<mode>:kernel|xla" body). Callers measure
    work by differencing two snapshots."""
    return dict(_DISPATCH_COUNTERS,
                bodies=dict(_DISPATCH_COUNTERS["bodies"]))


def _count_launch(body: str, steps: int, compacted: bool = False):
    _DISPATCH_COUNTERS["launches"] += 1
    _DISPATCH_COUNTERS["compactions"] += int(compacted)
    _DISPATCH_COUNTERS["steps"] += steps
    bodies = _DISPATCH_COUNTERS["bodies"]
    bodies[body] = bodies.get(body, 0) + 1


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "mode", "use_kernel"),
    donate_argnames=("state",),
)
def _persistent_launch(
    cfg: SearchConfig,
    queries, prog, base_vectors, attrs, neighbors, budgets,
    entry_point: int,
    state, gt_dist, quant, qprep, rem,
    rows=None, aux=None,
    *, mode: str, use_kernel: bool = False,
):
    """One persistent dispatch: advance by up to cfg.steps_per_launch steps.

    mode  "init"    no incoming state — build it (first launch of a search)
          "resume"  incoming probe carry — reactivate budget-stopped lanes
          "cont"    mid-search launch — must NOT reactivate: lanes that
                    terminated in an earlier launch of the *same* search
                    stay terminated (this is what makes a launch boundary
                    invisible, not a resume point)

    rem bounds the steps this launch may take (max_steps accounting across
    launches); `use_kernel` routes to the VMEM-resident multi-step Pallas
    kernel (TPU, post mode — `rows`/`aux` are its DMA-padded operand stores),
    otherwise the host inner while_loop runs the same steps. Either way the
    result is a bit-exact step boundary of the single-step loop.
    """
    if mode == "init":
        state = init_state(cfg, queries, prog, base_vectors, attrs,
                           entry_point, gt_dist, quant=quant, qprep=qprep)
    elif mode == "resume":
        state = prepare_resume(state)
    spl = max(1, cfg.steps_per_launch)

    if use_kernel:
        from repro.kernels.persistent_step import persistent_multi_step

        return persistent_multi_step(
            cfg, queries, prog, rows, aux, neighbors, budgets, state, rem,
            gt_dist, qprep, steps=spl, n_values=int(attrs[1].shape[1]),
            has_gt=gt_dist is not None)

    backend = get_backend(cfg.backend or "dense")
    step = make_step(cfg, backend, queries, prog, base_vectors, attrs,
                     neighbors, budgets, gt_dist, quant=quant, qprep=qprep)

    def icond(c):
        st, j = c
        return (j < spl) & (j < rem) & jnp.any(st.active)

    def ibody(c):
        st, j = c
        return step(st), j + 1

    state, _ = jax.lax.while_loop(icond, ibody, (state, jnp.int32(0)))
    return state


def run_search_persistent(
    cfg: SearchConfig,
    queries: jax.Array,
    prog,
    base_vectors: jax.Array,
    attrs,
    neighbors: jax.Array,
    budgets,
    entry_point: int,
    state: SearchState | None = None,
    gt_dist: jax.Array | None = None,
    quant=None,
    tracer=None,
    trace_id: str = "",
    kernel_operands=None,
) -> SearchState:
    """Eager launch-loop driver for persistent backends (single device).

    Same signature and bit-exact results as `run_search`; the difference is
    *how* the steps are dispatched. Each trip runs one `_persistent_launch`
    of up to cfg.steps_per_launch steps, then reads back only the per-lane
    `active`/`hops` scalars. Lanes that terminated early are compacted away
    between launches: the surviving lanes are gathered (`take_lanes`) into
    the next power-of-two batch width, advanced, and scattered back
    (`put_lanes`, donated). This host-side compaction is the CPU/GPU
    analogue of the TPU kernel's in-kernel early exit — finished lanes stop
    costing compute at launch granularity instead of riding as no-ops until
    the slowest lane finishes.

    The selection pad (repeating the first active lane up to the ladder
    width) is benign: duplicated lanes carry identical buffers, follow
    identical deterministic trajectories, and scatter back identical values.

    `state`, when passed, is donated (same contract as `run_search`).

    `tracer`/`trace_id` emit one span per launch (width, mode, steps
    advanced) and one instant event per compaction. Spans wrap only the
    dispatch + the `hops` readback the driver performs anyway — tracing
    adds no device synchronization and the state stream is untouched, so
    results are bit-identical with tracing on or off.

    `kernel_operands(precision)` returns the kernel's packed (rows, aux,
    nbrs) HBM operands; the engine passes `SearchEngine.persistent_operands`,
    which keeps them for its lifetime. Only called where the kernel runs.
    """
    from repro.obs.trace import as_tracer

    tr = as_tracer(tracer)
    qprep = _make_qprep(cfg, queries, quant)
    b = int(queries.shape[0])
    budgets = jnp.broadcast_to(jnp.asarray(budgets, jnp.int32), (b,))
    from repro.kernels.ops import interpret_mode

    # the multi-step kernel covers post mode; pre/widen run the jitted
    # launch loop over single steps (`dispatch_counters()["bodies"]`)
    use_kernel = cfg.mode == "post" and not interpret_mode()
    body = f"{cfg.mode}:{'kernel' if use_kernel else 'xla'}"
    rows = aux = None
    if use_kernel:
        rows, aux, neighbors = kernel_operands(cfg.precision or "float32")

    mode = "init" if state is None else "resume"
    hops0 = 0 if state is None else np.asarray(state.hops)
    with tr.span("launch", trace_id, mode=mode, width=b) as sp:
        state = _persistent_launch(
            cfg, queries, prog, base_vectors, attrs, neighbors, budgets,
            entry_point, state, gt_dist, quant, qprep,
            jnp.int32(cfg.max_steps), rows, aux, mode=mode,
            use_kernel=use_kernel)
        it = int((np.asarray(state.hops) - hops0).max(initial=0))
        sp.set(steps=it)
    _count_launch(body, it)

    min_w = min(8, b)  # ladder floor bounds the retrace count to O(log B)
    while it < cfg.max_steps:
        sel = np.flatnonzero(np.asarray(state.active))
        if sel.size == 0:
            break
        w = min(b, max(min_w, 1 << (int(sel.size) - 1).bit_length()))
        rem = jnp.int32(cfg.max_steps - it)
        if w == b:  # no compaction win — relaunch at full width
            hops0 = np.asarray(state.hops)
            with tr.span("launch", trace_id, mode="cont", width=b,
                         active=int(sel.size)) as sp:
                state = _persistent_launch(
                    cfg, queries, prog, base_vectors, attrs, neighbors,
                    budgets, entry_point, state, gt_dist, quant, qprep, rem,
                    rows, aux, mode="cont", use_kernel=use_kernel)
                d = int((np.asarray(state.hops) - hops0).max(initial=0))
                sp.set(steps=d)
            it += d
            _count_launch(body, d)
            continue
        pad = w - int(sel.size)
        tr.emit("compact", trace_id, from_width=b, to_width=w,
                active=int(sel.size), pad=pad)
        sel_p = (np.concatenate([sel, np.full(pad, sel[0], sel.dtype)])
                 if pad else sel)
        sub_state, sub_q, sub_prog, sub_bud, sub_gt, sub_qp = take_lanes(
            (state, queries, prog, budgets, gt_dist, qprep), sel_p)
        hops0 = np.asarray(sub_state.hops)
        with tr.span("launch", trace_id, mode="cont", width=w,
                     active=int(sel.size), compacted=True) as sp:
            out = _persistent_launch(
                cfg, sub_q, sub_prog, base_vectors, attrs, neighbors,
                sub_bud, entry_point, sub_state, sub_gt, quant, sub_qp, rem,
                rows, aux, mode="cont", use_kernel=use_kernel)
            d = int((np.asarray(out.hops) - hops0).max(initial=0))
            sp.set(steps=d)
        it += d
        _count_launch(body, d, compacted=True)
        state = put_lanes(state, out, sel_p)
    return state
