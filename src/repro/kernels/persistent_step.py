"""Persistent multi-step Pallas traversal kernel: VMEM-resident search state.

The single-step path (kernels/fused_step.py) pays a fixed per-step tax: one
kernel dispatch per lockstep step, with queue / result / visited buffers and
the gathered codes bouncing through HBM between steps. This kernel runs up
to `steps_per_launch` steps in ONE launch:

  * the candidate queue, result set and every per-lane counter ride the
    kernel's step loop as VMEM-resident carries, and the visited bitset is
    updated in place in its VMEM output block — nothing round-trips HBM
    until the launch boundary;
  * neighbor rows are gathered straight from HBM with per-row async copies
    into VMEM landing buffers, split into two streams (vector/code rows and
    packed attribute rows) so the visited-bitset pass — which needs only
    the neighbor ids — overlaps both streams' DMAs, the attribute wait
    lands just before the filter-program evaluation and the row wait just
    before the MXU distance block;
  * per-lane termination (budget exhausted, queue drained, or — with
    `greedy_stop` — the paper's early-exit condition queue-head ≥
    result-tail) is evaluated *in-kernel*: a lane that trips it contributes
    no DMAs and all of its merge writebacks are lane-masked no-ops, and the
    launch itself exits early (`lax.while_loop`) once every lane is done.

Bit-compatibility contract: each in-kernel step reproduces
`core.step.make_step` + the pallas backend exactly — same pop, same
visited test-before-set semantics (duplicate ids within a row both count,
as on the host), same `_merge_core` program+merge tail shared with the
single-step kernels, same lane-masked counter updates — so the kernel can
stop after ANY step boundary and emit a full `SearchState` that
probe→estimate→resume, the planner's shared probe carry, and serve's lane
surgery consume unchanged.

Operand layout (packed once per engine, `SearchEngine.persistent_operands`,
never per launch). Every
per-node store is one 128-lane row per node, because a one-row DMA out of
HBM must span whole (8, 128) tiles:

  rows [N, Dp]   f32 vectors | int8 codes | PQ codes, both widened to i32
                 (int8 packs 32 rows per tile), padded to a 128-lane row.
  aux  [N, Ap]   uint32-packed per-node words:
                 [0:W) label words | [W:W+V) value channels (f32 bitcast) |
                 W+V   ‖x̂‖² ADC norm | W+V+1 reconstruction error.
                 One aux row DMA replaces three separate gathers.
  nbrs [N, Rp]   neighbor ids, padded to a 128-lane row. Each popped
                 node's row lands twice: in VMEM for the vector work and in
                 SMEM for the scalar row addresses and bitset slots.

The visited bitset crosses the kernel boundary as [⌈nw/128⌉, B, 128] int32
(`_bitset_rows`): word w of lane l sits at row w // 128, so a test is one
dynamic-row load of an (8, 128) tile. VMEM per block (bb lanes) on top of
the single-step budget: the bitset's input and output blocks, each
bb·⌈N/4096⌉·512 B and double-buffered (1 MiB each at N = 2^20, bb = 8),
plus the landing buffers bb·R·(Dp + Ap + Rp)·4 B.

The kernel covers `mode="post"` (1-hop frontier, the serving hot path);
pre/widen frontiers (1-hop ∪ strided 2-hop with intra-step dedup) keep the
host multi-step path in core/search.py, which is also the CPU execution of
the `pallas_persistent` backend. A further step of DMA pipelining —
speculatively prefetching the *next* pop's rows during the current merge,
with an eviction guard when the merge changes the queue head — is
documented in docs/ARCHITECTURE.md as TPU-measurement future work; the
pop→gather dependency makes it a semantics-preserving gamble rather than
a straight rotation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.filters.compile import CLAUSE_FEATURE_SLOTS
from repro.kernels.distance import HIGHEST
from repro.kernels.fused_step import _merge_core
from repro.kernels.topk import (LANES as _LANES, network_width, pack_payload,
                                unpack_payload)

INF = float("inf")

# Column order of the packed per-lane counter block ([bb, 8] int32) that
# carries every scalar SearchState leaf through the kernel.
_CTR_FIELDS = ("cnt", "n_inspected", "n_valid_visited", "n_pop_valid",
               "hops", "conv_cnt", "res_full_cnt", "active")


def _pad_cols(a, width, fill=0):
    """Zero-pad the trailing axis to `width` (DMA row alignment)."""
    pad = width - a.shape[-1]
    if pad <= 0:
        return a
    widths = ((0, 0),) * (a.ndim - 1) + ((0, pad),)
    return jnp.pad(a, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("precision",))
def build_persistent_operands(precision, base_vectors, label_attrs,
                              value_attrs, neighbors, quant):
    """Pack the per-node HBM operands (rows, aux, nbrs) for the persistent
    kernel.

    The engine packs them once and keeps them (`SearchEngine
    .persistent_operands`): per-call packing made probe and resume each
    pay O(N·128) words of transient HBM, per-launch packing would also
    erase the dispatch-amortization win. Returns
    (rows [N, Dp], aux [N, Ap] u32, nbrs [N, Rp] i32); see the module
    docstring for layout.
    """
    from repro.quant.codecs import pad_rows_for_dma

    n = label_attrs.shape[0]
    if precision == "float32":
        rows = pad_rows_for_dma(jnp.asarray(base_vectors, jnp.float32))
        xn = jnp.zeros((n,), jnp.float32)
        err = jnp.zeros((n,), jnp.float32)
    elif precision == "int8":
        # widened to i32 rows: a one-row DMA out of HBM must span whole
        # (8, 128) tiles, and int8 packs 32 rows into each
        rows = pad_rows_for_dma(quant.codes.astype(jnp.int32))
        xn, err = quant.norms, quant.err
    elif precision == "pq":
        # uint8 store widened to i32 once: the in-kernel one-hot LUT
        # contraction consumes i32 slots. (A production TPU build would DMA
        # the uint8 rows and widen in-register; 4× operand memory is the
        # price of keeping this kernel's row DMA layout uniform.)
        rows = pad_rows_for_dma(quant.codes.astype(jnp.int32))
    else:
        raise ValueError(f"unknown precision {precision!r}")
    if precision == "pq":
        xn, err = quant.norms, quant.err
    bc = functools.partial(jax.lax.bitcast_convert_type,
                           new_dtype=jnp.uint32)
    aux = jnp.concatenate([
        label_attrs.astype(jnp.uint32),
        bc(value_attrs.astype(jnp.float32)),
        bc(xn)[:, None],
        bc(err)[:, None],
    ], axis=1)
    return rows, pad_rows_for_dma(aux), pad_rows_for_dma(neighbors)


def _persistent_kernel(*refs, bb, m, k, r, w, v, wq, wr, nrow, n_head,
                       steps, greedy, has_gt, precision, n_clause):
    """One launch: up to `steps` lockstep traversal steps, state in VMEM.

    Ref order: rem (SMEM) | nbrs, rows, aux (HBM) | head inputs (n_head) |
    8 program leaves | budgets | [gt] | cd, cp, rd, ri, vis, ctr, ncl, qerr
    | 8 outputs | nbid, nbs, seen_s, vbuf, abuf + 3 DMA semaphore arrays
    (scratch).

    Per-lane quantities are [bb, 1] columns and flags ride as int32:
    Mosaic lowers neither 1-D vectors nor i1 loads, reductions or scalar
    extracts reliably. The visited bitset is updated in place in the
    output block, laid out [nrow, bb, 128] so that one word test is one
    dynamic-row vreg load.
    """
    it = iter(refs)
    rem_ref = next(it)
    nbrs_hbm, rows_hbm, aux_hbm = next(it), next(it), next(it)
    heads = [next(it) for _ in range(n_head)]
    (kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref, neg_ref, term_ref,
     tact_ref) = (next(it) for _ in range(8))
    bud_ref = next(it)
    gt_ref = next(it) if has_gt else None
    (cd_ref, cp_ref, rd_ref, ri_ref, vis_ref, ctr_ref, ncl_ref,
     qerr_ref) = (next(it) for _ in range(8))
    (ocd_ref, ocp_ref, ord_ref, ori_ref, ovis_ref, octr_ref, oncl_ref,
     oqerr_ref) = (next(it) for _ in range(8))
    nbid, nbs, seen_s, vbuf, abuf, nsem, vsem, asem = (
        next(it) for _ in range(8))

    # ---- loop-invariant VMEM loads (once per launch, not per step) ----
    kinds, masks = kinds_ref[...], masks_ref[...]
    lo, hi = lo_ref[...], hi_ref[...]
    vattr, neg = vattr_ref[...], neg_ref[...]
    term_pack, tact = term_ref[...], tact_ref[...]
    budgets = bud_ref[...]                                     # [bb, 1]
    gt = gt_ref[...] if has_gt else None
    rem = rem_ref[0]
    if precision == "float32":
        q = heads[0][...].astype(jnp.float32)                  # [bb, Dp]
        qn_head = jnp.sum(q * q, axis=-1, keepdims=True)
    elif precision == "int8":
        qq = heads[0][...]                                     # [bb, Dp] i8
        sq, qn_head = heads[1][...], heads[2][...]             # [bb, 1] f32
    else:                                                      # pq
        lut = heads[0][...]                                    # [bb, SL, Kc]
        qn_head = heads[1][...]                                # [bb, 1] f32
        sl = lut.shape[1]

    ctr0 = ctr_ref[...]
    ovis_ref[...] = vis_ref[...]     # the bitset is updated in place below
    f32 = functools.partial(jax.lax.bitcast_convert_type,
                            new_dtype=jnp.float32)
    col_m = jax.lax.broadcasted_iota(jnp.int32, (bb, m), 1)
    row_1 = jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)
    row_r = jax.lax.broadcasted_iota(jnp.int32, (bb, r), 0)
    col_r = jax.lax.broadcasted_iota(jnp.int32, (bb, r), 1)
    row_v = jax.lax.broadcasted_iota(jnp.int32, (bb, _LANES), 0)
    col_v = jax.lax.broadcasted_iota(jnp.int32, (bb, _LANES), 1)

    def lane_scalar(col, l):
        """Element l of an int32 [bb, 1] column, as a scalar."""
        return jnp.sum(jnp.where(row_1 == l, col, 0))

    def bitset_slot(l, ri):
        """(row, in-tile mask, bit) of lane l's neighbor ri in the bitset."""
        j = jnp.maximum(nbs[l, ri], 0)
        word = j >> 5
        row = jnp.minimum(word >> 7, nrow - 1)
        at = (row_v == l) & (col_v == (word & (_LANES - 1)))
        return row, at, j & 31

    def body(carry):
        (s, cd, cp, rdv, riv, cnt, nin, nvv, nclv, npv, qerr, hops,
         prev_act, conv, rfull) = carry

        # ---- pop best unexpanded candidate per lane ----
        idx, exp, vbit = unpack_payload(cp)
        unexp = (~exp) & (idx >= 0)
        pop_key = jnp.where(unexp, cd, INF)
        best_d = jnp.min(pop_key, axis=1, keepdims=True)       # [bb, 1]
        # first minimum, as argmin picks it
        p = jnp.min(jnp.where(pop_key == best_d, col_m, m), axis=1,
                    keepdims=True)
        sel = col_m == p
        has_cand = best_d < INF
        u = jnp.sum(jnp.where(sel, idx, 0), axis=1, keepdims=True)
        u_valid = jnp.max(jnp.where(sel & vbit, 1, 0), axis=1,
                          keepdims=True) > 0

        # ---- in-kernel per-lane termination (the adaptive early exit) ----
        act = (prev_act != 0) & has_cand & (cnt < budgets)
        if greedy:
            worst_res = rdv[:, k - 1:k]
            act = act & ~((worst_res < INF) & (best_d > worst_res))
        act_i = act.astype(jnp.int32)
        act_s = [lane_scalar(act_i, l) for l in range(bb)]

        # mark the popped slot expanded (lane-masked, as on the host)
        cp_pop = jnp.where(sel & act, cp | (1 << 29), cp)

        # ---- gather frontier neighbor ids (1-hop row DMA per lane) ----
        # one copy lands in VMEM for the vector work, one in SMEM for the
        # scalar row addresses and bitset slots
        u_safe = jnp.maximum(u, 0)

        def nbr_copies(l):
            ul = lane_scalar(u_safe, l)
            return (pltpu.make_async_copy(nbrs_hbm.at[ul], nbid.at[l],
                                          nsem.at[0, l]),
                    pltpu.make_async_copy(nbrs_hbm.at[ul], nbs.at[l],
                                          nsem.at[1, l]))

        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                for c in nbr_copies(l):
                    c.start()
        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                for c in nbr_copies(l):
                    c.wait()
        nb = jnp.where(act, nbid[...][:, :r], -1)

        # ---- launch both gather streams (vector/code rows + aux rows) ----
        # Finished lanes issue nothing: their DMA slots stay idle and the
        # stale landing buffers are masked out of every consumer below.
        # One DMA semaphore per lane and stream (semaphore memory holds a
        # few hundred): each wait below retires one row's bytes.
        def row_copy(src, dst, sem, l, ri_):
            j = jnp.maximum(nbs[l, ri_], 0)
            return pltpu.make_async_copy(src.at[j], dst.at[l, ri_], sem.at[l])

        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                for ri_ in range(r):
                    row_copy(rows_hbm, vbuf, vsem, l, ri_).start()
                    row_copy(aux_hbm, abuf, asem, l, ri_).start()

        # ---- visited test-before-set, overlapping the in-flight DMAs ----
        # Every test reads the PRE-step words before any bit is set, which
        # keeps the host's duplicate-id semantics (both copies of an id
        # repeated within one row count as new). The set is an integer
        # ADD, not an OR: the host marks via .add(mode="drop"), so a
        # repeated id carries into the next bit, and so does this.
        seen = jnp.zeros((bb, r), jnp.int32)
        for l in range(bb):
            def test(ri_, seen, l=l):
                row, at, sh = bitset_slot(l, ri_)
                word = jnp.sum(jnp.where(at, ovis_ref[row], 0))
                hit = (word >> sh) & 1
                seen_s[l, ri_] = hit
                return jnp.where((row_r == l) & (col_r == ri_), hit, seen)

            seen = jax.lax.cond(act_s[l] != 0,
                                lambda sn, l=l, f=test: jax.lax.fori_loop(
                                    0, r, f, sn),
                                lambda sn: sn, seen)
        is_new = (nb >= 0) & (seen == 0)      # nb < 0 on finished lanes

        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                @pl.loop(0, r)
                def _(ri_):
                    @pl.when((nbs[l, ri_] >= 0) & (seen_s[l, ri_] == 0))
                    def _():
                        row, at, sh = bitset_slot(l, ri_)
                        ovis_ref[row] = ovis_ref[row] + jnp.where(
                            at, jnp.left_shift(jnp.int32(1), sh), 0)

        # ---- attribute stream lands: unpack the packed aux words ----
        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                for ri_ in range(r):
                    row_copy(aux_hbm, abuf, asem, l, ri_).wait()
        auxv = abuf[...]
        labels_g = auxv[:, :, :w]
        values_g = f32(auxv[:, :, w:w + v])
        xn_aux = f32(auxv[:, :, w + v])                        # [bb, r]
        err_g = f32(auxv[:, :, w + v + 1])

        # ---- row stream lands: distance block (same math per codec as
        # the single-step kernels in fused_step.py) ----
        for l in range(bb):
            @pl.when(act_s[l] != 0)
            def _(l=l):
                for ri_ in range(r):
                    row_copy(rows_hbm, vbuf, vsem, l, ri_).wait()
        if precision == "float32":
            x = vbuf[...].astype(jnp.float32)                  # [bb, r, Dp]
            xn = jnp.sum(x * x, axis=-1)
            qx = jax.lax.dot_general(
                q[:, None, :], x,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                precision=HIGHEST,
                preferred_element_type=jnp.float32)[:, 0, :]
            d = jnp.maximum(qn_head + xn - 2.0 * qx, 0.0)
        elif precision == "int8":
            codes = vbuf[...].astype(jnp.int8)                 # [bb, r, Dp]
            dot = jax.lax.dot_general(
                qq[:, None, :], codes,
                dimension_numbers=(((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.int32)[:, 0, :]
            d = jnp.maximum(
                qn_head + xn_aux - 2.0 * sq * dot.astype(jnp.float32), 0.0)
        else:                                                  # pq
            codes = vbuf[...][:, :, :sl]                       # [bb, r, SL]
            kc = lut.shape[2]
            ip = jnp.zeros((bb, r), jnp.float32)
            for si in range(sl):
                onehot = (codes[:, :, si][:, :, None]
                          == jnp.arange(kc, dtype=jnp.int32)[None, None, :]
                          ).astype(jnp.float32)
                ip = ip + jax.lax.dot_general(
                    onehot, lut[:, si, :][:, :, None],
                    dimension_numbers=(((2,), (1,)), ((0,), (0,))),
                    precision=HIGHEST,
                    preferred_element_type=jnp.float32)[:, :, 0]
            d = jnp.maximum(qn_head + xn_aux - 2.0 * ip, 0.0)

        # ---- shared program + merge tail (bit-shared with fused_step) ----
        ocd, ocp, ordd, ori, valid, occ = _merge_core(
            d, nb, is_new, kinds, masks, lo, hi, vattr, neg, term_pack,
            tact, labels_g, values_g, cd, cp_pop, rdv, riv,
            m=m, k=k, wq=wq, wr=wr, pre=False, n_clause=n_clause)

        # ---- counters, lane-masked exactly as core.step.make_step ----
        def rsum(x):
            return jnp.sum(x, axis=1, keepdims=True)

        ndc_add = rsum(is_new.astype(jnp.int32))               # post mode
        valid_add = rsum(valid.astype(jnp.int32))
        err_add = rsum(jnp.where(is_new, err_g, 0.0))
        cnt_n = cnt + jnp.where(act, ndc_add, 0)
        nin_n = nin + jnp.where(act, ndc_add, 0)
        nvv_n = nvv + jnp.where(act, valid_add, 0)
        nclv_n = nclv + jnp.where(act, occ, 0)
        npv_n = npv + jnp.where(act & u_valid, 1, 0)
        qerr_n = qerr + jnp.where(act, err_add, 0.0)
        hops_n = hops + jnp.where(act, 1, 0)

        if has_gt:
            covered = jnp.min(jnp.where(ordd <= gt + 1e-6, 1, 0), axis=1,
                              keepdims=True) > 0
            conv_n = jnp.where((conv < 0) & covered, cnt_n, conv)
        else:
            conv_n = conv
        now_full = (ordd[:, k - 1:k] < INF) & act
        rfull_n = jnp.where((rfull < 0) & now_full, cnt_n, rfull)

        return (s + 1,
                jnp.where(act, ocd, cd), jnp.where(act, ocp, cp_pop),
                jnp.where(act, ordd, rdv), jnp.where(act, ori, riv),
                cnt_n, nin_n, nvv_n, nclv_n, npv_n, qerr_n, hops_n,
                act_i, conv_n, rfull_n)

    def cond(carry):
        s = carry[0]
        prev_act = carry[12]
        return (s < steps) & (s < rem) & (jnp.max(prev_act) > 0)

    def col(c):
        return ctr0[:, c:c + 1]

    init = (jnp.int32(0), cd_ref[...], cp_ref[...], rd_ref[...], ri_ref[...],
            col(0), col(1), col(2), ncl_ref[...], col(3), qerr_ref[...],
            col(4), col(7), col(5), col(6))
    (_, cd, cp, rdv, riv, cnt, nin, nvv, nclv, npv, qerr, hops, act,
     conv, rfull) = jax.lax.while_loop(cond, body, init)

    ocd_ref[...] = cd
    ocp_ref[...] = cp
    ord_ref[...] = rdv
    ori_ref[...] = riv
    for c, x in enumerate((cnt, nin, nvv, npv, hops, conv, rfull, act)):
        octr_ref[:, c:c + 1] = x
    oncl_ref[...] = nclv
    oqerr_ref[...] = qerr


@functools.partial(jax.jit, static_argnames=("cfg", "steps", "n_values",
                                             "has_gt", "interpret",
                                             "block_b"))
def persistent_multi_step(cfg, queries, prog, rows, aux, neighbors, budgets,
                          state, rem, gt_dist, qprep, *, steps: int,
                          n_values: int, has_gt: bool,
                          interpret: bool = False, block_b: int = 8):
    """Run up to `steps` lockstep traversal steps in one kernel launch.

    rows/aux/neighbors are the per-node HBM operands from
    `build_persistent_operands` (packed once per engine); `rem` is a
    traced scalar bound on how many steps this launch may still take
    (cfg.max_steps bookkeeping), and
    the kernel additionally stops the moment every lane terminates.
    Returns a full `SearchState`, bit-compatible with `steps` iterations of
    the single-step path (post mode).
    """
    precision = cfg.precision or "float32"
    b = queries.shape[0]
    m, k, r = cfg.queue_size, cfg.k, cfg.degree
    s = prog.kinds.shape[1]
    t = prog.term_active.shape[1]
    w = prog.masks.shape[2]
    nw = state.visited.shape[1]
    dp = rows.shape[1]
    ap = aux.shape[1]
    v = n_values  # aux cols [w, w+v) — ap is DMA-padded, not layout-tight
    wq = network_width(m + r, interpret)
    wr = network_width(k + r, interpret)
    nrow = -(-nw // _LANES)      # bitset rows of 128 words per lane
    rp = neighbors.shape[1]      # one lane-aligned row DMA per node
    if rp % _LANES:
        raise ValueError(f"neighbor rows are {rp} wide: pass the "
                         "lane-padded nbrs of build_persistent_operands")
    term_pack = jnp.where(prog.active, prog.term, -1).astype(jnp.int32)

    # The per-lane DMA issue is statically unrolled over the block's lanes,
    # so the block stays small even in interpret mode (unlike fused_step's
    # full-batch interpret block).
    bb = min(block_b, b)
    pad = (-b) % bb

    def pad0(a, fill=0):
        if pad == 0:
            return a
        widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill)

    # head inputs per codec (query side of the distance block)
    if precision == "float32":
        head_in = [pad0(_pad_cols(queries.astype(jnp.float32), dp))]
        head_specs = [_row((bb, dp))]
    elif precision == "int8":
        head_in = [pad0(_pad_cols(qprep.qq, dp)), pad0(qprep.sq[:, None]),
                   pad0(qprep.qn[:, None])]
        head_specs = [_row((bb, dp)), _row((bb, 1)), _row((bb, 1))]
    elif precision == "pq":
        sl, kc = qprep.lut.shape[1], qprep.lut.shape[2]
        head_in = [pad0(qprep.lut), pad0(qprep.qn[:, None])]
        head_specs = [_row((bb, sl, kc)), _row((bb, 1))]
    else:
        raise ValueError(f"unknown precision {precision!r}")

    cp = pack_payload(state.cand_idx, state.cand_exp, state.cand_valid)
    ctr = jnp.stack(
        [state.cnt, state.n_inspected, state.n_valid_visited,
         state.n_pop_valid, state.hops, state.conv_cnt, state.res_full_cnt,
         state.active.astype(jnp.int32)], axis=1)

    inputs = head_in + [
        pad0(prog.kinds), pad0(prog.masks), pad0(prog.lo), pad0(prog.hi),
        pad0(prog.vattr), pad0(prog.neg).astype(jnp.int32),
        pad0(term_pack, -1), pad0(prog.term_active).astype(jnp.int32),
        pad0(jnp.asarray(budgets, jnp.int32)[:, None]),
    ]
    in_specs = head_specs + [
        _row((bb, s)), _row((bb, s, w)), _row((bb, s)), _row((bb, s)),
        _row((bb, s)), _row((bb, s)), _row((bb, s)), _row((bb, t)),
        _row((bb, 1)),
    ]
    if has_gt:
        inputs.append(pad0(jnp.asarray(gt_dist, jnp.float32)))
        in_specs.append(_row((bb, k)))
    inputs += [
        pad0(state.cand_dist.astype(jnp.float32), jnp.inf), pad0(cp, -1),
        pad0(state.res_dist.astype(jnp.float32), jnp.inf),
        pad0(state.res_idx, -1), _bitset_rows(pad0(state.visited), nrow),
        pad0(ctr),
        pad0(state.n_clause_valid), pad0(state.q_err_sum[:, None]),
    ]
    vis_spec = pl.BlockSpec((nrow, bb, _LANES), lambda i: (0, i, 0))
    in_specs += [
        _row((bb, m)), _row((bb, m)), _row((bb, k)), _row((bb, k)),
        vis_spec, _row((bb, 8)), _row((bb, CLAUSE_FEATURE_SLOTS)),
        _row((bb, 1)),
    ]
    bp = b + pad

    kern = functools.partial(
        _persistent_kernel, bb=bb, m=m, k=k, r=r, w=w, v=v, wq=wq, wr=wr,
        nrow=nrow, n_head=len(head_in), steps=steps,
        greedy=cfg.greedy_stop, has_gt=has_gt, precision=precision,
        n_clause=CLAUSE_FEATURE_SLOTS)
    ocd, ocp, ordd, ori, ovis, octr, oncl, oqerr = pl.pallas_call(
        kern,
        grid=(bp // bb,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * 3 + in_specs,
        out_specs=[
            _row((bb, m)), _row((bb, m)), _row((bb, k)), _row((bb, k)),
            vis_spec, _row((bb, 8)),
            _row((bb, CLAUSE_FEATURE_SLOTS)), _row((bb, 1)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, m), jnp.float32),
            jax.ShapeDtypeStruct((bp, m), jnp.int32),
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
            jax.ShapeDtypeStruct((nrow, bp, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((bp, 8), jnp.int32),
            jax.ShapeDtypeStruct((bp, CLAUSE_FEATURE_SLOTS), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, rp), jnp.int32),
            pltpu.SMEM((bb, rp), jnp.int32),
            pltpu.SMEM((bb, r), jnp.int32),
            pltpu.VMEM((bb, r, dp), rows.dtype),
            pltpu.VMEM((bb, r, ap), jnp.uint32),
            pltpu.SemaphoreType.DMA((2, bb)),
            pltpu.SemaphoreType.DMA((bb,)),
            pltpu.SemaphoreType.DMA((bb,)),
        ],
        interpret=interpret,
    )(jnp.asarray(rem, jnp.int32).reshape(1), neighbors, rows, aux, *inputs)

    idx, exp, vbit = unpack_payload(ocp[:b])
    from repro.core.state import SearchState

    return SearchState(
        cand_dist=ocd[:b], cand_idx=idx, cand_exp=exp, cand_valid=vbit,
        res_dist=ordd[:b], res_idx=ori[:b],
        visited=_bitset_words(ovis)[:b, :nw],
        cnt=octr[:b, 0], n_inspected=octr[:b, 1],
        n_valid_visited=octr[:b, 2], n_clause_valid=oncl[:b],
        n_pop_valid=octr[:b, 3], q_err_sum=oqerr[:b, 0], hops=octr[:b, 4],
        active=octr[:b, 7].astype(bool), d_start=state.d_start,
        conv_cnt=octr[:b, 5], res_full_cnt=octr[:b, 6])


def _bitset_rows(visited, nrow):
    """[B, nw] u32 bitset -> the kernel's [nrow, B, 128] int32 layout."""
    b = visited.shape[0]
    words = _pad_cols(visited, nrow * _LANES)
    words = jax.lax.bitcast_convert_type(words, jnp.int32)
    return words.reshape(b, nrow, _LANES).transpose(1, 0, 2)


def _bitset_words(rows):
    """Inverse of `_bitset_rows` (word-padded to nrow·128)."""
    nrow, b, _ = rows.shape
    words = rows.transpose(1, 0, 2).reshape(b, nrow * _LANES)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def _row(shape):
    return pl.BlockSpec(shape, lambda i: (i,) + (0,) * (len(shape) - 1))
