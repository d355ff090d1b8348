"""Pallas TPU kernel: fused traversal step (filter program + distance + merge).

One lockstep traversal step turns R gathered neighbor vectors *and their
attribute words* into updated candidate-queue and result-set buffers.
Executed as separate ops that is: a clause-program evaluation over
[B,S,R(,W|V)] intermediates, a [B,R] distance batch, a [B,M+R] argsort, a
[B,K+R] argsort, and six take_along_axis gathers — every intermediate
bouncing through HBM.

This kernel fuses the whole step for a block of lanes in one VMEM pass:

  1. compiled filter program (filters/compile.py): per clause slot all four
     primitives (contain / equal / range / any-of) over the gathered label
     words + numeric channels, selected by kind tag, combined through the
     DNF term table — statically unrolled over the S slots / T terms of the
     program shape, vectorized over lanes × neighbors
  2. squared-L2 distances q·x via the MXU (dot_general, f32 accumulate)
  3. mode-dependent mask (post: every first-visit scores; pre: valid only);
     masked entries emit +inf
  4. candidate-queue merge: bitonic top-M over width next_pow2(M+R)
  5. result-set merge: bitonic top-K over width next_pow2(K+R)

Besides the merged buffers it emits the validity mask and per-clause hit
counters (for the estimator's clause-wise probe selectivities) — the only
predicate state that leaves VMEM.

Payloads ride as packed int32 (node id + expanded/valid flags, see
kernels.topk.pack_payload) so the sorting network permutes one value lane.
Wired in as `SearchConfig(backend="pallas")` via repro.core.backends.

VMEM per block ≈ bB·(R·(d+W+V) + S·W + 2·next_pow2(M+R) + 2·next_pow2(K+R))·4 B;
for bB=8, R=64, d=1024, M=512, S=8, W=4 that's ~2.3 MB — comfortable on a
16 MB core.

Compressed-domain variants (repro.quant): two sibling kernels swap only
the distance block (step 2) and share the program-eval + merge tail via
`_program_and_merge` —

  int8  gathered [bB, R, d] int8 codes · quantized query factor, an
        int8×int8 → int32 MXU dot (exact integer arithmetic); the float32
        vector block never enters VMEM — ~4× less per-NDC bandwidth.
  pq    per-query inner-product LUT rows [bB, S·L, Kc] f32 stay
        VMEM-resident (≈ bB·S·L·Kc·4 B — 1.5 MB at bB=8, S·L=48, Kc=256)
        and each code row costs S·L lookups, lowered as one-hot × LUT-row
        contractions per slot, bit-equal to the gather; the distance
        assembles as ‖q‖² + ‖x̂‖² − 2·Σ lookups.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.filters.compile import (
    CLAUSE_FEATURE_SLOTS,
    clause_counts,
    eval_program_gathered,
)
from repro.kernels.distance import HIGHEST, sqdist_bdrd
from repro.kernels.topk import (bitonic_merge_sorted, merge_topm,
                                network_width, sort_kv_f32)

INF = float("inf")


def _all_last(x):
    """jnp.all over the last axis as an int32 reduction (Mosaic reduces
    no i1 vectors)."""
    return jnp.min(x.astype(jnp.int32), axis=-1) > 0


def _any_last(x):
    return jnp.max(x.astype(jnp.int32), axis=-1) > 0


def _program_valid_kernel(kinds, masks, lo, hi, vattr, neg, term, active,
                          term_active, labels, values):
    """In-kernel clause-program evaluation, unrolled over static S and T.

    labels [bb, R, W] u32, values [bb, R, V] f32; program leaves [bb, S, ...].
    Returns (valid [bb, R] bool, sats: S × [bb, R] bool). Same formulas as
    `filters.compile.eval_program_gathered`, restructured as per-slot loops
    (static python unrolling) so Mosaic sees only 2-D elementwise work.
    """
    s = kinds.shape[1]
    t = term_active.shape[1]
    v_chan = values.shape[2]
    lits, sats = [], []
    for si in range(s):
        msk = masks[:, si, :][:, None, :]                     # [bb,1,W]
        inter = jnp.bitwise_and(labels, msk)
        c_contain = _all_last(inter == msk)                   # [bb,R]
        c_equal = _all_last(labels == msk)
        c_in = _any_last(inter != 0)
        vs = values[:, :, 0]
        for ch in range(1, v_chan):                           # channel select
            vs = jnp.where(vattr[:, si][:, None] == ch, values[:, :, ch], vs)
        c_range = (vs >= lo[:, si][:, None]) & (vs <= hi[:, si][:, None])
        kk = kinds[:, si][:, None]
        prim = (((kk == 0) & c_contain) | ((kk == 1) & c_equal)
                | ((kk == 2) & c_range)
                | ((kk != 0) & (kk != 1) & (kk != 2) & c_in))
        lit = jnp.logical_xor(prim, neg[:, si][:, None])
        act = active[:, si][:, None]
        sats.append(lit & act)
        lits.append(lit | ~act)                               # inactive: no veto
    valid = jnp.zeros(labels.shape[:2], bool)
    for ti in range(t):
        ok = term_active[:, ti][:, None]
        for si in range(s):
            member = (term[:, si] == ti) & active[:, si]
            ok = ok & (lits[si] | ~member[:, None])
        valid = valid | ok
    return valid, sats


def _merge_core(d, nb, is_new, kinds, masks, lo, hi, vattr, neg, term_pack,
                tact, labels, values, cd, cp, rd, ri,
                *, m, k, wq, wr, pre, n_clause):
    """Value-level shared tail: filter program, masking, both bitonic merges.

    Pure function of the step's values — no refs — so it is callable both
    from the single-step kernels below (via the ref-plumbing wrapper
    `_program_and_merge`) and per step from the persistent multi-step
    kernel (kernels.persistent_step), whose state lives in VMEM scratch
    across steps. Returns (cand_dist, cand_pay, res_dist, res_idx,
    valid [bB, R] bool, clause_counts [bB, C] i32).
    """
    # ---- compiled filter program on the gathered attribute words ----
    # (kinds == -1 never matches a primitive tag; the active mask rides in
    # term_pack's sign bit — see fused_step packing below)
    active = term_pack >= 0
    term = jnp.maximum(term_pack, 0)
    # flags cross the kernel boundary as int32 (Mosaic loads no i1 refs)
    is_new, neg, tact = is_new != 0, neg != 0, tact != 0
    pvalid, sats = _program_valid_kernel(
        kinds, masks, lo, hi, vattr, neg, term, active, tact, labels, values)
    valid = pvalid & is_new
    dmask = valid if pre else is_new

    counts = []
    for c in range(n_clause):
        if c < len(sats):
            counts.append((sats[c] & is_new).sum(axis=1).astype(jnp.int32))
        else:
            counts.append(jnp.zeros(nb.shape[:1], jnp.int32))
    occ = jnp.stack(counts, axis=1)

    # ---- mask: non-scored neighbors never enter the buffers ----
    dd = jnp.where(dmask, d, INF)
    # pack_payload(nb, expanded=False, valid) inline; dmask ⇒ nb >= 0
    new_pay = jnp.where(dmask, nb | (valid.astype(jnp.int32) << 30), -1)

    # ---- candidate-queue merge (bitonic top-M) ----
    ocd, ocp = merge_topm(cd, cp, dd, new_pay, m, wq)

    # ---- result-set merge (valid only, bitonic top-K) ----
    res_in = jnp.where(valid & dmask, dd, INF)
    res_pay = jnp.where(valid & dmask, nb, -1)
    ordd, ori = merge_topm(rd, ri, res_in, res_pay, k, wr)
    return ocd, ocp, ordd, ori, valid, occ


def _program_and_merge(d, nb, is_new,
                       kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref,
                       neg_ref, term_ref, tact_ref, lab_ref, val_ref,
                       cd_ref, cp_ref, rd_ref, ri_ref,
                       ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref, occ_ref,
                       *, m, k, wq, wr, pre, n_clause):
    """Ref-plumbing wrapper over `_merge_core` for the single-step kernels.

    Every fused-step kernel variant (float32 MXU distances, int8 ADC, PQ
    ADC) computes its [bB, R] distance block `d` and delegates the rest
    here, so the program evaluation and merge dataflow can never diverge
    between precision modes (or between the single-step and persistent
    kernels, which share `_merge_core`).
    """
    ocd, ocp, ordd, ori, valid, occ = _merge_core(
        d, nb, is_new,
        kinds_ref[...], masks_ref[...], lo_ref[...], hi_ref[...],
        vattr_ref[...], neg_ref[...], term_ref[...], tact_ref[...],
        lab_ref[...], val_ref[...],
        cd_ref[...], cp_ref[...], rd_ref[...], ri_ref[...],
        m=m, k=k, wq=wq, wr=wr, pre=pre, n_clause=n_clause)
    ov_ref[...] = valid.astype(jnp.int32)
    occ_ref[...] = occ
    ocd_ref[...] = ocd
    ocp_ref[...] = ocp
    ord_ref[...] = ordd
    ori_ref[...] = ori


def _fused_step_kernel(q_ref, x_ref, nb_ref, new_ref, lab_ref, val_ref,
                       kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref,
                       neg_ref, term_ref, tact_ref,
                       cd_ref, cp_ref, rd_ref, ri_ref,
                       ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref, occ_ref,
                       *, m, k, wq, wr, pre, n_clause):
    q = q_ref[...].astype(jnp.float32)          # [bB, d]
    x = x_ref[...].astype(jnp.float32)          # [bB, R, d]

    # ---- distances (per-lane MXU contraction) ----
    qn = jnp.sum(q * q, axis=-1)[:, None]
    xn = jnp.sum(x * x, axis=-1)
    qx = jax.lax.dot_general(
        q[:, None, :], x,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )[:, 0, :]
    d = jnp.maximum(qn + xn - 2.0 * qx, 0.0)

    _program_and_merge(
        d, nb_ref[...], new_ref[...],
        kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref, neg_ref, term_ref,
        tact_ref, lab_ref, val_ref, cd_ref, cp_ref, rd_ref, ri_ref,
        ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref, occ_ref,
        m=m, k=k, wq=wq, wr=wr, pre=pre, n_clause=n_clause)


def _fused_step_int8_kernel(codes_ref, xn_ref, qq_ref, sq_ref, qn_ref,
                            nb_ref, new_ref, lab_ref, val_ref,
                            kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref,
                            neg_ref, term_ref, tact_ref,
                            cd_ref, cp_ref, rd_ref, ri_ref,
                            ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref,
                            occ_ref, *, m, k, wq, wr, pre, n_clause):
    """int8 ADC variant: the distance block is an int8×int8 → int32 MXU dot
    over the gathered codes — the index's float vectors never enter VMEM.

    codes [bB, R, d] i8, xn [bB, R] f32 (per-node ‖scale⊙c‖²),
    qq [bB, d] i8 (quantized query factor), sq/qn [bB, 1] f32.
    Same arithmetic as quant.codecs.adc_int8: the integer dot is exact, so
    kernel vs host agreement is bitwise up to the identical float tail.
    """
    qq = qq_ref[...]                             # [bB, d] i8
    codes = codes_ref[...]                       # [bB, R, d] i8
    dot = jax.lax.dot_general(
        qq[:, None, :], codes,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.int32,
    )[:, 0, :]                                   # [bB, R] i32
    d = jnp.maximum(
        qn_ref[...] + xn_ref[...] - 2.0 * sq_ref[...] * dot.astype(jnp.float32),
        0.0)

    _program_and_merge(
        d, nb_ref[...], new_ref[...],
        kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref, neg_ref, term_ref,
        tact_ref, lab_ref, val_ref, cd_ref, cp_ref, rd_ref, ri_ref,
        ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref, occ_ref,
        m=m, k=k, wq=wq, wr=wr, pre=pre, n_clause=n_clause)


def _fused_step_pq_kernel(codes_ref, lut_ref, xn_ref, qn_ref,
                          nb_ref, new_ref, lab_ref, val_ref,
                          kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref,
                          neg_ref, term_ref, tact_ref,
                          cd_ref, cp_ref, rd_ref, ri_ref,
                          ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref,
                          occ_ref, *, m, k, wq, wr, pre, n_clause):
    """PQ ADC variant: per-query inner-product LUT rows stay resident in
    VMEM ([S·L, bB, Kc] f32 ≈ bB·S·L·Kc·4 B — 1.5 MB at bB=8, S·L=48,
    Kc=256) and each gathered code row costs S·L table lookups, realized
    as one-hot × LUT-row MXU contractions, one slot per trip of a rolled
    loop (an unrolled loop over 96 slots takes Mosaic minutes to compile):
    exactly one unit weight per row, so the contraction equals the gather
    bit-for-bit while avoiding per-element dynamic indexing in the kernel.
    Codes and LUT arrive slot-major ([S·L, bB, ·]) so each trip reads its
    slot with a leading-axis index. The distance assembles as
    ‖q‖² + ‖x̂‖² − 2·Σ lookups (xn = gathered per-node ‖x̂‖², qn = per-lane
    ‖q‖²).
    """
    s, bb, r = codes_ref.shape
    kc = lut_ref.shape[2]
    centroid = jax.lax.broadcasted_iota(jnp.int32, (bb, kc, r), 1)

    def slot(si, ip):
        onehot = (codes_ref[si][:, None, :] == centroid).astype(jnp.float32)
        return ip + jax.lax.dot_general(
            lut_ref[si][:, None, :], onehot,             # [bB,1,Kc]·[bB,Kc,R]
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )[:, 0, :]

    ip = jax.lax.fori_loop(0, s, slot, jnp.zeros((bb, r), jnp.float32))
    d = jnp.maximum(qn_ref[...] + xn_ref[...] - 2.0 * ip, 0.0)

    _program_and_merge(
        d, nb_ref[...], new_ref[...],
        kinds_ref, masks_ref, lo_ref, hi_ref, vattr_ref, neg_ref, term_ref,
        tact_ref, lab_ref, val_ref, cd_ref, cp_ref, rd_ref, ri_ref,
        ocd_ref, ocp_ref, ord_ref, ori_ref, ov_ref, occ_ref,
        m=m, k=k, wq=wq, wr=wr, pre=pre, n_clause=n_clause)


def fused_step_host(q, x, nb, is_new, prog, labels_g, values_g,
                    cand_dist, cand_pay, res_dist, res_idx, *, pre: bool,
                    quant=None, precision: str = "float32"):
    """Host-path (non-TPU) equivalent of the fused kernel.

    Same dataflow — program evaluation, distances, mask, queue merge,
    result merge in one traced region — but the program evaluation is the
    *shared* `filters.compile.eval_program_gathered` (so dense/pallas
    parity is exact by construction) and the unrolled bitonic networks are
    replaced by the log-depth sorted-merge of kernels.topk (XLA:CPU
    compiles the full network pathologically; see the note there).
    Distance arithmetic matches the dense backend expression exactly —
    compressed mode included: both call `quant.codecs.quant_dist` — so
    dense/pallas parity is bitwise on CPU up to distance ties.
    """
    m, k = cand_dist.shape[1], res_dist.shape[1]
    pvalid, clause_sat = eval_program_gathered(prog, labels_g, values_g)
    valid = pvalid & is_new
    cadd = clause_counts(clause_sat, is_new)
    dist_mask = valid if pre else is_new

    if quant is None:
        d_raw = sqdist_bdrd(q, x)
    else:
        from repro.quant.codecs import quant_dist

        d_raw = quant_dist(precision, quant)
    dd = jnp.where(dist_mask, d_raw, INF)
    new_pay = jnp.where(dist_mask, nb | (valid.astype(jnp.int32) << 30), -1)

    ns_d, ns_p = sort_kv_f32(dd, new_pay)
    ocd, ocp = bitonic_merge_sorted(cand_dist.astype(jnp.float32), cand_pay,
                                    ns_d, ns_p, m)

    res_in = jnp.where(valid & dist_mask, dd, INF)
    res_pay = jnp.where(valid & dist_mask, nb, -1)
    rs_d, rs_p = sort_kv_f32(res_in, res_pay)
    ordd, ori = bitonic_merge_sorted(res_dist.astype(jnp.float32), res_idx,
                                     rs_d, rs_p, k)
    return ocd, ocp, ordd, ori, valid, cadd


@functools.partial(jax.jit,
                   static_argnames=("pre", "block_b", "interpret", "precision"))
def fused_step(q, x, nb, is_new, prog, labels_g, values_g, cand_dist,
               cand_pay, res_dist, res_idx, *, pre: bool = False,
               block_b: int = 8, interpret: bool = False,
               quant=None, precision: str = "float32"):
    """One fused traversal step over a batch of lanes.

    q [B,d], x [B,R,d], nb [B,R] i32, is_new [B,R] bool,
    prog FilterProgram (leaves [B,S,...]), labels_g [B,R,W] u32,
    values_g [B,R,V] f32,
    cand_dist [B,M] f32 + cand_pay [B,M] i32 (packed, sorted ascending),
    res_dist [B,K] f32 + res_idx [B,K] i32 (sorted ascending)
    -> (cand_dist, cand_pay, res_dist, res_idx, valid [B,R] bool,
        clause_add [B,C] i32) merged, sorted, best-M/K.

    Compressed mode: precision "int8" | "pq" with `quant` a QuantGather
    (per-query ADC prep + the step's gathered codes/norms); `x` may be
    None — the distance block runs on the codes (int8 MXU dot / in-VMEM
    LUT rows), the float vectors never enter the kernel.
    """
    b, dm = q.shape
    r = nb.shape[1]
    m = cand_dist.shape[1]
    k = res_dist.shape[1]
    s = prog.kinds.shape[1]
    t = prog.term_active.shape[1]
    w = labels_g.shape[2]
    v = values_g.shape[2]
    wq = network_width(m + r, interpret)
    wr = network_width(k + r, interpret)

    # slot activity riding in the term id's sign bit keeps the ref count
    # down (term >= 0 ⇔ active); neg packs as int32 for the same reason
    term_pack = jnp.where(prog.active, prog.term, -1).astype(jnp.int32)

    # Interpret mode simulates grid steps sequentially; a single full-batch
    # block keeps the simulated step vectorized. On TPU the block size is a
    # VMEM knob and stays small.
    bb = min(b, 1024) if interpret else min(block_b, b)
    pad = (-b) % bb

    def pad0(a, fill=0):
        if pad == 0:
            return a
        widths = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
        return jnp.pad(a, widths, constant_values=fill)

    q = pad0(q)
    if x is not None:
        x = pad0(x)
    nb = pad0(nb, -1)
    is_new = pad0(is_new).astype(jnp.int32)
    labels_g = pad0(labels_g)
    values_g = pad0(values_g)
    kinds = pad0(prog.kinds)
    masks = pad0(prog.masks)
    lo = pad0(prog.lo)
    hi = pad0(prog.hi)
    vattr = pad0(prog.vattr)
    neg = pad0(prog.neg).astype(jnp.int32)
    term_pack = pad0(term_pack, -1)
    tact = pad0(prog.term_active).astype(jnp.int32)
    cand_dist = pad0(cand_dist, jnp.inf)
    cand_pay = pad0(cand_pay, -1)
    res_dist = pad0(res_dist, jnp.inf)
    res_idx = pad0(res_idx, -1)
    bp = q.shape[0]

    def row(shape):
        return pl.BlockSpec(shape, lambda i: (i,) + (0,) * (len(shape) - 1))

    # variant head: (kernel fn, leading inputs + specs). The shared tail
    # (attributes, program, buffers) is identical across precisions.
    if precision == "float32":
        head_kern = _fused_step_kernel
        head_in = [q.astype(jnp.float32), x]
        head_specs = [row((bb, dm)), row((bb, r, dm))]
    elif precision == "int8":
        codes = pad0(quant.codes.astype(jnp.int8))
        xn = pad0(quant.norms)
        qq = pad0(quant.prep.qq)
        sq = pad0(quant.prep.sq[:, None])
        qn = pad0(quant.prep.qn[:, None])
        dq = codes.shape[2]
        head_kern = _fused_step_int8_kernel
        head_in = [codes, xn, qq, sq, qn]
        head_specs = [row((bb, r, dq)), row((bb, r)), row((bb, dq)),
                      row((bb, 1)), row((bb, 1))]
    elif precision == "pq":
        # slot-major: the kernel's loop reads one slot per trip
        codes = pad0(quant.codes.astype(jnp.int32)).transpose(2, 0, 1)
        lut = pad0(quant.prep.lut).transpose(1, 0, 2)
        xn = pad0(quant.norms)
        qn = pad0(quant.prep.qn[:, None])
        sp, kc = lut.shape[0], lut.shape[2]
        head_kern = _fused_step_pq_kernel
        head_in = [codes, lut, xn, qn]
        head_specs = [pl.BlockSpec((sp, bb, r), lambda i: (0, i, 0)),
                      pl.BlockSpec((sp, bb, kc), lambda i: (0, i, 0)),
                      row((bb, r)), row((bb, 1))]
    else:
        raise ValueError(f"unknown precision {precision!r}")

    kern = functools.partial(head_kern, m=m, k=k, wq=wq, wr=wr,
                             pre=pre, n_clause=CLAUSE_FEATURE_SLOTS)
    ocd, ocp, ordd, ori, ov, occ = pl.pallas_call(
        kern,
        grid=(bp // bb,),
        in_specs=head_specs + [
            row((bb, r)), row((bb, r)),
            row((bb, r, w)), row((bb, r, v)),
            row((bb, s)), row((bb, s, w)), row((bb, s)), row((bb, s)),
            row((bb, s)), row((bb, s)), row((bb, s)), row((bb, t)),
            row((bb, m)), row((bb, m)), row((bb, k)), row((bb, k)),
        ],
        out_specs=[
            row((bb, m)), row((bb, m)), row((bb, k)), row((bb, k)),
            row((bb, r)), row((bb, CLAUSE_FEATURE_SLOTS)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, m), jnp.float32),
            jax.ShapeDtypeStruct((bp, m), jnp.int32),
            jax.ShapeDtypeStruct((bp, k), jnp.float32),
            jax.ShapeDtypeStruct((bp, k), jnp.int32),
            jax.ShapeDtypeStruct((bp, r), jnp.int32),
            jax.ShapeDtypeStruct((bp, CLAUSE_FEATURE_SLOTS), jnp.int32),
        ],
        interpret=interpret,
    )(*head_in, nb, is_new, labels_g, values_g,
      kinds, masks, lo, hi, vattr, neg, term_pack, tact,
      cand_dist.astype(jnp.float32), cand_pay,
      res_dist.astype(jnp.float32), res_idx)
    return (ocd[:b], ocp[:b], ordd[:b], ori[:b], ov[:b].astype(bool),
            occ[:b])
