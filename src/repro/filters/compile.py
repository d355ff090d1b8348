"""Filter-program compiler: expression batches → fixed-shape predicate programs.

A `FilterProgram` is the jit-traceable lowering of a *batch* of filter
expressions with arbitrary (heterogeneous) boolean structure. Per query it
holds S padded clause slots — one per DNF literal — and a flattened
combiner table assigning each slot to one of T conjunctive terms:

  kinds   [B, S]    i32   CLAUSE_CONTAIN | EQUAL | RANGE | IN
  masks   [B, S, W] u32   packed label mask (label clauses)
  lo/hi   [B, S]    f32   closed interval (range clauses)
  vattr   [B, S]    i32   numeric-attribute channel (range clauses)
  neg     [B, S]    bool  literal negation
  term    [B, S]    i32   owning DNF term
  active  [B, S]    bool  slot in use (padding slots are neutral)
  term_active [B, T] bool term in use (a query is valid iff any active
                          term has no failing literal)

Evaluation (`eval_program_gathered`) computes every primitive for every
slot and selects by kind tag — one vectorized pass, no Python branching —
then combines through the term table. A query batch mixing `And(a, b)`,
`Or(a, Not(b))`, and bare single predicates therefore shares one traced
computation, which is what lets the serving layer batch requests of
different boolean shape into the same lanes.

The per-slot satisfaction mask is also returned: the traversal accumulates
per-clause valid counters from it, giving the cost estimator clause-wise
probe selectivities (the paper's "attribute distribution" signal,
generalized from one ρ to one ρ per clause).

Inert encodings (used for lane padding): a program row with no active term
evaluates to False everywhere; `pad_program` produces such rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.filters.expr import (
    CLAUSE_CONTAIN,
    CLAUSE_EQUAL,
    CLAUSE_IN,
    CLAUSE_RANGE,
    Expr,
    canonical_dnf,
    pack_mask,
)
from repro.filters.predicates import (
    PRED_CONTAIN,
    PRED_EQUAL,
    PRED_RANGE,
    FilterSpec,
)

# Fixed number of clause slots tracked by the per-clause probe-selectivity
# counters (SearchState.n_clause_valid / the rho_clause_* features). A
# program may have more slots; counters cover the first few canonical ones.
CLAUSE_FEATURE_SLOTS = 4

# Hard ceiling on compiled slots — masks ride in uint32 words and the
# per-clause counter path packs slot bits into an int32 lane on TPU.
MAX_SLOTS = 32

# Elements of one step's [B, S, rows, W] intermediates in the full-store
# bitmap pass. Each step is a dozen device ops, and a profiler trace
# records every one: a narrow program walks 2^20 rows in a few steps,
# a wide one in more, smaller steps that stay bounded in memory.
BITMAP_STEP_ELEMENTS = 1 << 22


class FilterProgram(NamedTuple):
    kinds: jax.Array        # [B, S] i32
    masks: jax.Array        # [B, S, W] u32
    lo: jax.Array           # [B, S] f32
    hi: jax.Array           # [B, S] f32
    vattr: jax.Array        # [B, S] i32
    neg: jax.Array          # [B, S] bool
    term: jax.Array         # [B, S] i32
    active: jax.Array       # [B, S] bool
    term_active: jax.Array  # [B, T] bool

    @property
    def batch(self) -> int:
        return int(self.kinds.shape[0])

    @property
    def n_slots(self) -> int:
        return int(self.kinds.shape[1])

    @property
    def n_terms(self) -> int:
        return int(self.term_active.shape[1])

    @property
    def n_words(self) -> int:
        return int(self.masks.shape[2])

    def slice(self, sl) -> "FilterProgram":
        return FilterProgram(*(np.asarray(a)[sl] for a in self))


def _leaf_slot(leaf: Expr, n_words: int, n_values: int):
    """(kind, mask, lo, hi, vattr) arrays for one literal's leaf."""
    from repro.filters.expr import Contain, Equal, In, Range

    if isinstance(leaf, Range):
        if leaf.attr >= n_values:
            raise ValueError(f"value channel {leaf.attr} outside [0,{n_values})")
        return (CLAUSE_RANGE, np.zeros(n_words, np.uint32),
                np.float32(leaf.lo), np.float32(leaf.hi), leaf.attr)
    kind = {Contain: CLAUSE_CONTAIN, Equal: CLAUSE_EQUAL, In: CLAUSE_IN}[type(leaf)]
    return (kind, pack_mask(leaf.labels, n_words), np.float32(0.0),
            np.float32(0.0), 0)


def compile_query(expr: Expr, n_words: int, n_values: int = 1):
    """One expression → per-query program rows (numpy, batch dim of 1).

    Slot order is the canonical DNF order, so equivalent expressions
    compile to identical rows (the serving cache and the feature extractor
    both rely on this determinism).
    """
    dnf = canonical_dnf(expr)
    n_slots = sum(len(t) for t in dnf)
    if n_slots > MAX_SLOTS:
        raise ValueError(f"filter compiles to {n_slots} clauses "
                         f"(max {MAX_SLOTS}); simplify the expression")
    s = max(1, n_slots)
    t = max(1, len(dnf))
    kinds = np.zeros((1, s), np.int32)
    masks = np.zeros((1, s, n_words), np.uint32)
    lo = np.zeros((1, s), np.float32)
    hi = np.zeros((1, s), np.float32)
    vattr = np.zeros((1, s), np.int32)
    neg = np.zeros((1, s), bool)
    term = np.zeros((1, s), np.int32)
    active = np.zeros((1, s), bool)
    term_active = np.zeros((1, t), bool)
    i = 0
    for ti, lits in enumerate(dnf):
        term_active[0, ti] = True
        for leaf, negated in lits:
            kinds[0, i], masks[0, i], lo[0, i], hi[0, i], vattr[0, i] = (
                _leaf_slot(leaf, n_words, n_values))
            neg[0, i] = negated
            term[0, i] = ti
            active[0, i] = True
            i += 1
    return FilterProgram(kinds, masks, lo, hi, vattr, neg, term, active,
                         term_active)


def pad_program(prog: FilterProgram, n_slots: int | None = None,
                n_terms: int | None = None, batch: int | None = None,
                ) -> FilterProgram:
    """Grow a program to (batch, n_slots, n_terms) with inert padding.

    Padding slots are inactive (never fail a term); padding terms are
    inactive (never validate a node); padding *rows* have no active term
    and therefore match nothing — exactly the serving layer's inert-lane
    contract (they also carry a 0 NDC budget).
    """
    b0, s0 = prog.kinds.shape
    t0 = prog.term_active.shape[1]
    s = s0 if n_slots is None else max(n_slots, s0)
    t = t0 if n_terms is None else max(n_terms, t0)
    b = b0 if batch is None else max(batch, b0)

    def grow(a, shape):
        a = np.asarray(a)
        out = np.zeros(shape, a.dtype)
        out[tuple(slice(0, d) for d in a.shape)] = a
        return out

    w = prog.masks.shape[2]
    return FilterProgram(
        kinds=grow(prog.kinds, (b, s)),
        masks=grow(prog.masks, (b, s, w)),
        lo=grow(prog.lo, (b, s)),
        hi=grow(prog.hi, (b, s)),
        vattr=grow(prog.vattr, (b, s)),
        neg=grow(prog.neg, (b, s)),
        term=grow(prog.term, (b, s)),
        active=grow(prog.active, (b, s)),
        term_active=grow(prog.term_active, (b, t)),
    )


def stack_programs(progs: Sequence[FilterProgram], n_slots: int | None = None,
                   n_terms: int | None = None, pad_to: int | None = None,
                   ) -> FilterProgram:
    """Stack per-query programs (batch 1 each) into one padded batch.

    Slot/term counts pad to the max across the batch (or the explicit
    minimums); `pad_to` appends inert match-nothing rows up to a lane
    width.
    """
    s = max([p.kinds.shape[1] for p in progs] + [n_slots or 1])
    t = max([p.term_active.shape[1] for p in progs] + [n_terms or 1])
    rows = [pad_program(p, s, t) for p in progs]
    cat = FilterProgram(*(np.concatenate([np.asarray(r[i]) for r in rows])
                          for i in range(len(rows[0]))))
    if pad_to is not None and pad_to > cat.batch:
        cat = pad_program(cat, batch=pad_to)
    return cat


def compile_filters(exprs: Sequence[Expr], n_words: int, n_values: int = 1,
                    n_slots: int | None = None, n_terms: int | None = None,
                    ) -> FilterProgram:
    """Compile a batch of (heterogeneous) expressions into one program."""
    return stack_programs([compile_query(e, n_words, n_values) for e in exprs],
                          n_slots, n_terms)


def compile_spec(spec, n_words: int, n_values: int = 1) -> FilterProgram:
    """Vectorized single-clause lowering of a legacy `FilterSpec` batch.

    Equivalent to `compile_filters(spec.to_expr(), ...)` but builds the
    arrays directly — the legacy entry points (benchmarks, training loops)
    call this per engine.search and should not pay a per-query Python loop.
    """
    b = spec.batch
    return FilterProgram(
        kinds=np.full((b, 1), _SPEC_KIND[spec.kind], np.int32),
        masks=(np.zeros((b, 1, n_words), np.uint32) if spec.kind == PRED_RANGE
               else np.asarray(spec.label_masks, np.uint32)[:, None, :]),
        lo=(np.asarray(spec.range_lo, np.float32)[:, None]
            if spec.kind == PRED_RANGE else np.zeros((b, 1), np.float32)),
        hi=(np.asarray(spec.range_hi, np.float32)[:, None]
            if spec.kind == PRED_RANGE else np.zeros((b, 1), np.float32)),
        vattr=np.zeros((b, 1), np.int32),
        neg=np.zeros((b, 1), bool),
        term=np.zeros((b, 1), np.int32),
        active=np.ones((b, 1), bool),
        term_active=np.ones((b, 1), bool),
    )


def as_program(filt, n_words: int, n_values: int = 1) -> FilterProgram:
    """Accept a FilterProgram | FilterSpec | Expr | sequence of Expr."""
    if isinstance(filt, FilterProgram):
        return filt
    if isinstance(filt, FilterSpec):
        return compile_spec(filt, n_words, n_values)
    if isinstance(filt, Expr):
        return compile_query(filt, n_words, n_values)
    return compile_filters(list(filt), n_words, n_values)


# ----------------------------------------------------------- evaluation ----
def eval_program_gathered(prog: FilterProgram, labels_g, values_g):
    """Evaluate the program on gathered per-candidate attributes.

    prog      leaves [B, S, ...] (device arrays)
    labels_g  [B, R, W] uint32 — candidate label masks
    values_g  [B, R, V] float32 — candidate numeric attributes
    returns   (valid [B, R] bool, clause_sat [B, S, R] bool)

    `valid` is the program's boolean output; `clause_sat` is per-slot
    literal satisfaction (active slots only) feeding the clause-wise
    selectivity counters. All four primitives are evaluated for every slot
    and selected by kind tag — branch-free and batch-uniform.
    """
    m = prog.masks[:, :, None, :]                       # [B,S,1,W]
    lg = labels_g[:, None, :, :]                        # [B,1,R,W]
    inter = jnp.bitwise_and(lg, m)
    c_contain = jnp.all(inter == m, axis=-1)            # [B,S,R]
    c_equal = jnp.all(lg == m, axis=-1)
    c_in = jnp.any(inter != 0, axis=-1)
    vat = jnp.clip(prog.vattr, 0, values_g.shape[-1] - 1)
    vsel = jnp.take_along_axis(
        values_g[:, None, :, :],                        # [B,1,R,V]
        vat[:, :, None, None], axis=-1)[..., 0]         # [B,S,R]
    c_range = (vsel >= prog.lo[:, :, None]) & (vsel <= prog.hi[:, :, None])

    k = prog.kinds[:, :, None]
    prim = jnp.where(
        k == CLAUSE_CONTAIN, c_contain,
        jnp.where(k == CLAUSE_EQUAL, c_equal,
                  jnp.where(k == CLAUSE_RANGE, c_range, c_in)))
    act = prog.active[:, :, None]
    lit = jnp.logical_xor(prim, prog.neg[:, :, None])
    clause_sat = lit & act

    # combiner: a term fails iff any of its literals fails; valid iff any
    # active term survives. One [B,S,T]x[B,S,R] contraction, no branching.
    fail = (~lit) & act
    t = prog.term_active.shape[1]
    member = (prog.term[:, :, None] == jnp.arange(t, dtype=prog.term.dtype)[
        None, None, :]) & prog.active[:, :, None]       # [B,S,T]
    n_fail = jnp.einsum("bst,bsr->btr", member.astype(jnp.int32),
                        fail.astype(jnp.int32))
    term_ok = prog.term_active[:, :, None] & (n_fail == 0)
    return jnp.any(term_ok, axis=1), clause_sat


@functools.partial(jax.jit, static_argnames=("chunk",))
def _matrix_pass(prog: FilterProgram, labels, values, chunk: int):
    """The whole full-store evaluation as one program: (valid [B, N],
    counts [B], clause counts [B, CLAUSE_FEATURE_SLOTS]).

    N pads up to a chunk multiple and `lax.map` walks the [N/chunk, chunk,
    W] views, so the [B, S, chunk, W] intermediates stay bounded. Pad rows
    are masked out of `valid` and of every count by their row id: a
    negated clause would match a zero label word."""
    b = prog.kinds.shape[0]
    n = labels.shape[0]
    n_chunks = -(-n // chunk)
    pad = n_chunks * chunk - n
    lab = jnp.pad(labels, ((0, pad), (0, 0))).reshape(n_chunks, chunk, -1)
    val = jnp.pad(values, ((0, pad), (0, 0))).reshape(n_chunks, chunk, -1)

    def one(xs):
        start, lc, vc = xs
        live = jnp.broadcast_to(start + jnp.arange(chunk) < n, (b, chunk))
        lg = jnp.broadcast_to(lc[None], (b,) + lc.shape)
        vg = jnp.broadcast_to(vc[None], (b,) + vc.shape)
        valid, csat = eval_program_gathered(prog, lg, vg)
        valid = valid & live
        return (valid, valid.sum(axis=1, dtype=jnp.int32),
                clause_counts(csat, live))

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    valid, counts, cc = jax.lax.map(one, (starts, lab, val))
    valid = jnp.moveaxis(valid, 0, 1).reshape(b, n_chunks * chunk)[:, :n]
    return valid, counts.sum(axis=0), cc.sum(axis=0)


def bitmap_chunk(prog: FilterProgram, n: int) -> int:
    """Rows per step of the bitmap pass of `prog` over n rows: the power
    of two (at least 1024) that keeps a step near BITMAP_STEP_ELEMENTS,
    and no more than n, so a small store is one step with no pad rows."""
    b, s, w = prog.masks.shape
    rows = max(1024, BITMAP_STEP_ELEMENTS // (b * s * w))
    return min(n, 1 << (rows.bit_length() - 1))


def eval_program_matrix(prog: FilterProgram, labels, values,
                        chunk: int | None = None):
    """Evaluate a program batch against the *full* attribute store.

    prog leaves [B, S, ...]; labels [N, W] u32; values [N, V] f32 →
    (valid [B, N] bool on the device, counts [B] i64 and clause_frac
    [B, CLAUSE_FEATURE_SLOTS] f32 on the host).

    This is the scan plan's candidate-bitmap compiler and the planner's
    exact per-query selectivity source: `counts / N` is σ_q with no
    sampling error, and `clause_frac` is the *global* analogue of the
    probe's rho_clause_* features (clause satisfaction over the whole
    store instead of over the probe's inspected set). One jitted pass per
    (B, S, N) shape, chunked over N inside the program because
    eval_program_gathered materializes [B, S, chunk, W] intermediates;
    `chunk` defaults to `bitmap_chunk`.
    The bitmap stays on the device (only the scan plan reads it); the
    counts and clause counts are the one device-to-host copy. Boolean
    evaluation only — no distances, so per the repo's NDC accounting
    (predicate evaluations are tracked separately in n_inspected)
    compiling the bitmap costs 0 NDC, like every other predicate
    evaluation in the traversal. Results are exact and per-lane
    independent: lane b's row depends only on its own program row, which
    the serving layer's batch-composition guarantees rely on.
    """
    values = jnp.asarray(values)
    if values.ndim == 1:
        values = values[:, None]
    n = int(labels.shape[0])
    valid, counts, cc = _matrix_pass(prog, jnp.asarray(labels), values,
                                     chunk=chunk or bitmap_chunk(prog, n))
    counts, cc = jax.device_get((counts, cc))
    frac = np.asarray(cc, np.float32) / float(n)
    return valid, counts.astype(np.int64), frac


def clause_counts(clause_sat, counted, n_slots: int = CLAUSE_FEATURE_SLOTS):
    """Per-clause hit counters over the counted (inspected-new) candidates.

    clause_sat [B, S, R] bool, counted [B, R] bool -> [B, n_slots] i32,
    truncating/zero-padding the program's S slots to the fixed feature
    width.
    """
    cs = (clause_sat & counted[:, None, :]).sum(-1).astype(jnp.int32)  # [B,S]
    s = cs.shape[1]
    if s >= n_slots:
        return cs[:, :n_slots]
    return jnp.pad(cs, ((0, 0), (0, n_slots - s)))


# legacy FilterSpec predicate tags → compiled clause kinds
_SPEC_KIND = {PRED_CONTAIN: CLAUSE_CONTAIN, PRED_EQUAL: CLAUSE_EQUAL,
              PRED_RANGE: CLAUSE_RANGE}
