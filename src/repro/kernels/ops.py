"""jit'd wrappers around the Pallas kernels, one execution per platform.

On a TPU backend the kernels run compiled (Mosaic). On the CPU (tests,
`JAX_PLATFORMS=cpu`):

  sqdist / gbdt   execute via `interpret=True` — the kernel body itself runs
                  through the Pallas interpreter, validating semantics
                  (tests assert allclose vs ref.py) while the BlockSpec
                  tiling remains the TPU-target source of truth.
  top-M merges    the unrolled compare-exchange networks make XLA:CPU
                  compile time explode exponentially in stage count (the
                  Mosaic lowering is unaffected), so the merge kernels
                  dispatch to semantically-equivalent log-depth host
                  implementations in kernels.topk / kernels.fused_step;
                  tests assert exact agreement vs the ref.py oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import distance as _distance
from repro.kernels import fused_step as _fused
from repro.kernels import gbdt as _gbdt
from repro.kernels import topk as _topk
from repro.kernels.topk import pack_payload, unpack_payload  # re-export


def interpret_mode() -> bool:
    """True on the CPU (interpret mode / host twins), False on the TPU
    (compiled kernels). Any other backend has no kernel path: an error,
    never a silent interpret run on an accelerator."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no kernel path for JAX backend {backend!r}: the Pallas kernels "
        "compile for 'tpu' and run interpreted on 'cpu'")


def batched_sqdist(q, x, mask=None):
    """q [B,d], x [B,R,d] -> [B,R] squared L2 (+inf where ~mask)."""
    if mask is None:
        mask = jnp.ones(x.shape[:2], bool)
    return _distance.sqdist_masked(q, x, mask, interpret=interpret_mode())


def masked_scan_dist(q, x, mask):
    """Pre-filter scan distance block: q [B,d], x [B,V,d] gathered valid
    rows (V a multiple of distance.SCAN_ALIGN), mask [B,V] -> [B,V] f32
    with +inf on masked pad entries.

    On TPU this is the fused masked-distance Pallas kernel (`sqdist_masked`
    — the scan plan reuses the traversal's distance kernel with the bitmap
    gather as its mask). On CPU it dispatches to the per-lane-deterministic
    host path instead: the batched kernel's values depend on the lane count,
    and the scan plan's bit-identity guarantees (vs the bruteforce oracle,
    and scheduled vs one-shot) need every (query, row) pair to evaluate to
    the same bits in any batch shape. The kernel itself is still
    interpret-validated against the host path in tests/test_planner.py.
    """
    if interpret_mode():
        return _distance.scan_sqdist_lanes(q, x, mask)
    return _distance.sqdist_masked(q, x, mask)


def queue_merge(dist, payload, new_dist, new_payload):
    """Merge a **sorted-ascending** [B,M] buffer with raw [B,R] entries.

    The sortedness precondition is load-bearing on the host path (the
    log-depth merge assumes the buffer is an ascending run); the TPU kernel
    happens to fully re-sort but callers must not rely on that.
    """
    if interpret_mode():
        return _topk.topm_merge_host(dist, payload, new_dist, new_payload)
    return _topk.topm_merge(dist, payload, new_dist, new_payload)


def fused_traversal_step(q, x, nb, is_new, prog, labels_g, values_g,
                         cand_dist, cand_pay, res_dist, res_idx, *,
                         pre: bool = False, quant=None,
                         precision: str = "float32"):
    """Fused filter program + distance + queue/result merge (one step).

    Returns (cand_dist, cand_pay, res_dist, res_idx, valid, clause_add) —
    see kernels.fused_step. `pre` selects the ACORN distance accounting
    (score predicate-valid first-visits only). `quant`/`precision` select
    the compressed-domain distance block (int8 ADC dot / PQ LUT gather);
    the host path shares `quant.codecs.quant_dist` with the dense backend
    so compressed-mode dense/pallas parity is exact on CPU.
    """
    if interpret_mode():
        return _fused.fused_step_host(q, x, nb, is_new, prog, labels_g,
                                      values_g, cand_dist, cand_pay,
                                      res_dist, res_idx, pre=pre,
                                      quant=quant, precision=precision)
    return _fused.fused_step(q, x, nb, is_new, prog, labels_g, values_g,
                             cand_dist, cand_pay, res_dist, res_idx, pre=pre,
                             quant=quant, precision=precision)


def estimator_predict(feats, packed_model, depth):
    feat_idx, thresh, leaf, base = packed_model
    return _gbdt.gbdt_predict(feats, feat_idx, thresh, leaf, base, depth,
                              interpret=interpret_mode())
