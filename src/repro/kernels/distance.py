"""Pallas TPU kernel: batched masked squared-L2 distance (the NDC hot spot).

Every traversal step evaluates distances from B query lanes to their R
gathered neighbor vectors — the paper's cost unit (NDC). The kernel tiles
lanes into VMEM blocks and drives the contraction through the MXU via
dot_general; the predicate/visited mask is fused (masked entries emit +inf
so they never enter the queues).

Block shapes: (bB lanes) × (≤ BLOCK_R rows) × (full d). VMEM per block
≈ bB·min(R, BLOCK_R)·d·4 B — 2 MiB at bB=8, BLOCK_R=512, d=128, inside the
16 MiB of scoped VMEM a v5e kernel gets by default, with d as the MXU lane
dimension (pad d to 128 upstream for peak utilization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

INF = float("inf")

# Distances are float32 contractions on every path. The TPU's default
# precision rounds f32 operands to bf16 (~3 significant digits), which
# would reorder near-tied candidates and break agreement with the oracle;
# CPU backends compute f32 either way.
HIGHEST = jax.lax.Precision.HIGHEST

# Rows per distance block past which `sqdist_masked` tiles the row axis.
BLOCK_R = 512

# Row-count alignment for the pre-filter scan plan's gathered distance
# blocks. Empirically (pinned by tests/test_planner.py), XLA:CPU emits the
# same reduction for the bdrd einsum at every R that is a multiple of 64,
# so a (query, row) pair evaluates to the same bits no matter how wide the
# gathered block around it is — which is what lets the scan plan, the
# bruteforce oracle, and any serving-time batch shape agree bitwise. Widths
# off the alignment (R=7, R=257, …) pick different vectorizations and drift
# in the last ulp.
SCAN_ALIGN = 64


def sqdist_bdrd(q, x):
    """Pure-jnp squared L2: q [B,d], x [B,R,d] -> [B,R], clamped >= 0.

    The single source of the distance expression — the engine's init path,
    the dense backend, and the fused kernel's host path all call this so a
    numerics tweak can never desynchronize them (backend parity depends on
    bitwise-identical distances).
    """
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1)[:, None]
    xn = jnp.sum(x * x, axis=-1)
    qx = jnp.einsum("bd,brd->br", q, x, precision=HIGHEST)
    return jnp.maximum(qn + xn - 2.0 * qx, 0.0)


@jax.jit
def scan_sqdist_lanes(q, x, mask):
    """Per-lane deterministic masked squared L2 for the pre-filter scan plan.

    q [B,d], x [B,V,d], mask [B,V] -> [B,V] f32 (+inf where ~mask).

    Each lane is evaluated at the canonical [1, V, d] shape via `lax.map`,
    so the value of any (query, row) pair is independent of which lanes
    share the batch — the serving layer pads scan batches to different lane
    widths than the one-shot planner, and the scheduled == one-shot
    bit-identity for scan-routed requests rides on this. V must be a
    multiple of SCAN_ALIGN (64-aligned widths are mutually bitwise-stable,
    see above), so the same pair also evaluates identically regardless of
    how much padding the gather added. Shares `sqdist_bdrd` per lane: one
    distance expression for traversal, scan, and oracle.
    """
    if x.shape[1] % SCAN_ALIGN:
        raise ValueError(
            f"scan width {x.shape[1]} not a multiple of SCAN_ALIGN "
            f"({SCAN_ALIGN}); pad the gathered block")
    d = jax.lax.map(lambda qx: sqdist_bdrd(qx[0][None], qx[1][None])[0],
                    (q.astype(jnp.float32), x.astype(jnp.float32)))
    return jnp.where(mask, d, INF)


def _sqdist_kernel(q_ref, x_ref, mask_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)          # [bB, d]
    x = x_ref[...].astype(jnp.float32)          # [bB, R, d]
    qn = jnp.sum(q * q, axis=-1)[:, None]       # [bB, 1]
    xn = jnp.sum(x * x, axis=-1)                # [bB, R]
    # per-lane MXU contraction: [bB,1,d] · [bB,R,d]^T -> [bB,R]
    qx = jax.lax.dot_general(
        q[:, None, :], x,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )[:, 0, :]
    d = jnp.maximum(qn + xn - 2.0 * qx, 0.0)
    o_ref[...] = jnp.where(mask_ref[...], d, INF)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def sqdist_masked(q, x, mask, *, block_b: int = 8, interpret: bool = False):
    """q [B,d], x [B,R,d], mask [B,R] -> [B,R] f32 (+inf where masked).

    The grid tiles lanes and, past BLOCK_R rows, the R axis too, so a
    block holds at most bB·BLOCK_R·d floats (2 MiB at bB=8, d=128)
    whatever R is — the scan plan passes R = σ·N gathered rows.
    """
    b, d = q.shape
    r = x.shape[1]
    bb = min(block_b, b)
    br = min(BLOCK_R, r)
    pad = (-b) % bb
    pad_r = (-r) % br
    if pad or pad_r:
        q = jnp.pad(q, ((0, pad), (0, 0)))
        x = jnp.pad(x, ((0, pad), (0, pad_r), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, pad_r)))
    bp, rp = x.shape[:2]

    out = pl.pallas_call(
        _sqdist_kernel,
        grid=(bp // bb, rp // br),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, br, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((bb, br), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bb, br), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, rp), jnp.float32),
        interpret=interpret,
    )(q, x, mask)
    return out[:b, :r]
