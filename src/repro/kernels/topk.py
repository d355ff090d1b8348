"""Pallas TPU kernel: sorted-buffer top-M merge (queue maintenance).

Each traversal step merges the sorted candidate buffer [B, M] with R fresh
neighbor distances and keeps the best M. Heaps don't vectorize; instead a
bitonic compare-exchange network (static data flow, pure VPU selects) sorts
the padded concatenation in VMEM. Payloads (packed node-id + expanded/valid
flags) ride through the same selects.

Width = next_pow2(M+R); the network has log²(width) stages of [bB, width]
element-wise ops — for M=512, R=64 that's 55 stages on a 1024-wide block.
Compiled for the TPU the width is at least one 128-lane vreg row
(`network_width`): lane rotations are only lowered on whole vregs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import _bitonic_stages

INF = float("inf")

# Narrowest network the compiled kernels run: one vreg row of lanes.
LANES = 128


def network_width(n: int, interpret: bool) -> int:
    """Sorting-network width for n live entries: next_pow2(n), raised to a
    full vreg row when compiled (interpret mode keeps the narrow network —
    XLA:CPU compile time grows exponentially with its stage count)."""
    w = 1 << (n - 1).bit_length()
    return w if interpret else max(w, LANES)


def bitonic_topm(keys, vals, m):
    """In-kernel ascending bitonic sort of [b, width] keys (width = pow2)
    carrying int32 vals through the same selects; returns the best-m prefix.

    Shared by the standalone queue-merge kernel below and the fused
    traversal-step kernel (kernels.fused_step), which runs it twice —
    once at queue width, once at result width — inside one VMEM pass.

    The compare-exchange partner of lane i is i ^ j: i + j where bit j of
    i is clear, i - j where it is set. Two lane rotations and a select
    give it, so the network needs no gather (which Mosaic cannot lower).
    """
    b, width = keys.shape
    idx = jax.lax.broadcasted_iota(jnp.int32, (b, width), 1)
    for j, k in _bitonic_stages(width):
        first = (idx & j) == 0                   # i < partner
        asc = (idx & k) == 0

        def partner(x, first=first, j=j):
            # roll(x, s)[i] == x[i - s]: shift width - j reads x[i + j]
            return jnp.where(first, pltpu.roll(x, width - j, 1),
                             pltpu.roll(x, j, 1))

        k_part = partner(keys)
        v_part = partner(vals)
        # the lower lane of an ascending pair keeps the smaller key, and so
        # on; boolean algebra, not a select of booleans (Mosaic lowers no
        # i1 select)
        up = ~(first ^ asc)
        keep_self = (up & (keys <= k_part)) | (~up & (keys >= k_part))
        keys = jnp.where(keep_self, keys, k_part)
        vals = jnp.where(keep_self, vals, v_part)
    return keys[:, :m], vals[:, :m]


def merge_topm(dist, pay, new_dist, new_pay, m, width):
    """Pad-concatenate a sorted [b,M] buffer with [b,R] fresh entries and
    keep the best m via the bitonic network (width = next_pow2(M+R))."""
    b = dist.shape[0]
    pad = width - dist.shape[1] - new_dist.shape[1]
    keys, vals = [dist, new_dist], [pay, new_pay]
    if pad:
        keys.append(jnp.full((b, pad), INF, jnp.float32))
        vals.append(jnp.full((b, pad), -1, jnp.int32))
    return bitonic_topm(jnp.concatenate(keys, axis=1),
                        jnp.concatenate(vals, axis=1), m)


def _merge_kernel(dist_ref, pay_ref, nd_ref, np_ref, od_ref, op_ref, *, m, width):
    od_ref[...], op_ref[...] = merge_topm(
        dist_ref[...], pay_ref[...], nd_ref[...], np_ref[...], m, width)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def topm_merge(dist, payload, new_dist, new_payload, *, block_b: int = 8,
               interpret: bool = False):
    """Merge sorted [B,M] + [B,R] -> sorted best-M (dist, payload)."""
    b, m = dist.shape
    r = new_dist.shape[1]
    width = network_width(m + r, interpret)
    bb = min(block_b, b)
    pad = (-b) % bb
    if pad:
        dist = jnp.pad(dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
        payload = jnp.pad(payload, ((0, pad), (0, 0)), constant_values=-1)
        new_dist = jnp.pad(new_dist, ((0, pad), (0, 0)), constant_values=jnp.inf)
        new_payload = jnp.pad(new_payload, ((0, pad), (0, 0)), constant_values=-1)
    bp = dist.shape[0]

    kern = functools.partial(_merge_kernel, m=m, width=width)
    od, op = pl.pallas_call(
        kern,
        grid=(bp // bb,),
        in_specs=[
            pl.BlockSpec((bb, m), lambda i: (i, 0)),
            pl.BlockSpec((bb, m), lambda i: (i, 0)),
            pl.BlockSpec((bb, r), lambda i: (i, 0)),
            pl.BlockSpec((bb, r), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, m), lambda i: (i, 0)),
            pl.BlockSpec((bb, m), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, m), jnp.float32),
            jax.ShapeDtypeStruct((bp, m), jnp.int32),
        ],
        interpret=interpret,
    )(dist.astype(jnp.float32), payload, new_dist.astype(jnp.float32), new_payload)
    return od[:b], op[:b]


# --------------------------------------------------------------------------
# host fallback (non-TPU): log-depth merge instead of the unrolled network
# --------------------------------------------------------------------------
# XLA:CPU compile time explodes exponentially in the depth of the unrolled
# compare-exchange chain (measured ~1.7× per stage), so the full log²-stage
# network above is TPU-only (Mosaic handles it fine). The host path exploits
# that the *buffer* is already sorted: stable-sort only the R fresh entries
# (uint32 monotone bitcast — squared distances are non-negative — makes the
# XLA sort an integer sort), then a single log(width)-stage bitonic *merge*
# phase combines the two sorted runs. Reshape-based pair exchange keeps every
# stage pure elementwise min/max — no gathers, which XLA:CPU executes
# scalar-slow. Exact stable-argsort semantics up to distance ties.


def sort_kv_f32(keys, vals):
    """Stable ascending sort of non-negative f32 keys [B,R] carrying vals."""
    k_u32 = jax.lax.bitcast_convert_type(keys.astype(jnp.float32), jnp.uint32)
    ks, vs = jax.lax.sort((k_u32, vals), dimension=1, num_keys=1, is_stable=True)
    return jax.lax.bitcast_convert_type(ks, jnp.float32), vs


def bitonic_merge_phase(keys, pos, lanes):
    """One full bitonic merge phase (strides w/2 … 1, all ascending) over a
    row-bitonic [B, w] block under the lexicographic total order (key, pos).

    `lanes` is a tuple of extra [B, w] arrays riding the same selects.
    Because `pos` participates in the comparison, the phase realizes a
    *total* order whenever the pos values within a row are distinct — the
    property the cross-shard merge (distributed.merge) uses to make the
    merged result independent of the merge-tree shape, bit for bit.
    """
    b, w = keys.shape
    j = w // 2
    while j >= 1:
        kk = keys.reshape(b, w // (2 * j), 2, j)
        pp = pos.reshape(b, w // (2 * j), 2, j)
        ll = [x.reshape(b, w // (2 * j), 2, j) for x in lanes]
        lo_k, hi_k = kk[:, :, 0, :], kk[:, :, 1, :]
        lo_p, hi_p = pp[:, :, 0, :], pp[:, :, 1, :]
        keep = (lo_k < hi_k) | ((lo_k == hi_k) & (lo_p <= hi_p))
        keys = jnp.stack([jnp.where(keep, lo_k, hi_k),
                          jnp.where(keep, hi_k, lo_k)], axis=2).reshape(b, w)
        pos = jnp.stack([jnp.where(keep, lo_p, hi_p),
                         jnp.where(keep, hi_p, lo_p)], axis=2).reshape(b, w)
        lanes = tuple(
            jnp.stack([jnp.where(keep, x[:, :, 0, :], x[:, :, 1, :]),
                       jnp.where(keep, x[:, :, 1, :], x[:, :, 0, :])],
                      axis=2).reshape(b, w)
            for x in ll)
        j //= 2
    return keys, pos, lanes


def bitonic_merge_sorted(old_d, old_p, ns_d, ns_p, m):
    """Merge sorted asc [B,M0] with sorted asc [B,R] -> best m, log-depth.

    The inf-padded concat `old ++ pad ++ reversed(new)` is bitonic, so a
    single merge phase (strides w/2 … 1, all ascending) sorts it. A carried
    position lane breaks key ties lexicographically in concat order (old
    entries first, then new in their sorted order, pads last), making the
    result bitwise-identical to a stable argsort over `[old | new]` — ties
    included. (The TPU kernel's full network has no such tiebreak; on real
    ties its payload order may differ.)
    """
    b, m0 = old_d.shape
    r = ns_d.shape[1]
    w = 1 << (m0 + r - 1).bit_length()
    pad = w - m0 - r
    keys = jnp.concatenate(
        [old_d, jnp.full((b, pad), INF, jnp.float32), ns_d[:, ::-1]], axis=1)
    vals = jnp.concatenate(
        [old_p, jnp.full((b, pad), -1, jnp.int32), ns_p[:, ::-1]], axis=1)
    pos = jnp.broadcast_to(
        jnp.concatenate([jnp.arange(m0, dtype=jnp.int32),
                         jnp.arange(m0 + r, w, dtype=jnp.int32),  # pads last
                         jnp.arange(m0 + r - 1, m0 - 1, -1, dtype=jnp.int32)]),
        (b, w))
    keys, _, (vals,) = bitonic_merge_phase(keys, pos, (vals,))
    return keys[:, :m], vals[:, :m]


def topm_merge_host(dist, payload, new_dist, new_payload):
    """Host-path equivalent of `topm_merge` (sorted [B,M] + raw [B,R])."""
    ns_d, ns_p = sort_kv_f32(new_dist, new_payload)
    return bitonic_merge_sorted(dist.astype(jnp.float32), payload, ns_d, ns_p,
                                dist.shape[1])


def pack_payload(idx, expanded, valid):
    """node id (<2^29) + expanded/valid flags into one non-negative int32."""
    p = idx | (expanded.astype(jnp.int32) << 29) | (valid.astype(jnp.int32) << 30)
    return jnp.where(idx < 0, -1, p)


def unpack_payload(p):
    neg = p < 0
    idx = jnp.where(neg, -1, p & ((1 << 29) - 1))
    expanded = ~neg & ((p >> 29) & 1 != 0)
    valid = ~neg & ((p >> 30) & 1 != 0)
    return idx, expanded, valid
