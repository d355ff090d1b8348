"""Feed-forward layers: gated dense MLP and sort-based dropping MoE.

MoE dispatch is the TPU-standard sorted-scatter ("dropping") scheme:
token→expert assignments are sorted by expert id, ranked within expert,
and scattered into a static [E, C, d] buffer sharded over the model axis
(expert parallelism). Capacity overflow drops (classic GShard semantics);
a load-balance auxiliary loss keeps the router honest.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import P, dense_init, gelu, silu


def init_mlp(key, cfg, d_in=None, d_ff=None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    prm = {
        "w_in": dense_init(ks[0], (d, f), d, cfg.param_dtype, ("embed", "mlp")),
        "w_out": dense_init(ks[1], (f, d), f, cfg.param_dtype, ("mlp", "embed")),
    }
    if cfg.act == "silu":  # gated (llama-style)
        prm["w_gate"] = dense_init(ks[2], (d, f), d, cfg.param_dtype, ("embed", "mlp"))
    return prm


def mlp_forward(cfg, p, x):
    cd = cfg.compute_dtype
    h = x @ p["w_in"].astype(cd)
    if "w_gate" in p:
        h = silu(x @ p["w_gate"].astype(cd)) * h
    else:
        h = gelu(h)
    return h @ p["w_out"].astype(cd)


# --------------------------------------------------------------------- MoE ----
def init_moe(key, cfg):
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    ks = jax.random.split(key, 5)
    prm = {
        "router": dense_init(ks[0], (d, e), d, cfg.param_dtype, ("embed", None)),
        "w_in": dense_init(ks[1], (e, d, f), d, cfg.param_dtype, ("expert", "embed", "mlp")),
        "w_gate": dense_init(ks[2], (e, d, f), d, cfg.param_dtype, ("expert", "embed", "mlp")),
        "w_out": dense_init(ks[3], (e, f, d), f, cfg.param_dtype, ("expert", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        prm["shared"] = init_mlp(ks[4], cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return prm


def moe_forward_global(cfg, p, x, return_aux=False):
    """Beyond-baseline MoE dispatch: global sort + capacity-sharded buffer.

    buf [E, C, d] is sharded (expert→model, capacity→data): the expert
    einsums then contract an UNSHARDED d — no activation-sized partial-sum
    all-reduces (the baseline per-row variant contracts the FSDP-sharded
    embed dim and pays ~2.5 TB/device/layer on deepseek-v3). The dispatch
    scatter from x [B(data),S,d] into buf is the canonical EP all-to-all.
    Enabled with REPRO_MOE_GLOBAL=1 (perf iteration; see EXPERIMENTS §Perf).
    """
    from repro.distributed.sharding import constrain

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cd = cfg.compute_dtype
    t = b * s
    xf = x.reshape(t, d)

    logits = (xf @ p["router"].astype(cd)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_v, gate_e = jax.lax.top_k(probs, k)
    gate_v = gate_v / jnp.maximum(gate_v.sum(-1, keepdims=True), 1e-9)

    cap = int(cfg.capacity_factor * k * t / e) + 1
    cap = -(-cap // 16) * 16

    flat_e = gate_e.reshape(-1)
    flat_g = gate_v.reshape(-1)
    tok_ix = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    se, sg, st = flat_e[order], flat_g[order], tok_ix[order]
    starts = jnp.searchsorted(se, jnp.arange(e, dtype=se.dtype))
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[se].astype(jnp.int32)
    keep = rank < cap
    e_ix = jnp.where(keep, se, e).astype(jnp.int32)
    r_ix = jnp.where(keep, rank, cap)

    buf = jnp.zeros((e, cap, d), cd)
    buf = buf.at[e_ix, r_ix].set(xf[st].astype(cd), mode="drop")
    buf = constrain(buf, ("expert", "capacity", None))             # EP × DP

    # ZeRO-3 weight gather: unshard the contraction dim so the expert
    # einsums are fully local (weight-sized AG ≪ activation-sized AR)
    w_in = constrain(p["w_in"].astype(cd), ("expert", None, None))
    w_gate = constrain(p["w_gate"].astype(cd), ("expert", None, None))
    w_out = constrain(p["w_out"].astype(cd), ("expert", None, None))
    h = jnp.einsum("ecd,edf->ecf", buf, w_in)
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    yb = jnp.einsum("ecf,efd->ecd", silu(g) * h, w_out)
    yb = constrain(yb, ("expert", "capacity", None))

    gathered = yb[e_ix, r_ix] * jnp.where(keep, sg, 0.0)[:, None].astype(cd)
    out = jnp.zeros((t, d), cd).at[st].add(gathered, mode="drop")
    out = constrain(out.reshape(b, s, d), ("batch", None, None))

    if cfg.n_shared_experts:
        out = out + mlp_forward(cfg, p["shared"], xf).reshape(b, s, d)

    if return_aux:
        me = probs.mean(axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[flat_e].add(1.0) / (t * k)
        return out, e * jnp.sum(me * ce)
    return out


def moe_forward(cfg, p, x, return_aux=False):
    import os

    if os.environ.get("REPRO_MOE_GLOBAL"):
        return moe_forward_global(cfg, p, x, return_aux)
    return _moe_forward_rowwise(cfg, p, x, return_aux)


def _moe_forward_rowwise(cfg, p, x, return_aux=False):
    """x [B, S, d] -> [B, S, d] (+ load-balance aux loss).

    Dispatch is PER BATCH ROW: each row sorts its own S·k assignments and
    scatters into a [B, E, C_row, d] buffer with C_row = cf·k·S/E. The
    leading B dim keeps the data sharding (each data shard dispatches its
    local rows only — no global token sort, no cross-shard gather), and the
    E dim carries expert parallelism over the model axis. Row-level
    capacity slightly raises drop variance vs global capacity; cf covers it
    (recorded in DESIGN.md).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cd = cfg.compute_dtype

    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cd)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_v, gate_e = jax.lax.top_k(probs, k)                      # [B, S, k]
    gate_v = gate_v / jnp.maximum(gate_v.sum(-1, keepdims=True), 1e-9)

    cap = int(cfg.capacity_factor * k * s / e) + 1
    cap = -(-cap // 8) * 8

    flat_e = gate_e.reshape(b, s * k)
    flat_g = gate_v.reshape(b, s * k)
    tok_ix = jnp.repeat(jnp.arange(s, dtype=jnp.int32), k)[None, :]  # [1, S*k]
    tok_ix = jnp.broadcast_to(tok_ix, (b, s * k))
    order = jnp.argsort(flat_e, axis=1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=1)
    sg = jnp.take_along_axis(flat_g, order, axis=1)
    st = jnp.take_along_axis(tok_ix, order, axis=1)
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(e, dtype=row.dtype)))(se)
    rank = jnp.arange(s * k, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        starts, se.astype(jnp.int32), axis=1).astype(jnp.int32)
    keep = rank < cap
    e_ix = jnp.where(keep, se, e).astype(jnp.int32)               # OOB drops
    r_ix = jnp.where(keep, rank, cap)
    b_ix = jnp.arange(b, dtype=jnp.int32)[:, None]

    from repro.distributed.sharding import _ambient_mesh, constrain

    import os
    mesh = _ambient_mesh()
    use_shmap = bool(os.environ.get("REPRO_MOE_SHMAP")) and mesh is not None \
        and "data" in mesh.shape

    if use_shmap:
        # Dispatch under shard_map: the token gather + capacity scatter are
        # *provably local* per data shard (GSPMD otherwise lowers the
        # cross-shard gather as full-result all-reduces; §Perf iter 4).
        from jax.sharding import PartitionSpec as PS
        from jax import shard_map as _shm

        dp = PS(("pod", "data") if "pod" in mesh.shape else "data")
        row = PS(*dp, None)
        row3 = PS(*dp, None, None)

        def _dispatch(xl, stl, el, rl):
            bl = xl.shape[0]
            bi = jnp.arange(bl, dtype=jnp.int32)[:, None]
            xt = jnp.take_along_axis(xl, stl[..., None], axis=1).astype(cd)
            bufl = jnp.zeros((bl, e, cap, d), cd)
            return bufl.at[bi, el, rl].set(xt, mode="drop")

        buf = _shm(_dispatch, mesh=mesh, in_specs=(row3, row, row, row),
                   out_specs=PS(*dp, None, None, None), check_vma=False)(
                       x, st, e_ix, r_ix)
        buf = constrain(buf, ("batch", "expert", None, None))      # slice E: free
    else:
        xt = jnp.take_along_axis(x, st[..., None], axis=1).astype(cd)  # [B, S*k, d]
        xt = constrain(xt, ("batch", None, None))
        buf = jnp.zeros((b, e, cap, d), cd)
        buf = buf.at[b_ix, e_ix, r_ix].set(xt, mode="drop")
        buf = constrain(buf, ("batch", "expert", None, None))      # DP × EP

    import os
    w_in = p["w_in"].astype(cd)
    w_gate = p["w_gate"].astype(cd)
    w_out = p["w_out"].astype(cd)
    if os.environ.get("REPRO_MOE_ZERO3"):
        # ZeRO-3 weight gather: unshard the FSDP (embed) dim so the expert
        # einsums contract locally — weight-sized AG instead of
        # activation-sized partial-sum AR (see EXPERIMENTS §Perf iter 3)
        w_in = constrain(w_in, ("expert", None, None))
        w_gate = constrain(w_gate, ("expert", None, None))
        w_out = constrain(w_out, ("expert", None, None))
    h = jnp.einsum("becd,edf->becf", buf, w_in)
    g = jnp.einsum("becd,edf->becf", buf, w_gate)
    if os.environ.get("REPRO_MOE_CONSTRAIN_OUT"):
        h = constrain(h, ("batch", "expert", None, None))
        g = constrain(g, ("batch", "expert", None, None))
    yb = jnp.einsum("becf,efd->becd", silu(g) * h, w_out)
    if os.environ.get("REPRO_MOE_CONSTRAIN_OUT"):
        yb = constrain(yb, ("batch", "expert", None, None))

    if use_shmap:
        # combine under shard_map: gather yb over E locally (one explicit
        # activation-sized all-gather over model) then scatter-add locally
        yb = constrain(yb, ("batch", None, None, None))  # AG over model
        gates = jnp.where(keep, sg, 0.0).astype(cd)

        def _combine(ybl, el, rl, stl, gl):
            bl = ybl.shape[0]
            bi = jnp.arange(bl, dtype=jnp.int32)[:, None]
            bk = ybl[bi, el, rl] * gl[..., None]
            return jnp.zeros((bl, s, d), cd).at[bi, stl].add(bk, mode="drop")

        from jax.sharding import PartitionSpec as PS
        from jax import shard_map as _shm
        dp = PS(("pod", "data") if "pod" in mesh.shape else "data")
        row = PS(*dp, None)
        out = _shm(_combine, mesh=mesh,
                   in_specs=(PS(*dp, None, None, None), row, row, row, row),
                   out_specs=PS(*dp, None, None), check_vma=False)(
                       yb, e_ix, r_ix, st, gates)
    else:
        back = yb[b_ix, e_ix, r_ix] * jnp.where(keep, sg, 0.0)[..., None].astype(cd)
        back = constrain(back, ("batch", None, None))
        out = jnp.zeros((b, s, d), cd).at[b_ix, st].add(back, mode="drop")
    out = constrain(out, ("batch", None, None))

    if cfg.n_shared_experts:
        out = out + mlp_forward(cfg, p["shared"], x.reshape(b * s, d)).reshape(b, s, d)

    if return_aux:
        # GShard load-balance loss: E * Σ_e f_e · p_e
        me = probs.mean(axis=(0, 1))                              # [E]
        ce = jnp.zeros((e,), jnp.float32).at[flat_e.reshape(-1)].add(1.0) / (b * s * k)
        aux = e * jnp.sum(me * ce)
        return out, aux
    return out
