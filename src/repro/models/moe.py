"""Mixture-of-Experts layer with expert parallelism.

Three dispatch implementations, selected by ``cfg.moe_impl`` (or "auto"):

* ``dense_onehot`` — GShard-style einsum dispatch with a capacity dim.
  O(T·E·C) memory: only viable at test scale; used when no mesh is active.
* ``ep_a2a``       — production path.  ``shard_map`` over the "model" axis:
  tokens are sequence-sharded, routed assignments are exchanged with
  ``all_to_all`` to their owning expert shard, locally sorted into per-expert
  batches, batch-einsum'd through the shard's experts, and returned.  This is
  the EP pattern that scales to 160-expert DeepSeek-V2 on a 16-way model
  axis.
* ``ep_psum``      — decode path.  Token counts are tiny, so every expert
  shard applies its local experts to all tokens (masked) and a psum over the
  model axis combines; no all_to_all latency on the decode critical path.

Router: softmax → top-k (renormalised), switch-style load-balance aux loss
plus a z-loss for logit drift.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers


def moe_init(key, cfg, dtype=jnp.float32):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    scale = d**-0.5
    params = {
        "router": {"w": (jax.random.normal(ks[0], (d, e)) * scale).astype(jnp.float32)},
        "experts": {
            "gate": (jax.random.normal(ks[1], (e, d, f)) * scale).astype(dtype),
            "up": (jax.random.normal(ks[2], (e, d, f)) * scale).astype(dtype),
            "down": (jax.random.normal(ks[3], (e, f, d)) * f**-0.5).astype(dtype),
        },
    }
    if cfg.n_shared_experts:
        params["shared"] = layers.mlp_init(
            ks[4], d, f * cfg.n_shared_experts, act="silu", dtype=dtype
        )
    return params


def moe_axes(cfg):
    axes = {
        "router": {"w": (None, None)},
        "experts": {
            "gate": ("experts", None, None),
            "up": ("experts", None, None),
            "down": ("experts", None, None),
        },
    }
    if cfg.n_shared_experts:
        axes["shared"] = layers.mlp_axes(act="silu")
    return axes


def _route(router_w: jnp.ndarray, x_flat: jnp.ndarray, cfg):
    """x_flat (T, D) → (weights (T, k), ids (T, k), aux_loss scalar)."""
    logits = x_flat.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    weights, ids = jax.lax.top_k(probs, cfg.moe_top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # Switch-transformer load-balance loss + z-loss.
    e = cfg.n_experts
    me = probs.mean(axis=0)  # mean router prob per expert
    ce = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = e * jnp.sum(me * ce)
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return weights, ids, aux + 1e-3 * z


def _expert_ffn(gate_w, up_w, down_w, xe: jnp.ndarray) -> jnp.ndarray:
    """Batched SwiGLU over stacked experts.  xe: (E, C, D) → (E, C, D)."""
    gate = jnp.einsum("ecd,edf->ecf", xe, gate_w.astype(xe.dtype))
    up = jnp.einsum("ecd,edf->ecf", xe, up_w.astype(xe.dtype))
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, down_w.astype(xe.dtype))


# ---------------------------------------------------------------------------
# dense_onehot — test-scale reference dispatch
# ---------------------------------------------------------------------------


def _moe_dense_onehot(params, x: jnp.ndarray, cfg):
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    weights, ids, aux = _route(params["router"]["w"], xf, cfg)
    e, k = cfg.n_experts, cfg.moe_top_k
    # Floor keeps tiny decode batches drop-free (capacity dropping is a
    # throughput trade for big T, not meant to distort 2-token steps).
    cap = max(int(cfg.capacity_factor * t * k / e), min(t, 8))

    onehot = jax.nn.one_hot(ids, e, dtype=jnp.float32)  # (T, k, E)
    mask = onehot.max(axis=1)  # (T, E) 0/1
    weight_e = (onehot * weights[..., None]).sum(axis=1)  # (T, E)
    # position of each token within its expert queue (first-come order)
    pos = jnp.cumsum(mask, axis=0) - 1.0  # (T, E)
    keep = (pos < cap) * mask
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    dispatch = keep[..., None] * pos_oh  # (T, E, C)
    combine = (keep * weight_e)[..., None] * pos_oh

    xe = jnp.einsum("tec,td->ecd", dispatch, xf.astype(jnp.float32))
    ye = _expert_ffn(
        params["experts"]["gate"], params["experts"]["up"],
        params["experts"]["down"], xe,
    )
    y = jnp.einsum("tec,ecd->td", combine, ye)
    return y.reshape(b, s, d).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# ep_a2a — shard_map expert parallelism over the "model" axis
# ---------------------------------------------------------------------------


def _moe_ep_a2a(params, x: jnp.ndarray, cfg, mesh):
    axis = "model"
    batch_axes = tuple(a for a in mesh.axis_names if a != axis)
    pspec_x = jax.sharding.PartitionSpec(batch_axes, axis, None)
    e_spec = jax.sharding.PartitionSpec(axis, None, None)
    r_spec = jax.sharding.PartitionSpec(None, None)
    pspec_scalar = jax.sharding.PartitionSpec()

    def local_moe(xl, router_w, gate_w, up_w, down_w):
        # xl: (b_loc, s_loc, D); gate/up/down: (E_loc, ·, ·) local experts.
        ep = jax.lax.axis_size(axis)
        bl, sl, d = xl.shape
        t = bl * sl
        xf = xl.reshape(t, d)
        weights, ids, aux = _route(router_w, xf, cfg)
        k = cfg.moe_top_k
        e_loc = cfg.n_experts // ep
        cap = max(int(cfg.capacity_factor * t * k / ep), 8)

        # --- group routed assignments by destination shard --------------
        flat_ids = ids.reshape(-1)  # (T·k,)
        flat_w = weights.reshape(-1)
        dest = flat_ids // e_loc
        order = jnp.argsort(dest)
        dsorted = dest[order]
        counts = jnp.bincount(dest, length=ep)
        starts = jnp.cumsum(counts) - counts
        pos = jnp.arange(t * k) - starts[dsorted]
        keep = pos < cap
        slot = jnp.where(keep, dsorted * cap + pos, ep * cap)  # overflow bin
        src_tok = order // k

        send_x = jnp.zeros((ep * cap + 1, d), xf.dtype).at[slot].set(xf[src_tok])
        send_e = jnp.full((ep * cap + 1,), e_loc, jnp.int32).at[slot].set(
            (flat_ids[order] % e_loc).astype(jnp.int32)
        )
        send_x, send_e = send_x[:-1], send_e[:-1]

        # --- all_to_all: chunk j of shard i → shard j --------------------
        recv_x = jax.lax.all_to_all(
            send_x.reshape(ep, cap, d), axis, split_axis=0, concat_axis=0
        ).reshape(ep * cap, d)
        recv_e = jax.lax.all_to_all(
            send_e.reshape(ep, cap, 1), axis, split_axis=0, concat_axis=0
        ).reshape(ep * cap)

        # --- local per-expert batching (sort by local expert id) ---------
        t_r = ep * cap
        cap2 = max(int(cfg.capacity_factor * t_r / e_loc), 8)
        order2 = jnp.argsort(recv_e)
        esort = recv_e[order2]
        counts2 = jnp.bincount(recv_e, length=e_loc + 1)[: e_loc + 1]
        starts2 = jnp.cumsum(counts2) - counts2
        pos2 = jnp.arange(t_r) - jnp.take(starts2, jnp.minimum(esort, e_loc))
        valid2 = (esort < e_loc) & (pos2 < cap2)
        slot2 = jnp.where(valid2, esort * cap2 + pos2, e_loc * cap2)

        xe = jnp.zeros((e_loc * cap2 + 1, d), jnp.float32)
        xe = xe.at[slot2].set(recv_x[order2].astype(jnp.float32))
        ye = _expert_ffn(gate_w, up_w, down_w, xe[:-1].reshape(e_loc, cap2, d))
        ye_flat = ye.reshape(e_loc * cap2, d)

        # --- undo the local sort, reverse exchange -----------------------
        y_sorted = jnp.where(
            valid2[:, None],
            jnp.take(ye_flat, jnp.minimum(slot2, e_loc * cap2 - 1), axis=0),
            0.0,
        )
        y_recv = jnp.zeros((t_r, d), jnp.float32).at[order2].set(y_sorted)
        y_send = jax.lax.all_to_all(
            y_recv.reshape(ep, cap, d), axis, split_axis=0, concat_axis=0
        ).reshape(t_r, d)

        # --- combine: weighted scatter-add back onto tokens --------------
        contrib = jnp.where(
            keep[:, None],
            jnp.take(y_send, jnp.minimum(slot, t_r - 1), axis=0)
            * flat_w[order][:, None],
            0.0,
        )
        y_tok = jnp.zeros((t, d), jnp.float32).at[src_tok].add(contrib)

        aux = jax.lax.pmean(aux, axis)
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        return y_tok.reshape(bl, sl, d).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(pspec_x, r_spec, e_spec, e_spec, e_spec),
        out_specs=(pspec_x, pspec_scalar),
    )(
        x,
        params["router"]["w"],
        params["experts"]["gate"],
        params["experts"]["up"],
        params["experts"]["down"],
    )
    return y, aux


# ---------------------------------------------------------------------------
# ep_psum — decode path (tiny token counts, no all_to_all)
# ---------------------------------------------------------------------------


def _moe_ep_psum(params, x: jnp.ndarray, cfg, mesh):
    axis = "model"
    batch_axes = tuple(a for a in mesh.axis_names if a != axis)
    pspec_x = jax.sharding.PartitionSpec(batch_axes, None, None)
    e_spec = jax.sharding.PartitionSpec(axis, None, None)
    r_spec = jax.sharding.PartitionSpec(None, None)
    pspec_scalar = jax.sharding.PartitionSpec()

    def local_moe(xl, router_w, gate_w, up_w, down_w):
        ep = jax.lax.axis_size(axis)
        bl, sl, d = xl.shape
        t = bl * sl
        xf = xl.reshape(t, d)
        weights, ids, aux = _route(router_w, xf, cfg)
        e_loc = cfg.n_experts // ep
        lo = jax.lax.axis_index(axis) * e_loc

        rel = ids - lo  # (T, k)
        in_range = (rel >= 0) & (rel < e_loc)
        oh = jax.nn.one_hot(jnp.where(in_range, rel, 0), e_loc) * (
            jnp.where(in_range, weights, 0.0)
        )[..., None]
        local_w = oh.sum(axis=1)  # (T, e_loc)

        xe = jnp.broadcast_to(xf.astype(jnp.float32), (e_loc, t, d))
        ye = _expert_ffn(gate_w, up_w, down_w, xe)
        y = jnp.einsum("te,etd->td", local_w, ye)
        y = jax.lax.psum(y, axis)
        aux = jax.lax.pmean(aux, axis)
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        return y.reshape(bl, sl, d).astype(xl.dtype), aux

    y, aux = jax.shard_map(
        local_moe,
        mesh=mesh,
        in_specs=(pspec_x, r_spec, e_spec, e_spec, e_spec),
        out_specs=(pspec_x, pspec_scalar),
    )(
        x,
        params["router"]["w"],
        params["experts"]["gate"],
        params["experts"]["up"],
        params["experts"]["down"],
    )
    return y, aux


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def _active_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty or "model" not in mesh.axis_names else mesh


def moe_apply(params, x: jnp.ndarray, cfg, *, decode: bool = False):
    """(B, S, D) → (y, aux_loss).  Implementation chosen by cfg/mesh."""
    impl = cfg.moe_impl
    mesh = _active_mesh()

    if impl == "auto":
        if mesh is None:
            impl = "dense_onehot"
        else:
            impl = "ep_psum" if decode else "ep_a2a"

    if impl == "dense_onehot" or mesh is None:
        y, aux = _moe_dense_onehot(params, x, cfg)
    elif impl == "ep_a2a":
        y, aux = _moe_ep_a2a(params, x, cfg, mesh)
    elif impl == "ep_psum":
        y, aux = _moe_ep_psum(params, x, cfg, mesh)
    else:
        raise ValueError(f"unknown moe_impl {impl!r}")

    if "shared" in params:
        y = y + layers.mlp_apply(params["shared"], x, act="silu")
    return y, aux
