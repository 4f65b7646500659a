"""Mixture-of-Experts FFN: top-k router + capacity-based dispatch.

Two interchangeable implementations (same math, same params):

* ``moe_gspmd`` — index-scatter dispatch expressed in plain einsum/scatter;
  GSPMD derives the collectives from sharding constraints (experts on
  "model").  This is the *baseline* the roofline measures.
* ``moe_ep_shardmap`` — explicit expert parallelism: shard_map over the
  model axis with hand-placed ``all_to_all`` dispatch/combine (the
  beyond-paper optimization exercised in §Perf hillclimbing).

Capacity: each expert accepts at most C = ceil(T_local*k/E * cf) tokens;
overflow tokens are dropped (contribute zero) like Switch/GShard.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .base import P


def moe_specs(d_model: int, d_ff: int, n_experts: int):
    return {
        "router": P((d_model, n_experts), ("embed", "experts"), dtype=jnp.float32),
        "w_gate": P((n_experts, d_model, d_ff), ("experts", "embed", "ffn")),
        "w_up": P((n_experts, d_model, d_ff), ("experts", "embed", "ffn")),
        "w_down": P((n_experts, d_ff, d_model), ("experts", "ffn", "embed")),
    }


def _route(params, x2d, top_k: int):
    """Router: top-k expert ids + renormalized weights.  x2d: (T, D)."""
    logits = (x2d.astype(jnp.float32) @ params["router"])  # (T, E)
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9
    )
    return experts, weights.astype(x2d.dtype), logits


def _capacity(n_tokens: int, top_k: int, n_experts: int, cf: float) -> int:
    c = int(np.ceil(n_tokens * top_k / n_experts * cf))
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU-friendly shapes


def moe_gspmd(params, x, *, top_k: int, capacity_factor: float = 1.25,
              constrain=None):
    """Capacity MoE via scatter dispatch; sharding left to GSPMD.

    x: (B, S, D) -> (B, S, D).  ``constrain(tensor, logical_axes)`` applies
    sharding constraints (injected by the distribution layer; identity in
    tests).
    """
    if constrain is None:
        constrain = lambda t, axes: t
    B, S, D = x.shape
    E = params["router"].shape[1]
    T = B * S
    x2d = x.reshape(T, D)
    experts, weights, _ = _route(params, x2d, top_k)  # (T, k)

    C = _capacity(T, top_k, E, capacity_factor)
    # position of each (token, k) within its expert, by arrival order
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)      # (T, k, E)
    flat = onehot.reshape(T * top_k, E)
    pos_in_e = jnp.cumsum(flat, axis=0) * flat - 1            # (T*k, E)
    pos = jnp.max(pos_in_e, axis=-1)                          # (T*k,)
    e_flat = experts.reshape(T * top_k)
    keep = pos < C

    # scatter tokens into (E, C, D) buffers; dropped tokens -> row C (waste row)
    buf = jnp.zeros((E, C + 1, D), x.dtype)
    slot = jnp.where(keep, pos, C)
    src = jnp.repeat(x2d, top_k, axis=0)                      # (T*k, D)
    buf = buf.at[e_flat, slot].add(src)
    buf = constrain(buf, ("experts", None, "embed"))[:, :C, :]

    # expert FFN (swiglu), experts sharded on "model"
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = constrain(h, ("experts", None, "ffn"))
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = constrain(out_buf, ("experts", None, "embed"))

    # combine: gather each (token, k) result, weight, sum over k
    gathered = out_buf[e_flat, jnp.minimum(slot, C - 1)]      # (T*k, D)
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    gathered = gathered.reshape(T, top_k, D) * weights[..., None]
    return gathered.sum(axis=1).reshape(B, S, D)


def moe_ep_shardmap(params, x, *, top_k: int, mesh, model_axis: str = "model",
                    capacity_factor: float = 1.25):
    """Explicit expert parallelism (hillclimb variant).

    Tokens sharded over all mesh axes; experts sharded over the model
    axis.  Dispatch/combine are single ``all_to_all`` pairs instead of the
    GSPMD-derived gather/scatter collectives.
    """
    from jax.sharding import PartitionSpec as PS

    E = params["router"].shape[1]
    ep = mesh.shape[model_axis]
    assert E % ep == 0, (E, ep)
    e_local = E // ep
    B, S, D = x.shape
    batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)

    def local_fn(router, w_gate, w_up, w_down, xl):
        # xl: (b_l, s_l, D) — batch sharded over data axes, seq over model
        b_l, s_l = xl.shape[0], xl.shape[1]
        t_l = b_l * s_l
        x2d = xl.reshape(t_l, D)
        prm = {"router": router}
        experts, weights, _ = _route(prm, x2d, top_k)
        C = _capacity(t_l, top_k, E, capacity_factor)

        onehot = jax.nn.one_hot(experts, E, dtype=jnp.int32)
        flat = onehot.reshape(t_l * top_k, E)
        pos = jnp.max(jnp.cumsum(flat, axis=0) * flat - 1, axis=-1)
        e_flat = experts.reshape(t_l * top_k)
        keep = pos < C
        slot = jnp.where(keep, pos, C)

        buf = jnp.zeros((E, C + 1, D), xl.dtype)
        buf = buf.at[e_flat, slot].add(jnp.repeat(x2d, top_k, axis=0))
        buf = buf[:, :C, :].reshape(ep, e_local, C, D)
        # dispatch: tokens routed to the device owning their expert
        buf = jax.lax.all_to_all(buf, model_axis, 0, 0, tiled=False)
        # buf now (ep, e_local, C, D): rows from every source device
        h = jax.nn.silu(jnp.einsum("pecd,edf->pecf", buf, w_gate))
        h = h * jnp.einsum("pecd,edf->pecf", buf, w_up)
        out = jnp.einsum("pecf,efd->pecd", h, w_down)
        # combine: send results back to token owners
        out = jax.lax.all_to_all(out, model_axis, 0, 0, tiled=False)
        out = out.reshape(E, C, D)
        pad = jnp.zeros((E, 1, D), out.dtype)
        out = jnp.concatenate([out, pad], axis=1)
        gathered = out[e_flat, slot]
        gathered = jnp.where(keep[:, None], gathered, 0.0)
        gathered = gathered.reshape(t_l, top_k, D) * weights[..., None]
        return gathered.sum(axis=1).reshape(b_l, s_l, D)

    return jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            PS(),                      # router replicated
            PS(model_axis, None, None),
            PS(model_axis, None, None),
            PS(model_axis, None, None),
            # batch over data axes, sequence over the model axis: every
            # device owns a token shard => all_to_all is the only collective
            PS(batch_axes, model_axis, None),
        ),
        out_specs=PS(batch_axes, model_axis, None),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
