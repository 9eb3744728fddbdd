"""Mixture-of-Experts FFN (qwen2-moe: 60 routed top-4 + shared; granite-moe:
32 routed top-8).

Two dispatch implementations (both capacity-bounded, GShard-style):

* ``moe_ffn_gspmd`` — one-hot cumsum positions + scatter into an (E, C, D)
  buffer, sharding left to GSPMD (baseline; the compiler's collective choice
  for the scatter is part of the §Perf story).
* ``moe_ffn_shardmap`` — explicit expert parallelism: activations are
  replicated across the "model" axis (they already are, post-attention in a
  Megatron block), each shard dispatches *locally* to its E/tp experts and the
  combine is the same psum the TP MLP needs anyway.  No all-to-all at all.
  This reuses the capacity-bounded static-shape idiom of ``core/spmat.py``
  (token→expert dispatch is a sparse boolean matrix, DESIGN.md §4).

Expert counts are padded to a multiple of the model-axis size (60 → 64 for
qwen2-moe); padded experts get −inf router logits and zero weights.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax import shard_map
from .layers import dense


def router_topk(x, w_router, n_experts_real: int, top_k: int):
    """Returns (weights (T, K) fp32, idx (T, K) int32)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router)
    e_pad = w_router.shape[1]
    if e_pad > n_experts_real:
        pad_mask = jnp.arange(e_pad) >= n_experts_real
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    topv, topi = jax.lax.top_k(logits, top_k)
    w = jax.nn.softmax(topv, axis=-1)
    return w, topi.astype(jnp.int32)


def expert_ffn(xe, w_gate, w_up, w_down):
    """xe (E, C, D); weights (E, D, F)/(E, F, D)."""
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate.astype(xe.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(xe.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down.astype(xe.dtype))


def _dispatch_combine(x, w, idx, params, capacity: int):
    """Shared dispatch→FFN→combine given (T,K) routing. O(T·K·E) bookkeeping
    ints + (E, C, D) buffer."""
    t, d = x.shape
    k = idx.shape[1]
    e = params["w_gate"].shape[0]
    flat_e = idx.reshape(t * k)
    flat_w = w.reshape(t * k)
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # (T·K, E)
    pos = jnp.cumsum(oh, axis=0) - 1
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < capacity
    safe_e = jnp.where(keep, flat_e, e)  # dummy expert row for overflow
    safe_p = jnp.where(keep, flat_pos, 0)
    tok = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e + 1, capacity, d), x.dtype)
    buf = buf.at[safe_e, safe_p].add(x[tok])
    y_e = expert_ffn(buf[:e], params["w_gate"], params["w_up"], params["w_down"])
    # combine: gather back each assignment's expert output, weight, sum over K
    y_pad = jnp.concatenate([y_e, jnp.zeros((1, capacity, d), y_e.dtype)], 0)
    y_tok = y_pad[safe_e, safe_p] * (flat_w * keep)[:, None].astype(y_e.dtype)
    return jnp.zeros((t, d), y_e.dtype).at[tok].add(y_tok)


def moe_ffn_gspmd(
    x,  # (T, D) token-major
    params,  # router (D, E); w_gate/w_up (E, D, F); w_down (E, F, D)
    *,
    n_experts_real: int,
    top_k: int,
    capacity_factor: float = 1.25,
):
    t, d = x.shape
    e = params["w_gate"].shape[0]
    w, idx = router_topk(x, params["router"], n_experts_real, top_k)
    capacity = max(1, int(t * top_k * capacity_factor / e))
    return _dispatch_combine(x, w, idx, params, capacity)


def moe_ffn_shardmap(
    x,  # (T, D), sharded over token axes, replicated over "model"
    params,  # experts sharded over "model" on the leading E axis
    *,
    mesh,
    n_experts_real: int,
    top_k: int,
    capacity_factor: float = 1.25,
    token_axes=("data",),
    expert_axis: str = "model",
):
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape[expert_axis]
    e = params["w_gate"].shape[0]
    e_loc = e // tp

    def f(x, router, w_gate, w_up, w_down):
        t = x.shape[0]
        my = jax.lax.axis_index(expert_axis)
        w, idx = router_topk(x, router, n_experts_real, top_k)
        # keep only assignments destined to this shard's experts
        local = (idx >= my * e_loc) & (idx < (my + 1) * e_loc)
        idx_l = jnp.where(local, idx - my * e_loc, e_loc)
        w_l = jnp.where(local, w, 0.0)
        capacity = max(1, int(t * top_k * capacity_factor / e))
        p_loc = {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}
        y = _dispatch_combine(x, w_l, idx_l.astype(jnp.int32), p_loc, capacity)
        return jax.lax.psum(y, expert_axis)

    return shard_map(
        f,
        mesh=mesh,
        in_specs=(
            P(tuple(token_axes), None),
            P(),
            P(expert_axis), P(expert_axis), P(expert_axis),
        ),
        out_specs=P(tuple(token_axes), None),
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
