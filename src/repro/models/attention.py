"""Attention: chunked (flash-style) training/prefill path + split-KV decode.

* ``flash_attention`` — pure-JAX online-softmax attention, scanned over query
  and KV blocks so the S×S score matrix is never materialized (required at
  32k prefill; a 32768² f32 score buffer would be 4 GB/head).  Supports GQA,
  causal masking, and sliding windows.
* ``decode_attention`` — one-token attention over a KV cache.  When the cache
  is sequence-sharded (long contexts), ``decode_attention_sharded`` runs the
  flash-decoding split-KV merge under shard_map: each model-shard computes
  local (m, l, o) statistics over its KV slice and the merge is two psums and
  a pmax — the TPU-native analogue of FlashDecoding.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax import shard_map

NEG_INF = -1e30


def _gqa_expand(q, n_kv: int):
    """(B, S, Hq, D) -> (B, S, Hkv, G, D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def flash_attention(
    q: jnp.ndarray,  # (B, Sq, Hq, D)
    k: jnp.ndarray,  # (B, Skv, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,  # global position of q[0] (for cached prefill)
    q_block: int = 512,
    kv_block: int = 512,
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    n_qb = -(-sq // qb)
    n_kb = -(-skv // kb)
    # pad to block multiples
    q = jnp.pad(q, ((0, 0), (0, n_qb * qb - sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, n_kb * kb - skv), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, n_kb * kb - skv), (0, 0), (0, 0)))
    qr = q.reshape(b, n_qb, qb, hkv, g, d).transpose(1, 0, 3, 4, 2, 5)
    kr = k.reshape(b, n_kb, kb, hkv, d).transpose(1, 0, 3, 2, 4)
    vr = v.reshape(b, n_kb, kb, hkv, d).transpose(1, 0, 3, 2, 4)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    def q_step(_, qi_and_blk):
        qi, qblk = qi_and_blk  # qblk: (B, Hkv, G, qb, D)
        qpos = q_offset + qi * qb + jnp.arange(qb)

        @jax.checkpoint  # flash backward recomputes p; never store S² scores
        def kv_step(carry, ki_and_kv):
            m, l, acc = carry
            ki, kblk, vblk = ki_and_kv  # (B, Hkv, kb, D)
            kpos = ki * kb + jnp.arange(kb)
            s_ = jnp.einsum(
                "bhgqd,bhkd->bhgqk", qblk, kblk,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = jnp.ones((qb, kb), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            mask &= (kpos < skv)[None, :]
            s_ = jnp.where(mask[None, None, None], s_, NEG_INF)
            m2 = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.exp(s_ - m2[..., None])
            corr = jnp.exp(m - m2)
            l2 = l * corr + jnp.sum(p, axis=-1)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32,
            )
            return (m2, l2, acc2), None

        m0 = jnp.full((b, hkv, g, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, qb), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, qb, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(n_kb), kr, vr)
        )
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(qblk.dtype)

    _, outs = jax.lax.scan(
        jax.checkpoint(q_step), None, (jnp.arange(n_qb), qr)
    )
    # outs: (n_qb, B, Hkv, G, qb, D)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, n_qb * qb, hq, d)
    return out[:, :sq].astype(q.dtype)


def decode_attention(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    cur_len: jnp.ndarray,  # (B,) or scalar: valid cache length
    *,
    window: int | None = None,
) -> jnp.ndarray:
    """Single-token attention over the cache (dense; cache fits per device)."""
    b, s, hkv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    qr = q.reshape(b, hkv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s_ = jnp.einsum(
        "bhgd,bshd->bhgs", qr, k_cache, preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(s)
    cur = jnp.asarray(cur_len)
    cur = cur[:, None] if cur.ndim == 1 else cur
    mask = pos[None, :] < cur
    if window is not None:
        mask &= pos[None, :] >= cur - window
    s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def decode_attention_sharded(
    q, k_cache, v_cache, cur_len, *, mesh, seq_axis: str = "model",
    window=None,
):
    """FlashDecoding-style split-KV decode: the cache's sequence dim is
    sharded over ``seq_axis`` (batch stays sharded over the data axes); each
    shard computes local softmax statistics and the merge is pmax + two psums
    (DESIGN.md §Perf)."""
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_shards = mesh.shape[seq_axis]
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    bg, s, hkv, d = k_cache.shape
    b = bg // max(1, n_dp) if bg % max(1, n_dp) == 0 else bg
    dp_axes = dp_axes if bg % max(1, n_dp) == 0 else ()
    s_loc = s // n_shards
    hq = q.shape[2]
    g = hq // hkv
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

    def f(q, kc, vc, cur):
        idx = jax.lax.axis_index(seq_axis)
        qr = q.reshape(b, hkv, g, d)
        s_ = jnp.einsum(
            "bhgd,bshd->bhgs", qr, kc, preferred_element_type=jnp.float32
        ) * scale
        pos = idx * s_loc + jnp.arange(s_loc)
        cur2 = jnp.asarray(cur).reshape(b, 1)
        mask = pos[None, :] < cur2
        if window is not None:
            mask &= pos[None, :] >= cur2 - window
        s_ = jnp.where(mask[:, None, None, :], s_, NEG_INF)
        m_loc = jnp.max(s_, axis=-1)
        m = jax.lax.pmax(m_loc, seq_axis)
        p = jnp.exp(s_ - m[..., None])
        l = jax.lax.psum(jnp.sum(p, axis=-1), seq_axis)
        o = jax.lax.psum(
            jnp.einsum(
                "bhgs,bshd->bhgd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            ),
            seq_axis,
        )
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(b, 1, hq, d).astype(q.dtype)

    dp = dp_axes if dp_axes else None
    return shard_map(
        f,
        mesh=mesh,
        in_specs=(
            P(dp), P(dp, seq_axis), P(dp, seq_axis), P(dp),
        ),
        out_specs=P(dp),
    )(q, k_cache, v_cache, cur_len)


def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Write k/v_new (B, S_new, Hkv, D) at position ``pos`` (scalar)."""
    k_cache = jax.lax.dynamic_update_slice(
        k_cache, k_new.astype(k_cache.dtype), (0, pos, 0, 0)
    )
    v_cache = jax.lax.dynamic_update_slice(
        v_cache, v_new.astype(v_cache.dtype), (0, pos, 0, 0)
    )
    return k_cache, v_cache


def cache_update_sharded(k_cache, v_cache, k_new, v_new, pos, *, mesh,
                         seq_axis: str = "model"):
    """Owner-writes single-token cache update for a sequence-sharded cache
    (§Perf: the GSPMD dynamic_update_slice on a seq-sharded cache gathers the
    whole cache to every device; here only the owning shard writes)."""
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    bg = k_cache.shape[0]
    dp = dp_axes if bg % max(1, n_dp) == 0 else None

    def f(kc, vc, kn, vn):
        s_loc = kc.shape[1]
        idx = jax.lax.axis_index(seq_axis)
        local = pos - idx * s_loc
        owner = (local >= 0) & (local < s_loc)
        safe = jnp.clip(local, 0, s_loc - 1)
        kw = jax.lax.dynamic_update_slice(
            kc, kn.astype(kc.dtype), (0, safe, 0, 0))
        vw = jax.lax.dynamic_update_slice(
            vc, vn.astype(vc.dtype), (0, safe, 0, 0))
        kc2 = jnp.where(owner, kw, kc)
        vc2 = jnp.where(owner, vw, vc)
        return kc2, vc2

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(dp, seq_axis), P(dp, seq_axis), P(dp), P(dp)),
        out_specs=(P(dp, seq_axis), P(dp, seq_axis)),
        check_vma=False,  # owner-write: result provably consistent per shard
    )(k_cache, v_cache, k_new, v_new)
