"""ModelConfig + parameter init + train/serve step factories for all 10
assigned architectures (dense / MoE / SSM / hybrid / audio / VLM backbones).

Design notes
------------
* Layers are stacked per *period slot* and iterated with ``lax.scan`` +
  ``jax.checkpoint`` — one lowered layer body regardless of depth (compile
  time at 512 fake devices) and remat'ed activations (memory at 4k×256).
  gemma3's 5:1 local:global pattern makes the period 6; everything else is 1.
* Cross-entropy is token-chunked (scan + checkpoint) so the (tokens, vocab)
  logits are never materialized (gemma3's 262k vocab at 1M train tokens would
  be ≳0.5 TB).
* Vocab sizes are padded to multiples of 256 so the unembed shards evenly on
  a 16-wide model axis; padded logits are masked out of the loss.
* MoE expert counts are padded to a multiple of the model axis (60→64).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from jax import shard_map
from .attention import (
    cache_update,
    decode_attention,
    decode_attention_sharded,
    flash_attention,
)
from .layers import apply_rope, dense, init_dense, rms_norm, rope_freqs
from .moe import moe_ffn_gspmd, moe_ffn_shardmap
from .ssm import SSMState, mamba2_forward, mamba2_params_shapes


def _pad_to(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _dp_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _csc(x, mesh, *spec):
    """with_sharding_constraint that silently drops axes which don't divide
    the dimension (tiny smoke configs, gemma3's 8 heads on a 16-wide model
    axis, batch=1 long-context cells...)."""
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P

    clean = []
    for i, ax in enumerate(spec):
        if ax is None or i >= x.ndim:
            clean.append(None)
            continue
        axes = (ax,) if isinstance(ax, str) else tuple(ax)
        sz = 1
        for a in axes:
            if a not in mesh.axis_names:
                sz = 0
                break
            sz *= mesh.shape[a]
        clean.append(ax if sz and x.shape[i] % sz == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*clean)))


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    d_head: Optional[int] = None
    qk_norm: bool = False
    rope_theta: float = 1e4
    sliding_window: Optional[int] = None  # window for local layers
    local_global_period: int = 1  # period-slot grouping (scan body width)
    local_global_every: int = 0  # gemma3: every 6th layer is global (5:1)
    rope_theta_local: float = 1e4  # gemma3: local layers use 10k theta
    mlp_type: str = "swiglu"  # swiglu | gelu | geglu | none
    # moe
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    d_ff_shared: int = 0
    # ssm
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    conv_width: int = 4
    # hybrid (hymba): attn ∥ ssm in every block; these layers are global attn
    hybrid_global_layers: tuple = ()
    frontend: str = "token"  # token | embed (audio/vlm stub)
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    moe_impl: str = "shardmap"  # shardmap | gspmd
    ce_chunk: int = 1024
    ssd_chunk: int = 128
    ssd_bf16: bool = False  # §Perf: bf16 SSD intra-chunk buffers
    bf16_grad_activations: bool = False  # §Perf: bf16 activation cotangents
    batch_over_model: bool = False  # §Perf: SSM/hybrid shard batch over model
    sharded_cache_update: bool = False  # §Perf: owner-writes decode cache
    decode_unroll: bool = False  # §Perf: unroll decode layers (in-place cache)

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        return _pad_to(self.vocab_size, 256)

    @property
    def n_experts_padded(self) -> int:
        return _pad_to(self.n_experts, 16) if self.n_experts else 0

    @property
    def period(self) -> int:
        return self.local_global_period

    @property
    def n_periods(self) -> int:
        assert self.n_layers % self.period == 0
        return self.n_layers // self.period

    def slot_kind(self, slot: int) -> str:
        """Layer kind for period slot (gemma3: slots 0-4 local, 5 global)."""
        if self.family == "ssm":
            return "ssm"
        if self.family == "hybrid":
            return "hybrid"
        if self.period > 1:
            return "attn_local" if slot < self.period - 1 else "attn"
        if self.sliding_window is not None and self.period == 1:
            return "attn_local"
        return "attn"

    def param_count(self) -> int:
        """Analytic parameter count (true vocab)."""
        d, f = self.d_model, self.d_ff
        hq, hkv, dh = self.n_heads, self.n_kv_heads, self.head_dim
        per = 0
        if self.family in ("dense", "moe", "audio", "vlm", "hybrid"):
            per += d * (hq * dh) + 2 * d * (hkv * dh) + (hq * dh) * d
        if self.family == "ssm" or self.family == "hybrid":
            dims = mamba2_params_shapes(
                d, expand=self.ssm_expand, headdim=self.ssm_headdim,
                state=self.ssm_state, conv_width=self.conv_width,
            )
            per += d * dims["in_features"] + dims["d_inner"] * d
            per += dims["conv_width"] * dims["conv_dim"]
        if self.family == "moe":
            per += d * self.n_experts  # router
            per += self.n_experts * 3 * d * self.d_ff_expert
            if self.d_ff_shared:
                per += 3 * d * self.d_ff_shared
        elif self.mlp_type == "gelu" and f:
            per += 2 * d * f
        elif f:
            per += 3 * d * f
        total = self.n_layers * per + 2 * self.vocab_size * d
        return total

    def active_param_count(self) -> int:
        """Activated params per token (= param_count for non-MoE)."""
        if self.family != "moe":
            return self.param_count()
        d = self.d_model
        per_moe_full = self.n_experts * 3 * d * self.d_ff_expert
        per_moe_act = self.top_k * 3 * d * self.d_ff_expert
        return self.param_count() - self.n_layers * (per_moe_full - per_moe_act)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _init_attn(key, cfg: ModelConfig):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_dense(ks[0], d, hq * dh),
        "wk": init_dense(ks[1], d, hkv * dh),
        "wv": init_dense(ks[2], d, hkv * dh),
        "wo": init_dense(ks[3], hq * dh, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((dh,), jnp.float32)
        p["k_norm"] = jnp.zeros((dh,), jnp.float32)
    return p


def _init_mlp(key, cfg: ModelConfig, d_ff: int):
    ks = jax.random.split(key, 3)
    if cfg.mlp_type == "gelu":
        return {
            "w_in": init_dense(ks[0], cfg.d_model, d_ff),
            "w_out": init_dense(ks[1], d_ff, cfg.d_model),
        }
    return {
        "w_gate": init_dense(ks[0], cfg.d_model, d_ff),
        "w_up": init_dense(ks[1], cfg.d_model, d_ff),
        "w_down": init_dense(ks[2], d_ff, cfg.d_model),
    }


def _init_moe(key, cfg: ModelConfig):
    e = cfg.n_experts_padded
    fe = cfg.d_ff_expert
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    real = jnp.arange(e) < cfg.n_experts
    mask = real[:, None, None].astype(jnp.float32)

    def ew(k, sh):
        return (jax.random.normal(k, sh, jnp.float32) / jnp.sqrt(sh[1])) * mask

    p = {
        "router": init_dense(ks[0], d, e),
        "w_gate": ew(ks[1], (e, d, fe)),
        "w_up": ew(ks[2], (e, d, fe)),
        "w_down": ew(ks[3], (e, fe, d)),
    }
    if cfg.d_ff_shared:
        p["shared"] = _init_mlp(jax.random.fold_in(key, 7), cfg, cfg.d_ff_shared)
    return p


def _init_ssm(key, cfg: ModelConfig):
    dims = mamba2_params_shapes(
        cfg.d_model, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
        state=cfg.ssm_state, conv_width=cfg.conv_width,
    )
    ks = jax.random.split(key, 3)
    h = dims["n_heads"]
    return {
        "in_proj": init_dense(ks[0], cfg.d_model, dims["in_features"]),
        "out_proj": init_dense(ks[1], dims["d_inner"], cfg.d_model),
        "conv_w": jax.random.normal(
            ks[2], (dims["conv_width"], dims["conv_dim"]), jnp.float32
        ) * 0.2,
        "conv_b": jnp.zeros((dims["conv_dim"],), jnp.float32),
        "dt_bias": jnp.full((h,), -2.0, jnp.float32),
        "a_log": jnp.zeros((h,), jnp.float32),
        "d_skip": jnp.ones((h,), jnp.float32),
        "norm": jnp.zeros((dims["d_inner"],), jnp.float32),
    }


def _init_slot(key, cfg: ModelConfig, slot: int):
    kind = cfg.slot_kind(slot)
    ks = jax.random.split(key, 4)
    p: dict = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind in ("attn", "attn_local"):
        p["attn"] = _init_attn(ks[0], cfg)
    elif kind == "ssm":
        p["ssm"] = _init_ssm(ks[0], cfg)
    elif kind == "hybrid":
        p["attn"] = _init_attn(ks[0], cfg)
        p["ssm"] = _init_ssm(ks[1], cfg)
        p["bnorm_a"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["bnorm_s"] = jnp.zeros((cfg.d_model,), jnp.float32)
    if cfg.family == "moe":
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["moe"] = _init_moe(ks[2], cfg)
    elif cfg.d_ff and cfg.mlp_type != "none" and cfg.family != "ssm":
        p["ln2"] = jnp.zeros((cfg.d_model,), jnp.float32)
        p["mlp"] = _init_mlp(ks[2], cfg, cfg.d_ff)
    return p


def init_params(cfg: ModelConfig, key) -> Any:
    ks = jax.random.split(key, 3)
    params: dict = {"final_norm": jnp.zeros((cfg.d_model,), jnp.float32)}
    if cfg.frontend == "token":
        params["embed"] = (
            jax.random.normal(ks[0], (cfg.vocab_padded, cfg.d_model), jnp.float32)
            * 0.02
        )
    params["unembed"] = init_dense(ks[1], cfg.d_model, cfg.vocab_padded)

    def slot_stack(slot):
        def one(i):
            k = jax.random.fold_in(ks[2], slot * 10007 + i)
            return _init_slot(k, cfg, slot)

        leaves = [one(i) for i in range(cfg.n_periods)]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)

    params["slots"] = [slot_stack(s) for s in range(cfg.period)]
    return params


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _attn_forward(x, p, cfg: ModelConfig, *, window, positions, kv=None,
                  cache=None, pos=None, mesh=None, seq_shards: int = 1,
                  theta=None):
    """x (B, S, D). Returns (out, (k, v) or updated cache)."""
    b, s, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dp = _dp_axes(mesh) if mesh is not None else None
    q = dense(x, p["wq"]).reshape(b, s, hq, dh)
    k = dense(x, p["wk"]).reshape(b, s, hkv, dh)
    v = dense(x, p["wv"]).reshape(b, s, hkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    cos, sin = rope_freqs(positions, dh,
                          cfg.rope_theta if theta is None else theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    g = hq // hkv
    if mesh is not None:
        q = _csc(q, mesh, dp, None, "model", None)
    if cache is None:
        # GQA via kv-repeat: keeps the head dim shardable over "model"
        # (splitting Hq into (Hkv, G) would break TP whenever Hkv < tp).
        pass
        kf = jnp.repeat(k, g, axis=2) if g > 1 else k
        vf = jnp.repeat(v, g, axis=2) if g > 1 else v
        if mesh is not None:
            # kv: gathered over seq (every q shard attends the full KV) and
            # replicated over heads — head-sharding Hkv < tp would force the
            # SPMD "involuntary full remat" path
            kf = _csc(kf, mesh, dp, None, None, None)
            vf = _csc(vf, mesh, dp, None, None, None)
        out = flash_attention(q, kf, vf, causal=True, window=window)
        new_cache = None
    else:
        if (s == 1 and mesh is not None and seq_shards > 1
                and cfg.sharded_cache_update):
            from .attention import cache_update_sharded

            kc, vc = cache_update_sharded(
                cache["k"], cache["v"], k, v, pos, mesh=mesh)
        else:
            kc, vc = cache_update(cache["k"], cache["v"], k, v, pos)
        cur = pos + s
        if s == 1:
            if mesh is not None and seq_shards > 1:
                out = decode_attention_sharded(
                    q, kc, vc, jnp.full((b,), cur), mesh=mesh, window=window
                )
            else:
                out = decode_attention(q, kc, vc, jnp.full((b,), cur),
                                       window=window)
        else:  # prefill into cache
            kf = jnp.repeat(k, g, axis=2) if g > 1 else k
            vf = jnp.repeat(v, g, axis=2) if g > 1 else v
            if mesh is not None:
                kf = _csc(kf, mesh, dp, None, "model", None)
                vf = _csc(vf, mesh, dp, None, "model", None)
            out = flash_attention(q, kf, vf, causal=True, window=window,
                                  q_offset=pos)
        new_cache = {"k": kc, "v": vc}
    out = dense(out.reshape(b, s, hq * dh), p["wo"])
    if mesh is not None:
        out = _csc(out, mesh, dp, None, None)
    return out, new_cache


def _mlp_forward(x, p, cfg: ModelConfig, mesh=None):
    dp = _dp_axes(mesh) if mesh is not None else None
    if cfg.mlp_type == "gelu":
        h = dense(x, p["w_in"])
        h = _csc(h, mesh, dp, None, "model")
        return dense(jax.nn.gelu(h), p["w_out"])
    act = jax.nn.gelu if cfg.mlp_type == "geglu" else jax.nn.silu
    g = act(_csc(dense(x, p["w_gate"]), mesh, dp, None, "model"))
    u = _csc(dense(x, p["w_up"]), mesh, dp, None, "model")
    return dense(g * u, p["w_down"])


def _moe_forward(x, p, cfg: ModelConfig, mesh=None):
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    if cfg.moe_impl == "shardmap" and mesh is not None:
        token_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        y = moe_ffn_shardmap(
            xt, p, mesh=mesh, n_experts_real=cfg.n_experts, top_k=cfg.top_k,
            token_axes=token_axes,
        )
    else:
        y = moe_ffn_gspmd(
            xt, p, n_experts_real=cfg.n_experts, top_k=cfg.top_k
        )
    y = y.reshape(b, s, d)
    if "shared" in p:
        y = y + _mlp_forward(x, p["shared"], cfg, mesh=mesh)
    return y


def _slot_forward(x, p, cfg: ModelConfig, slot: int, *, positions, cache=None,
                  pos=None, mesh=None, seq_shards: int = 1, layer_idx=None):
    kind = cfg.slot_kind(slot)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if mesh is not None:
        # pin the SP layout on the bf16 norm OUTPUT: otherwise GSPMD hoists
        # the seq all-gather before rms_norm's final cast and moves f32
        h = _csc(h, mesh, _resid_batch_axes(cfg, mesh), _resid_seq_axis(cfg),
                 None)
    new_cache = cache
    if kind in ("attn", "attn_local"):
        window = cfg.sliding_window if kind == "attn_local" else None
        theta = cfg.rope_theta
        if cfg.local_global_every and window is not None and layer_idx is not None:
            # gemma3 5:1 pattern as a traced switch (34 layers, one scan body)
            every = cfg.local_global_every
            is_global = (layer_idx % every) == (every - 1)
            window = jnp.where(is_global, jnp.int32(2**30), window)
            theta = jnp.where(is_global, cfg.rope_theta, cfg.rope_theta_local)
        a, new_cache = _attn_forward(
            h, p["attn"], cfg, window=window, positions=positions,
            cache=cache, pos=pos, mesh=mesh, seq_shards=seq_shards,
            theta=theta,
        )
        if mesh is not None:
            a = _csc(a, mesh, _resid_batch_axes(cfg, mesh),
                     _resid_seq_axis(cfg), None)
        x = x + a
    elif kind == "ssm":
        state = None if cache is None else SSMState(h=cache["h"], conv=cache["conv"])
        a, st = mamba2_forward(h, p["ssm"], cfg, state=state,
                               chunk=cfg.ssd_chunk, mesh=mesh)
        x = x + a
        new_cache = None if cache is None else {"h": st.h, "conv": st.conv}
    elif kind == "hybrid":
        # hymba: parallel attn + ssm heads; global attn on designated layers
        # (window passed as a traced scalar so the scanned body stays uniform)
        window = cfg.sliding_window
        if (
            window is not None
            and layer_idx is not None
            and cfg.hybrid_global_layers
        ):
            is_global = jnp.any(
                layer_idx == jnp.asarray(cfg.hybrid_global_layers)
            )
            window = jnp.where(is_global, jnp.int32(2**30), window)
        att_cache = None if cache is None else cache["attn"]
        a, ac = _attn_forward(
            h, p["attn"], cfg, window=window, positions=positions,
            cache=att_cache, pos=pos, mesh=mesh, seq_shards=seq_shards,
        )
        state = None if cache is None else SSMState(
            h=cache["ssm"]["h"], conv=cache["ssm"]["conv"]
        )
        m, st = mamba2_forward(h, p["ssm"], cfg, state=state,
                               chunk=cfg.ssd_chunk, mesh=mesh)
        out = 0.5 * (
            rms_norm(a, p["bnorm_a"], cfg.norm_eps)
            + rms_norm(m, p["bnorm_s"], cfg.norm_eps)
        )
        x = x + out
        new_cache = (
            None if cache is None
            else {"attn": ac, "ssm": {"h": st.h, "conv": st.conv}}
        )
    if "mlp" in p or "moe" in p:
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        if mesh is not None:
            h2 = _csc(h2, mesh, _resid_batch_axes(cfg, mesh),
                      _resid_seq_axis(cfg), None)
        if "mlp" in p:
            m_out = _mlp_forward(h2, p["mlp"], cfg, mesh=mesh)
        else:
            m_out = _moe_forward(h2, p["moe"], cfg, mesh=mesh)
        if mesh is not None:
            # reduce-scatter the bf16 block output (not a later f32 upcast)
            m_out = _csc(m_out, mesh, _resid_batch_axes(cfg, mesh),
                         _resid_seq_axis(cfg), None)
        x = x + m_out
    if mesh is not None:
        x = _csc(x, mesh, _resid_batch_axes(cfg, mesh), _resid_seq_axis(cfg),
                 None)
    if cfg.bf16_grad_activations:
        x = _bf16_grad_barrier(x)
    return x, new_cache


def _resid_seq_axis(cfg: ModelConfig):
    """Megatron-style sequence parallelism: the residual stream between
    blocks is sharded over "model" along the sequence for attention-family
    archs (norms/residuals run on 1/tp of the tokens; remat carries shrink
    tp×).  SSM/hybrid keep a replicated stream — the SSD chunk scan is
    sequential along S and must not cross shard boundaries."""
    return None if cfg.family in ("ssm", "hybrid") else "model"


@jax.custom_vjp
def _bf16_grad_barrier(x):
    """Identity forward; casts the cotangent to bf16 (then back to x's
    dtype).  Placed at block boundaries so backward activation collectives
    (SP all-gathers / TP reduces of the residual cotangent) move bf16
    instead of f32 — §Perf for collective-bound train cells."""
    return x


def _bgb_fwd(x):
    # residuals must be jax types: carry the dtype via a 0-size array
    return x, jnp.zeros((0,), x.dtype)


def _bgb_bwd(res, g):
    return (g.astype(jnp.bfloat16).astype(res.dtype),)


_bf16_grad_barrier.defvjp(_bgb_fwd, _bgb_bwd)


def _resid_batch_axes(cfg: ModelConfig, mesh):
    """SSM/hybrid §Perf option: treat "model" as a second data axis for the
    residual stream (SSD TP gives little; B/dev shrinks tp×)."""
    dp = _dp_axes(mesh)
    if cfg.batch_over_model and cfg.family in ("ssm", "hybrid"):
        return dp + ("model",)
    return dp


def forward(params, batch, cfg: ModelConfig, *, mesh=None, caches=None,
            pos=None, seq_shards: int = 1):
    """Full stack. batch: {"tokens": (B,S) int32} or {"embeddings": (B,S,D)}.
    Returns (hidden (B,S,D), new_caches)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.frontend == "token":
        x = params["embed"][batch["tokens"]].astype(dt)
    else:
        x = batch["embeddings"].astype(dt)
    if mesh is not None:
        x = _csc(x, mesh, _resid_batch_axes(cfg, mesh), _resid_seq_axis(cfg),
                 None)
    b, s, _ = x.shape
    base = 0 if pos is None else pos
    positions = base + jnp.arange(s)

    def body(carry, xs):
        x = carry
        lp = xs["params"]
        lc = xs.get("cache")
        pidx = xs["pidx"]
        new_c = []
        for slot in range(cfg.period):
            sp = lp[slot]
            sc = None if lc is None else lc[slot]
            x, nc = _slot_forward(
                x, sp, cfg, slot, positions=positions, cache=sc, pos=pos,
                mesh=mesh, seq_shards=seq_shards,
                layer_idx=pidx * cfg.period + slot,
            )
            new_c.append(nc)
        out_c = None if lc is None else new_c
        return x, out_c

    if caches is not None and cfg.decode_unroll and s == 1:
        # §Perf (decode): python-unrolled layers write the cache stack with
        # .at[i].set — the whole stack aliases the donated input instead of
        # being re-materialized by a scan's ys buffers.
        new_caches = caches
        for i in range(cfg.n_periods):
            lp = [jax.tree.map(lambda a: a[i], sp) for sp in params["slots"]]
            for slot in range(cfg.period):
                sc = jax.tree.map(lambda a: a[i], new_caches[slot])
                x, nc = _slot_forward(
                    x, lp[slot], cfg, slot, positions=positions, cache=sc,
                    pos=pos, mesh=mesh, seq_shards=seq_shards,
                    layer_idx=i * cfg.period + slot,
                )
                new_caches[slot] = jax.tree.map(
                    lambda full, upd: full.at[i].set(upd),
                    new_caches[slot], nc,
                )
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, new_caches

    xs = {
        "params": params["slots"],
        "pidx": jnp.arange(cfg.n_periods),
    }
    if caches is not None:
        xs["cache"] = caches
    body_fn = jax.checkpoint(body) if caches is None else body
    x, new_caches = jax.lax.scan(body_fn, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches


def chunked_ce_loss(x, labels, w_unembed, cfg: ModelConfig, *, mesh=None):
    """Sequence-chunked, vocab-parallel cross entropy.  x (B,S,D); labels
    (B,S) int32 (−1 = ignore).  Never materializes (B·S, vocab): the scan
    walks S-chunks (batch stays dp-sharded, the scanned dim is unsharded)
    and the per-chunk logits are vocab-sharded over "model" so logsumexp
    reduces with one small psum — Megatron-style vocab-parallel CE."""
    b, s, d = x.shape
    dp = _dp_axes(mesh) if mesh is not None else None
    cs = min(cfg.ce_chunk, s)
    n_chunks = -(-s // cs)
    pad = n_chunks * cs - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xc = jnp.moveaxis(x.reshape(b, n_chunks, cs, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n_chunks, cs), 1, 0)

    if mesh is not None:
        # explicit vocab-parallel CE (shard_map): GSPMD's own partitioning of
        # the logit einsum kept materializing/gathering full-vocab logits
        # (~10 GB/device at 152k vocab); making the max/sum/gold reductions
        # explicit pins the wire traffic to three (B, cs) psums per chunk.
        from jax.sharding import PartitionSpec as P

        v_loc = cfg.vocab_padded // mesh.shape["model"]

        def ce_local(xi, li, w):
            my = jax.lax.axis_index("model")
            logits = jnp.einsum(
                "btd,dv->btv", xi, w.astype(xi.dtype),
                preferred_element_type=jnp.float32,
            )
            vids = my * v_loc + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 2
            )
            logits = jnp.where(vids < cfg.vocab_size, logits, -1e30)
            # pmax has no JVP rule; gather the 16 per-shard maxima instead
            m_loc = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
            m = jnp.max(jax.lax.all_gather(m_loc, "model", axis=0), axis=0)
            se = jax.lax.psum(
                jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), "model"
            )
            lse = m + jnp.log(se)
            gold = jax.lax.psum(
                jnp.sum(jnp.where(vids == li[..., None], logits, 0.0), -1),
                "model",
            )
            wt = (li >= 0).astype(jnp.float32)
            loss = jnp.sum((lse - gold) * wt)
            cnt = jnp.sum(wt)
            loss = jax.lax.psum(loss, dp) if dp else loss
            cnt = jax.lax.psum(cnt, dp) if dp else cnt
            return loss, cnt

        # check_vma=False: lse/gold are psummed over "model" so loss is
        # provably model-invariant, but the vma tracker marks the all-gathered
        # max as varying and can't see the invariance.
        # repro: noqa[R001] — built at trace time of the jitted train step
        # (assigned and consumed inside one trace), not per eager call.
        ce_sm = shard_map(
            ce_local,
            mesh=mesh,
            in_specs=(P(dp), P(dp), P(None, "model")),
            out_specs=(P(), P()),
            check_vma=False,
        )

        @jax.checkpoint
        def ce_chunk(carry, inp):
            xi, li = inp
            loss, cnt = ce_sm(xi, li, w_unembed)
            return (carry[0] + loss, carry[1] + cnt), None

    else:

        @jax.checkpoint
        def ce_chunk(carry, inp):
            xi, li = inp  # (B, cs, D), (B, cs)
            logits = jnp.einsum(
                "btd,dv->btv", xi, w_unembed.astype(xi.dtype),
                preferred_element_type=jnp.float32,
            )
            vids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(vids < cfg.vocab_size, logits, -1e30)
            lse = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.sum(
                jnp.where(vids == li[..., None], logits, 0.0), axis=-1
            )
            w = (li >= 0).astype(jnp.float32)
            loss = jnp.sum((lse - gold) * w)
            return (carry[0] + loss, carry[1] + jnp.sum(w)), None

    (tot, cnt), _ = jax.lax.scan(ce_chunk, (0.0, 0.0), (xc, lc))
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params, batch, cfg: ModelConfig, *, mesh=None):
    x, _ = forward(params, batch, cfg, mesh=mesh)
    if mesh is not None:
        # leave sequence parallelism before the loss: the CE scan chunks the
        # seq dim, which must not stay sharded (scan slices it)
        x = _csc(x, mesh, _dp_axes(mesh), None, None)
    if cfg.bf16_grad_activations:
        # The CE backward emits an f32 x-cotangent; the backward layer-scan
        # carries ONE dtype for all iterations, so without this cast the f32
        # infects all n_layers of backward activation collectives (in-body
        # barriers get promoted away by carry-dtype unification).
        x = _bf16_grad_barrier(x)
    return chunked_ce_loss(x, batch["labels"], params["unembed"], cfg,
                           mesh=mesh)


# ---------------------------------------------------------------------------
# Step factories
# ---------------------------------------------------------------------------


def make_train_step(cfg: ModelConfig, optimizer, *, mesh=None,
                    mixed_precision: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).  ``optimizer`` is
    a repro.optim object with init/update.  ``mixed_precision`` keeps f32
    master params in the state but computes (and therefore FSDP-gathers and
    grad-reduces) in bf16 — §Perf optimization for collective-bound cells."""

    def compute_loss(p, batch):
        if mixed_precision:
            p = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if x.dtype == jnp.float32 else x, p)
        return loss_fn(p, batch, cfg, mesh=mesh)

    def train_step(state, batch):
        params, opt_state, step = state
        loss, grads = jax.value_and_grad(
            lambda p: compute_loss(p, batch)
        )(params)
        updates, opt_state = optimizer.update(grads, opt_state, params, step)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        gnorm = jnp.sqrt(
            sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
        )
        return (params, opt_state, step + 1), {"loss": loss, "grad_norm": gnorm}

    return train_step


def init_cache(cfg: ModelConfig, batch_size: int, max_len: int, dtype=None):
    """Per-period-slot stacked caches."""
    dt = dtype or jnp.dtype(cfg.dtype)
    npd = cfg.n_periods
    hkv, dh = cfg.n_kv_heads, cfg.head_dim

    def attn_cache():
        return {
            "k": jnp.zeros((npd, batch_size, max_len, hkv, dh), dt),
            "v": jnp.zeros((npd, batch_size, max_len, hkv, dh), dt),
        }

    def ssm_cache():
        dims = mamba2_params_shapes(
            cfg.d_model, expand=cfg.ssm_expand, headdim=cfg.ssm_headdim,
            state=cfg.ssm_state, conv_width=cfg.conv_width,
        )
        return {
            "h": jnp.zeros(
                (npd, batch_size, dims["n_heads"], cfg.ssm_state,
                 dims["d_inner"] // dims["n_heads"]),
                jnp.float32,
            ),
            "conv": jnp.zeros(
                (npd, batch_size, cfg.conv_width - 1, dims["conv_dim"]), dt
            ),
        }

    caches = []
    for slot in range(cfg.period):
        kind = cfg.slot_kind(slot)
        if kind in ("attn", "attn_local"):
            caches.append(attn_cache())
        elif kind == "ssm":
            caches.append(ssm_cache())
        else:  # hybrid
            caches.append({"attn": attn_cache(), "ssm": ssm_cache()})
    return caches


def make_serve_step(cfg: ModelConfig, *, mesh=None, seq_shards: int = 1):
    """Returns serve_step(params, caches, tokens, pos) -> (logits, caches):
    one decode step with a KV/SSM cache at position ``pos``."""

    def serve_step(params, caches, batch, pos):
        x, new_caches = forward(
            params, batch, cfg, mesh=mesh, caches=caches, pos=pos,
            seq_shards=seq_shards,
        )
        # only the final token's logits; full (tiny) vocab head is fine at S=1
        logits = jnp.einsum(
            "bd,dv->bv", x[:, -1], params["unembed"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits, new_caches

    return serve_step


def make_prefill_step(cfg: ModelConfig, *, mesh=None):
    def prefill(params, caches, batch):
        x, new_caches = forward(
            params, batch, cfg, mesh=mesh, caches=caches, pos=0
        )
        logits = jnp.einsum(
            "bd,dv->bv", x[:, -1], params["unembed"].astype(x.dtype),
            preferred_element_type=jnp.float32,
        )
        return logits, new_caches

    return prefill
