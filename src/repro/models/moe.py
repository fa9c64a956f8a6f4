"""Mixture-of-Experts FFN (dbrx / granite / jamba) with sort-based dispatch.

Dispatch is capacity-bucketed (Switch-style) so all shapes are static and
FLOPs stay proportional to *active* experts: tokens are argsorted by expert id,
each expert keeps at most ``capacity`` tokens, the rest are dropped (their
combine weight is zero, residual passes through).  Logical sharding:
``expert`` -> EP over the model axis when num_experts divides it (dbrx 16/16),
otherwise falls back and ``expert_ff`` TP-shards each expert's hidden dim
(granite: 40 experts, d_ff 512/16).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constraint
from repro.models import layers as L
from repro.models.layers import PD

CAPACITY_FACTOR = 1.25


def moe_defs(cfg, d_ff=None):
    d, f, e = cfg.d_model, d_ff or cfg.d_ff, cfg.num_experts
    return {
        "router": PD((d, e), ("embed", None)),
        "w1": PD((e, d, f), ("expert", "embed", "expert_ff")),
        "w3": PD((e, d, f), ("expert", "embed", "expert_ff")),
        "w2": PD((e, f, d), ("expert", "expert_ff", "embed")),
    }


def capacity(num_tokens, cfg):
    c = int(num_tokens * cfg.experts_per_token / cfg.num_experts * CAPACITY_FACTOR)
    # round to 64 so the capacity dim stays shardable over dp(+tp) axes; the
    # logical rules degrade gracefully (drop axes) when it does not divide.
    return max(64, -(-c // 64) * 64)


def _dispatch(x, router, cfg, C):
    """Local sort-based dispatch.  x [T,D] -> (xe [E,C,D], combine closure, aux).

    Tokens are argsorted by expert id and bucketed with fixed capacity C; the
    scatter uses drop-mode out-of-range indices so no +1 pad rows are needed.
    """
    T, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    probs = jax.nn.softmax((x @ router).astype(jnp.float32), axis=-1)  # [T,E]
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # load-balance aux loss (Switch): E * sum_e frac_tokens_e * mean_prob_e
    me = probs.mean(0)
    ce = jnp.zeros(E).at[top_e.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    flat_e = top_e.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_w = top_w.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros(E, jnp.int32).at[se].add(1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * K) - offsets[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)          # E*C -> dropped

    buf_tok = jnp.zeros((E * C,), jnp.int32).at[slot].set(
        jnp.where(keep, st, 0), mode="drop").reshape(E, C)
    xe = jnp.take(x, buf_tok, axis=0, mode="clip")            # [E, C, D]

    buf_w = jnp.zeros((E * C,), flat_w.dtype).at[slot].set(
        jnp.where(keep, sw, 0.0), mode="drop")
    buf_src = jnp.full((E * C,), T, jnp.int32).at[slot].set(
        jnp.where(keep, st, T), mode="drop")

    def combine(ye):
        out = jnp.zeros((T, D), jnp.float32)
        upd = ye.reshape(E * C, D).astype(jnp.float32) * buf_w[:, None]
        return out.at[buf_src].add(upd, mode="drop").astype(x.dtype)

    return xe, combine, aux


def _expert_ffn(xe, w1, w3, w2):
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w1))
    g = g * jnp.einsum("ecd,edf->ecf", xe, w3)
    return jnp.einsum("ecf,efd->ecd", g, w2)


def moe_fwd(p, h, cfg):
    """h [B,S,D] -> ([B,S,D], aux_loss).

    Distribution: GSPMD cannot partition the data-dependent dispatch
    gather/scatter (it replicates [T,D]-sized f32 buffers per device —
    measured 6 GB x13 for dbrx train), so under an active mesh the MoE runs in
    ``jax.shard_map``: dispatch is *local* to each data shard, then either
      * EP (num_experts % model == 0, dbrx/jamba): all-to-all over the model
        axis moves capacity buckets to their expert's device and back, or
      * expert-TP (granite): every device holds a d_ff shard of every expert;
        partial results psum over the model axis.
    Without a mesh (unit tests) the same dispatch runs locally in full.
    """
    from repro.distributed.sharding import active_mesh
    mesh = active_mesh()
    B, S, D = h.shape
    if mesh is None:
        xe, combine, aux = _dispatch(
            h.reshape(B * S, D), p["router"], cfg, capacity(B * S, cfg))
        return combine(_expert_ffn(xe, p["w1"], p["w3"], p["w2"])).reshape(B, S, D), aux

    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in ("pod", "data") if a in axes)
    while dp and B % _size(axes, dp) != 0:
        dp = dp[1:]          # long_500k decode (B=1): replicate over data
    ep = axes.get("model", 1)
    use_ep = cfg.num_experts % ep == 0
    # EP wants tokens sharded over the model axis too (each device dispatches
    # a distinct token slice; the all-to-all then carries no duplicates).
    # Expert-TP instead *requires* token replication over model (each device
    # holds a d_ff shard of every expert; psum adds the partial outputs).
    seq_model = "model" if (use_ep and S % ep == 0) else None
    P_ = jax.sharding.PartitionSpec
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    S_local = S // (ep if seq_model else 1)
    T_local = (B // max(_size(axes, dp), 1)) * S_local
    C = capacity(T_local, cfg)
    E = cfg.num_experts

    def body(hl, router, w1, w3, w2):
        Bl, Sl = hl.shape[0], hl.shape[1]
        x = hl.reshape(Bl * Sl, D)
        xe, combine, aux = _dispatch(x, router, cfg, C)
        if use_ep:
            # [E, C, D] -> [E/ep, C*ep, D]: capacity buckets travel to experts
            xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=1,
                                    tiled=True)
            ye = _expert_ffn(xe, w1, w3, w2)
            ye = jax.lax.all_to_all(ye, "model", split_axis=1, concat_axis=0,
                                    tiled=True)
        else:
            # expert-TP: local d_ff shard of every expert, psum partial outputs
            ye = jax.lax.psum(_expert_ffn(xe, w1, w3, w2), "model")
        out = combine(ye).reshape(Bl, Sl, D)
        for ax in dp:
            aux = jax.lax.pmean(aux, ax)
        if seq_model:
            aux = jax.lax.pmean(aux, "model")
        return out, aux

    if use_ep:
        w13_spec = w2_spec = P_("model", None, None)
    else:  # w1/w3 are [E, D, F], w2 is [E, F, D]: shard the F dim of each
        w13_spec = P_(None, None, "model")
        w2_spec = P_(None, "model", None)
    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P_(dp_spec, seq_model, None), P_(None, None),
                  w13_spec, w13_spec, w2_spec),
        out_specs=(P_(dp_spec, seq_model, None), P_()),
        check_vma=False,
    )(h, p["router"], p["w1"], p["w3"], p["w2"])
    return out, aux


def _size(axes, names):
    n = 1
    for a in names:
        n *= axes[a]
    return n


# ---------------------------------------------------------------------------
# MoE transformer (dbrx / granite): attention + MoE FFN blocks
# ---------------------------------------------------------------------------

def block_defs(cfg):
    return {
        "attn_norm": PD((cfg.d_model,), ("embed",), "ones"),
        "attn": L.attention_defs(cfg),
        "mlp_norm": PD((cfg.d_model,), ("embed",), "ones"),
        "moe": moe_defs(cfg),
    }


def model_defs(cfg):
    from repro.models.transformer import stacked
    return {
        "embed": L.embed_defs(cfg),
        "blocks": stacked(block_defs(cfg), cfg.num_layers),
        "final_norm": PD((cfg.d_model,), ("embed",), "ones"),
    }


def block_fwd(p, h, cfg, positions):
    p = L.fsdp_gather(p, block_defs(cfg))
    a, _ = L.attention_fwd(p["attn"], L.rmsnorm(h, p["attn_norm"], cfg.norm_eps),
                           cfg, positions=positions)
    h = h + a
    m, aux = moe_fwd(p["moe"], L.rmsnorm(h, p["mlp_norm"], cfg.norm_eps), cfg)
    return constraint(h + m, ("batch", "seq_sp", None)), aux


def forward(params, tokens, cfg):
    h = L.embed_fwd(params["embed"], tokens, cfg.jnp_dtype)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def body(carry, bp):
        h, aux = carry
        h, a = block_fwd(bp, h, cfg, positions)
        return (h, aux + a), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), params["blocks"])
    return L.rmsnorm(h, params["final_norm"], cfg.norm_eps), aux / cfg.num_layers


def loss_fn(params, batch, cfg, aux_weight=0.01):
    h, aux = forward(params, batch["tokens"], cfg)
    logits = L.unembed_fwd(params["embed"], h)
    return L.cross_entropy(logits, batch["labels"], batch.get("loss_mask")) + aux_weight * aux


def init_cache(cfg, batch, max_seq, dtype):
    from repro.models import transformer
    return transformer.init_cache(cfg, batch, max_seq, dtype)


def cache_logical(cfg):
    from repro.models import transformer
    return transformer.cache_logical(cfg)


def decode_step(params, cache, tokens, pos, cfg):
    # cache in scan carry -> in-place updates (see transformer.decode_step)
    h = L.embed_fwd(params["embed"], tokens, cfg.jnp_dtype)

    def body(carry, bp):
        h, ck_all, cv_all, i = carry
        bp = L.fsdp_gather(bp, block_defs(cfg))
        ck = jax.lax.dynamic_index_in_dim(ck_all, i, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(cv_all, i, 0, keepdims=False)
        a, ck, cv = L.attention_decode(
            bp["attn"], L.rmsnorm(h, bp["attn_norm"], cfg.norm_eps), cfg, ck, cv, pos)
        ck_all = jax.lax.dynamic_update_slice_in_dim(ck_all, ck[None], i, 0)
        cv_all = jax.lax.dynamic_update_slice_in_dim(cv_all, cv[None], i, 0)
        h = h + a
        m, _ = moe_fwd(bp["moe"], L.rmsnorm(h, bp["mlp_norm"], cfg.norm_eps), cfg)
        return (h + m, ck_all, cv_all, i + 1), None

    (h, ck_all, cv_all, _), _ = jax.lax.scan(
        body, (h, cache["k"], cache["v"], jnp.int32(0)), params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    return L.unembed_fwd(params["embed"], h), {"k": ck_all, "v": cv_all}


def prefill(params, tokens, cfg, max_seq):
    h = L.embed_fwd(params["embed"], tokens, cfg.jnp_dtype)
    positions = jnp.arange(tokens.shape[1])[None, :]

    def body(h, bp):
        bp = L.fsdp_gather(bp, block_defs(cfg))
        a, (k, v) = L.attention_fwd(
            bp["attn"], L.rmsnorm(h, bp["attn_norm"], cfg.norm_eps), cfg,
            positions=positions)
        h = h + a
        m, _ = moe_fwd(bp["moe"], L.rmsnorm(h, bp["mlp_norm"], cfg.norm_eps), cfg)
        return constraint(h + m, ("batch", "seq_sp", None)), (k, v)

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    h, (k_all, v_all) = jax.lax.scan(body, h, params["blocks"])
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    logits = L.unembed_fwd(params["embed"], h[:, -1:])
    pad = max_seq - tokens.shape[1]
    cache = {
        "k": jnp.pad(k_all, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
        "v": jnp.pad(v_all, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0))),
    }
    return logits, cache
