"""Mixture-of-Experts layer: top-k router, capacity-bounded scatter
dispatch, per-expert SwiGLU, optional shared experts (Moonlight-style).

TPU/JAX shape discipline: dispatch is a static-capacity scatter into an
[E, C, D] buffer (tokens over capacity are dropped, the standard TPU MoE
trade-off), expert FFNs run as one batched einsum, and the combine is a
gather + weighted sum.  Experts shard over the `expert` logical axis
("data" on the production mesh — EP), expert FFN width over "model" (TP);
the token shuffle between batch-sharded activations and expert-sharded
buffers lowers to an all_to_all under SPMD.

Integration with the paper (DESIGN.md §4): expert load statistics are a
guarded COUNT(*) ... GROUP BY expert; `load_stats` computes them with the
same segmented-sum machinery as the query engine's FreqJoin pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard
from repro.models.config import ModelConfig
from repro.models.layers import dense_init


def moe_init(key, cfg: ModelConfig, stacked: int | None = None):
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    pre = (stacked,) if stacked is not None else ()
    lead = ("layers",) if stacked is not None else ()
    p = {
        "router": dense_init(ks[0], pre + (d, e)),
        "wi": dense_init(ks[1], pre + (e, d, f)),
        "wg": dense_init(ks[2], pre + (e, d, f)),
        "wo": dense_init(ks[3], pre + (e, f, d), in_axis=-2),
    }
    s = {
        "router": lead + ("embed", None),
        "wi": lead + ("experts", None, "expert_mlp"),
        "wg": lead + ("experts", None, "expert_mlp"),
        "wo": lead + ("experts", "expert_mlp", None),
    }
    if cfg.n_shared_experts:
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wi": dense_init(sk[0], pre + (d, f * cfg.n_shared_experts)),
            "wg": dense_init(sk[1], pre + (d, f * cfg.n_shared_experts)),
            "wo": dense_init(sk[2], pre + (f * cfg.n_shared_experts, d),
                             in_axis=-2),
        }
        s["shared"] = {
            "wi": lead + ("embed", "mlp"),
            "wg": lead + ("embed", "mlp"),
            "wo": lead + ("mlp", "embed"),
        }
    return p, s


def _capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, (c + 7) // 8 * 8)


def moe_apply(p, cfg: ModelConfig, x, dtype):
    """x: [B, S, D] → [B, S, D], aux-loss dict."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(cfg, t)
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt, p["router"].astype(dtype))
    logits = logits.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert_idx = jax.lax.top_k(probs, k)           # [t, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) within its expert, capacity-dropped.
    # SORT-based (not one-hot cumsum): a [t·k, e] one-hot prefix sum is
    # counted/lowered as an O(N·w) reduce-window — at 1M tokens it alone
    # was 1.6e14 FLOPs/device and 1.6 GB (EXPERIMENTS §Dry-run note ²).
    # A stable argsort by expert gives identical first-come-first-served
    # positions in O(N log N), shardable, with no [N, e] intermediates.
    n_assign = t * k
    flat_e_all = expert_idx.reshape(n_assign)
    order = jnp.argsort(flat_e_all, stable=True)
    sorted_e = flat_e_all[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(e))    # [e]
    pos_sorted = jnp.arange(n_assign) - seg_start[sorted_e]
    pos = jnp.zeros((n_assign,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32)).reshape(t, k)
    keep = pos < cap

    # scatter tokens into the expert buffer.  Two SPMD-friendliness tricks:
    # (a) LINEAR 1-D indices into a flattened [e·(cap+1), d] buffer — 2-D
    #     (expert, pos) scatters make XLA materialise [t·k, d_shard] u32
    #     index matrices; 1-D row scatters keep indices at [t·k];
    # (b) operand/updates sharded on d ("dispatch_embed") so the scatter is
    #     fully local per shard; the buffer reshards for the expert einsum.
    flat_e = expert_idx.reshape(t * k)
    flat_pos = jnp.where(keep.reshape(-1), pos.reshape(-1), cap)  # cap = trash
    lin = flat_e * (cap + 1) + flat_pos
    buf = shard(jnp.zeros((e * (cap + 1), d), dtype),
                None, "dispatch_embed")
    tok_src = jnp.repeat(xt, k, axis=0) if k > 1 else xt
    tok_src = shard(tok_src.astype(dtype), "batch", "dispatch_embed")
    buf = buf.at[lin].set(tok_src, mode="drop")
    buf = buf.reshape(e, cap + 1, d)[:, :cap]
    if cfg.dispatch_reshard:
        buf = shard(buf, "experts", None, "act_embed")

    # batched per-expert SwiGLU
    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(dtype))
    g = jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dtype))
    h = jax.nn.silu(g) * h
    h = shard(h, "experts", None, "expert_mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dtype))
    # reshard to d + flatten for a fully local 1-D row gather
    out_buf = shard(out_buf.reshape(e * cap, d), None, "dispatch_embed")

    # combine: gather each (token, choice) result, weight by gate
    lin_out = flat_e * cap + jnp.minimum(flat_pos, cap - 1)
    out_tok = out_buf[lin_out]                                 # [t*k, d]
    out_tok = shard(out_tok, "batch", "dispatch_embed")
    w = (gate.reshape(t * k) * keep.reshape(t * k)).astype(dtype)
    out = (out_tok * w[:, None]).reshape(t, k, d).sum(axis=1)

    if cfg.n_shared_experts:
        sp = p["shared"]
        sh = jnp.einsum("td,df->tf", xt, sp["wi"].astype(dtype))
        sg = jnp.einsum("td,df->tf", xt, sp["wg"].astype(dtype))
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(sg) * sh,
                               sp["wo"].astype(dtype))

    # aux losses (Switch-style load balance + router z-loss)
    counts = jnp.zeros((e,), jnp.float32).at[flat_e_all].add(1.0)
    density = counts / t                                            # [e]
    router_prob = probs.mean(axis=0)
    aux = {
        "load_balance": e * jnp.sum(density * router_prob),
        "router_z": jnp.mean(
            jnp.square(jax.nn.logsumexp(logits, axis=-1))),
        "dropped_frac": 1.0 - keep.mean(),
    }
    return out.reshape(b, s, d), aux


def load_stats(expert_idx: jax.Array, n_experts: int):
    """Expert load = `SELECT expert, COUNT(*) GROUP BY expert` over the
    (token→expert) assignment relation — computed with the paper engine's
    segmented-sum machinery (see DESIGN.md §4)."""
    from repro.kernels import ops as kops
    flat = expert_idx.reshape(-1).astype(jnp.int32)
    keys, sums, valid = kops.group_by_sum(flat, jnp.ones_like(flat))
    loads = jnp.zeros((n_experts,), jnp.int32)
    loads = loads.at[jnp.where(valid, keys, n_experts)].add(
        jnp.where(valid, sums, 0), mode="drop")
    return loads
