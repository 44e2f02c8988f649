"""Backbone building blocks: norms, RoPE, attention (GQA/MQA/SWA, KV cache,
optional per-head q/k norm), GLU MLPs, MoE (GShard-style capacity dispatch,
or dropless routing over the experts held on this chip), time conditioning.

Pure functions over parameter pytrees (no flax). All matmuls via einsum with
``preferred_element_type=float32`` accumulation when inputs are bf16.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig


def _acc(x):
    """Accumulation dtype for mixed-precision einsums."""
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype


def matmul(x, w):
    out = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=_acc(x))
    return out.astype(x.dtype)


def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (normed * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def sinusoidal_embedding(t, dim: int, max_period: float = 10_000.0):
    """Timestep embedding for diffusion conditioning (t scalar or (B,))."""
    t = jnp.atleast_1d(t)
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period) * jnp.arange(half, dtype=jnp.float32) / half)
    args = t[:, None].astype(jnp.float32) * freqs[None, :] * 1000.0
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# --------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, positions, theta: float):
    """positions: (...,S) int -> cos/sin of shape (..., S, head_dim//2)."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (B, S, H, D). Rotates pairs (x1, x2) = split halves."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- attention
def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def attention_scores(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Sq,H,D), k/v: (B,Sk,H,D) (already GQA-expanded). mask broadcastable
    to (B, H, Sq, Sk) boolean (True = attend). fp32 softmax."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def make_attention_mask(q_pos, kv_pos, causal: bool, window: int = 0,
                        kv_valid=None):
    """Boolean mask (B?, 1, Sq, Sk) from position tensors (broadcast (S,) ok)."""
    qp = q_pos[..., :, None]
    kp = kv_pos[..., None, :]
    mask = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), dtype=bool)
    if causal:
        mask = mask & (kp <= qp)
    if window:
        mask = mask & (kp > qp - window)
    if kv_valid is not None:
        mask = mask & kv_valid[..., None, :]
    return mask[..., None, :, :] if mask.ndim == 2 else mask[:, None]


def init_attention(key, cfg: ModelConfig, dtype):
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": (jax.random.normal(ks[0], (d, qd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kvd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kvd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (qd, d)) * s / math.sqrt(2 * cfg.n_layers)).astype(dtype),
    }
    if cfg.qk_norm:
        hd = cfg.resolved_head_dim
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def attention(params, cfg: ModelConfig, x, positions, *, causal=True,
              cache=None, cache_index=None, kv_override=None,
              return_kv: bool = False, use_pallas: bool = False,
              valid_len=None):
    """Multi-head attention with GQA + RoPE + optional SWA and KV cache.

    cache: None (train/prefill w/o cache) or dict {k, v} with shape
      (B, S_cache, KV, D); decode writes current kv at ``cache_index``.
    kv_override: (k, v) for cross-attention (already projected).
    return_kv: prefill mode -- return the (post-RoPE) KV as a cache (ring
    layout of window size for SWA archs).
    valid_len: optional (B,) int -- per-row true sequence length when rows
      are right-padded to a bucketed S; key positions >= valid_len are
      masked out so row content is independent of the bucket it landed in.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = matmul(x, params["wq"]).reshape(b, s, cfg.n_heads, hd)
    if kv_override is None:
        k = matmul(x, params["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
        v = matmul(x, params["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(q, params["q_norm"], cfg.norm_eps)
            k = rms_norm(k, params["k_norm"], cfg.norm_eps)
        cos, sin = rope_frequencies(hd, positions, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    else:
        k, v = kv_override

    new_cache = None
    if return_kv and cache is None and kv_override is None:
        if cfg.sliding_window and s > cfg.sliding_window:
            w = cfg.sliding_window
            pos0 = s - w
            idxs = np.arange(pos0, s) % w
            ck = jnp.zeros((b, w) + k.shape[2:], k.dtype).at[:, idxs].set(k[:, pos0:])
            cv = jnp.zeros((b, w) + v.shape[2:], v.dtype).at[:, idxs].set(v[:, pos0:])
            new_cache = {"k": ck, "v": cv}
        else:
            new_cache = {"k": k, "v": v}
    if cache is not None and kv_override is None:
        # decode: write this step's kv into the cache at cache_index (ring
        # buffer for SWA), then attend over the whole cache
        s_cache = cache["k"].shape[1]
        if cfg.sliding_window and s_cache == cfg.sliding_window:
            write_idx = jnp.mod(cache_index, s_cache)
        else:
            write_idx = cache_index
        write_idx = write_idx.astype(jnp.int32) if hasattr(write_idx, "astype") \
            else jnp.int32(write_idx)
        zero = jnp.zeros((), jnp.int32)
        ck = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                          (zero, write_idx, zero, zero))
        cv = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                          (zero, write_idx, zero, zero))
        new_cache = {"k": ck, "v": cv}
        k, v = ck, cv

    if use_pallas and cache is None and kv_override is None \
            and valid_len is None:
        # full-sequence self-attention through the Pallas flash kernel
        # (interpret mode off-TPU); GQA handled inside the kernel's index
        # maps -- kv heads are never materialized n_rep times
        from ..kernels.ops import flash_attention as _flash
        out = _flash(q, k, v, causal=causal, window=cfg.sliding_window)
        out = matmul(out.reshape(b, s, cfg.q_dim), params["wo"])
        return out, new_cache

    n_rep = cfg.n_heads // max(1, k.shape[2])
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    if cache is not None and kv_override is None:
        s_cache = k.shape[1]
        if cfg.sliding_window and s_cache == cfg.sliding_window:
            # ring buffer: valid positions are cache_index - window + 1 .. cache_index
            slot = jnp.arange(s_cache)
            age = jnp.mod(cache_index - slot, s_cache)
            kv_pos = cache_index - age
            valid = kv_pos >= 0
            mask = (kv_pos <= positions[..., :, None]) & valid
            mask = mask[:, None] if mask.ndim == 3 else mask[None, None]
        else:
            kv_pos = jnp.arange(s_cache)
            mask = kv_pos[None, None, None, :] <= positions[..., :, None][:, None]
            if cfg.sliding_window:
                mask = mask & (kv_pos[None, None, None, :] >
                               positions[..., :, None][:, None] - cfg.sliding_window)
    elif kv_override is not None:
        mask = jnp.ones((1, 1, s, k.shape[1]), dtype=bool)
    else:
        kv_pos = positions
        kv_valid = None
        if valid_len is not None:
            kv_valid = jnp.arange(s)[None, :] < valid_len[:, None]
        mask = make_attention_mask(positions, kv_pos, causal,
                                   cfg.sliding_window, kv_valid=kv_valid)

    out = attention_scores(q, k, v, mask, cfg.logit_softcap)
    out = matmul(out.reshape(b, s, cfg.q_dim), params["wo"])
    return out, new_cache


# --------------------------------------------------------------------- MLPs
def init_mlp(key, cfg: ModelConfig, dtype):
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    s = 1.0 / math.sqrt(d)
    p = {"w_up": (jax.random.normal(ks[0], (d, f)) * s).astype(dtype),
         "w_down": (jax.random.normal(ks[1], (f, d)) * s / math.sqrt(2 * cfg.n_layers)).astype(dtype)}
    if cfg.glu:
        p["w_gate"] = (jax.random.normal(ks[2], (d, f)) * s).astype(dtype)
    return p


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def mlp(params, cfg: ModelConfig, x):
    up = matmul(x, params["w_up"])
    if cfg.glu:
        up = _act(cfg.act)(matmul(x, params["w_gate"])) * up
    else:
        up = _act(cfg.act)(up)
    return matmul(up, params["w_down"])


# ---------------------------------------------------------------------- MoE
def init_moe(key, cfg: ModelConfig, dtype):
    """The router over all ``num_experts``, and the stacks of the experts
    held here (every expert, unless ``experts_held`` says otherwise)."""
    d, f = cfg.d_model, cfg.expert_width
    e, held = cfg.moe.num_experts, cfg.moe.held
    ks = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    return {
        "router": (jax.random.normal(ks[0], (d, e)) * s).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (held, d, f)) * s).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (held, d, f)) * s).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (held, f, d)) * s / math.sqrt(2 * cfg.n_layers)).astype(dtype),
    }


def route_held(router, cfg: ModelConfig, x):
    """Routing of tokens ``x`` (T, d) over all ``num_experts``: a float32
    softmax router, top-k, the k gates renormalised to sum 1.
    Returns ``(gates (T, held) float32, held count (T,) int32)``: each
    token's gate on every held expert (0 where it is not routed there) and
    the number of its k choices that fall on a held expert."""
    mcfg = cfg.moe
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    vals, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), mcfg.top_k)
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    local = idx - mcfg.expert_offset                              # (T, k)
    hit = local[..., None] == jnp.arange(mcfg.held)               # (T, k, H)
    gates = jnp.sum(jnp.where(hit, vals[..., None], 0.0), axis=1)
    return gates, jnp.sum(hit, axis=(1, 2), dtype=jnp.int32)


def moe_held(params, experts, layer, cfg: ModelConfig, x, valid_len=None):
    """Dropless MoE over the experts held on this chip (``cfg.moe.dropless``).

    Every position is routed over all ``num_experts``
    (:func:`route_held`); the assignments that fall on the held experts
    (ids ``[expert_offset, expert_offset + held)``) run through the grouped
    expert kernel with no capacity, and the gate-weighted sum of their
    outputs is returned. What the experts held elsewhere would add is left
    out. A position's output depends on its own hidden state alone.

    ``params``: this layer's ``router``; ``experts``: the ``w_gate`` /
    ``w_up`` / ``w_down`` stacks of every layer, ``layer`` the index into
    them. Returns ``(out (B, S, d), held assignments (B,))``, counted at
    positions below ``valid_len`` (every position when it is None)."""
    from ..kernels.moe_experts import moe_experts
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    gates, hits = route_held(params["router"], cfg, xt)
    out = moe_experts(xt, gates, experts["w_gate"], experts["w_up"],
                      experts["w_down"], layer,
                      per_token=min(cfg.moe.top_k, cfg.moe.held))
    hits = hits.reshape(b, s)
    if valid_len is not None:
        hits = jnp.where(jnp.arange(s)[None, :] < valid_len[:, None], hits, 0)
    return (out.astype(x.dtype).reshape(b, s, d),
            jnp.sum(hits, axis=1, dtype=jnp.int32))


def moe(params, cfg: ModelConfig, x, *, expert_parallel: bool = False):
    """Top-k capacity-based MoE. Two dispatch modes (cfg.moe_dispatch):

    'einsum' -- GShard one-hot dispatch matmuls (classic TPU idiom; baseline).
                Costs an extra O(S*E*C*D) matmul + an O(S*E*C) one-hot tensor
                each way.
    'gather' -- scatter/gather dispatch: build an (E, C) token-index table,
                gather expert inputs, combine by weighted scatter-equivalent
                one-hot on the RETURN path only where cheap. Removes the
                dispatch matmul FLOPs/bytes entirely (EXPERIMENTS.md §Perf,
                grok iteration).
    Returns (out, aux_losses)."""
    mcfg = cfg.moe
    b, s, d = x.shape
    e, k = mcfg.num_experts, mcfg.top_k
    n_tok = s
    cap = max(1, int(mcfg.capacity_factor * n_tok * k / e))
    cap = min(cap, n_tok)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), params["router"])
    gates = jax.nn.softmax(logits, axis=-1)                      # (B,S,E)
    gate_vals, gate_idx = jax.lax.top_k(gates, k)                # (B,S,k)
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

    # position of each (token, choice) within its expert queue
    choice_onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (B,S,k,E)
    flat = choice_onehot.reshape(b, s * k, e)
    pos_f = jnp.cumsum(flat, axis=1) - flat                      # (B,S*k,E)
    pos_f = pos_f.reshape(b, s, k, e)
    pos = jnp.sum(pos_f * choice_onehot, axis=-1)                # (B,S,k) slot idx
    within_cap = pos < cap

    def _pin_batch(t):
        """Pin the leading (batch) dim to the configured data axes so GSPMD's
        scatter-add backward cannot silently replicate the batch (observed:
        ~170GB/layer all-reduces of batch-replicated expert grads)."""
        if cfg.act_shard_axes is None:
            return t
        from jax.sharding import PartitionSpec as _P
        spec = _P(tuple(cfg.act_shard_axes), *([None] * (t.ndim - 1)))
        return jax.lax.with_sharding_constraint(t, spec)

    if cfg.moe_dispatch == "gather":
        # token index table per (expert, slot): scatter token ids
        tok_ids = jnp.broadcast_to(jnp.arange(s)[None, :, None], (b, s, k))
        flat_slot = (gate_idx * cap + pos.astype(jnp.int32)).reshape(b, s * k)
        valid = within_cap.reshape(b, s * k)
        upd = jnp.where(valid, tok_ids.reshape(b, s * k), 0).astype(jnp.int32)
        # out-of-capacity entries scatter to a dustbin slot (e*cap)
        slot = jnp.where(valid, flat_slot, e * cap).astype(jnp.int32)
        table = jnp.zeros((b, e * cap + 1), jnp.int32).at[
            jnp.arange(b)[:, None], slot].set(upd)[:, :-1]
        occupied = jnp.zeros((b, e * cap + 1), jnp.bool_).at[
            jnp.arange(b)[:, None], slot].set(valid)[:, :-1]
        xin = jnp.take_along_axis(x, table[..., None], axis=1)   # (B,E*C,D)
        xin = jnp.where(occupied[..., None], xin, 0).reshape(b, e, cap, d)
        xin = _pin_batch(xin)
        h = jnp.einsum("becd,edf->becf", xin, params["w_up"])
        g = jnp.einsum("becd,edf->becf", xin, params["w_gate"])
        h = (jax.nn.silu(g.astype(jnp.float32)) * h.astype(jnp.float32)).astype(x.dtype)
        out_e = jnp.einsum("becf,efd->becd", _pin_batch(h), params["w_down"])
        out_e = _pin_batch(out_e.reshape(b, e * cap, d))
        # return path: each token gathers its k slots back (dropped tokens
        # read slot 0 but are zero-weighted below)
        gflat = (gate_idx * cap + pos.astype(jnp.int32)).reshape(b, s * k)
        gflat = jnp.where(valid, gflat, 0)
        got = jnp.take_along_axis(out_e, gflat[..., None], axis=1)  # (B,S*k,D)
        got = _pin_batch(got.reshape(b, s, k, d))
        w = (gate_vals * within_cap).astype(got.dtype)
        out = _pin_batch(jnp.einsum("bsk,bskd->bsd", w, got))
        frac_dispatched = jnp.mean(
            jnp.sum(choice_onehot * within_cap[..., None], axis=2), axis=(0, 1))
    else:
        pos_onehot = jax.nn.one_hot(pos, cap, dtype=jnp.float32)     # (B,S,k,C)
        disp_k = choice_onehot[..., None] * pos_onehot[..., None, :] \
            * within_cap[..., None, None]                             # (B,S,k,E,C)
        dispatch = jnp.sum(disp_k, axis=2)                            # (B,S,E,C)
        combine = jnp.einsum("bsk,bskec->bsec", gate_vals, disp_k)
        xin = _pin_batch(jnp.einsum("bsec,bsd->becd", dispatch.astype(x.dtype), x))
        h = jnp.einsum("becd,edf->becf", xin, params["w_up"])
        g = jnp.einsum("becd,edf->becf", xin, params["w_gate"])
        h = (jax.nn.silu(g.astype(jnp.float32)) * h.astype(jnp.float32)).astype(x.dtype)
        out_e = jnp.einsum("becf,efd->becd", _pin_batch(h), params["w_down"])
        out = _pin_batch(jnp.einsum("bsec,becd->bsd", combine.astype(x.dtype), out_e))
        frac_dispatched = jnp.mean(jnp.sum(dispatch, axis=-1), axis=(0, 1))

    # aux losses (Switch/GShard): load-balance + router z-loss
    me = jnp.mean(gates, axis=(0, 1))                             # mean gate prob
    lb_loss = e * jnp.sum(me * frac_dispatched) * mcfg.load_balance_loss
    z_loss = jnp.mean(jax.nn.logsumexp(logits, -1) ** 2) * mcfg.router_z_loss
    return out, {"moe_lb": lb_loss, "moe_z": z_loss}
