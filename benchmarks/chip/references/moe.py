"""Plain reference of the sparse-expert diffusion eps-net (Qwen3-MoE-style
blocks, as SDAR's), on one chip's share of its experts.

Straightforward ``jax.numpy`` in float32: RMSNorm with a ``1 + scale``
gain, per-head RMSNorm of q and k over ``head_dim`` before the rotary
embedding (where ``qk_norm``), grouped-query bidirectional attention over
a row's valid keys, and a mixture of experts in place of the MLP. The
router is a float32 softmax over all ``num_experts``; each position keeps
its ``top_k`` choices, renormalised to sum 1 (SDAR's ``norm_topk_prob``).
The experts held here are ids ``[expert_offset, expert_offset +
experts_held)``; each of them runs densely over every position (a SiLU-
gated MLP of width ``expert_d_ff``) and is weighted by the position's gate
on it, 0 where the position did not choose it. What the experts held
elsewhere would add is left out. A sinusoidal time embedding through a
two-layer MLP is added to every position, and a linear eps head follows
the final norm. It follows the model as the configuration file describes
it and imports nothing of the program.

Every matrix product goes through ``mm``, so one forward serves both the
reference (float32 at ``highest`` precision) and the control (operands
rounded to a lower precision first). The router's product goes through it
too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def init_std(path: tuple[str, ...], m: dict) -> float:
    """Standard deviation of the seeded normal for the parameter at
    ``path`` (0 for gains and biases, which start at zero)."""
    name = path[-1]
    d, deep = m["d_model"], math.sqrt(2 * m["n_layers"])
    if name in ("norm1", "norm2", "final_norm", "b1", "b2", "q_norm",
                "k_norm"):
        return 0.0
    if name in ("embed", "lm_head", "w1", "w2", "eps_head"):
        return 0.02
    if name in ("wq", "wk", "wv", "w_up", "w_gate", "router"):
        return 1.0 / math.sqrt(d)
    if name in ("wo", "w_down"):
        return 1.0 / math.sqrt(d) / deep
    raise KeyError(f"parameter {'/'.join(path)} is not part of the MoE "
                   "reference model")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(F32))


def _rope(x, theta):
    """x: (R, L, H, D); rotates the pair (first half, second half)."""
    length, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(length, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def time_embedding(p, m: dict, diff: dict, t, mm):
    """(R,) times -> (R, d_model) conditioning vector."""
    half = m["time_emb_dim"] // 2
    freqs = jnp.exp(-math.log(diff["sinusoid_max_period"])
                    * jnp.arange(half, dtype=F32) / half)
    args = t[:, None].astype(F32) * freqs[None] * diff["sinusoid_t_scale"]
    te = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    tm = p["time_mlp"]
    te = jax.nn.silu(mm(te, tm["w1"]) + tm["b1"].astype(F32))
    return mm(te, tm["w2"]) + tm["b2"].astype(F32)


def held_gates(router, moe: dict, h, mm):
    """(R, L, held) gate of every position on every held expert."""
    probs = jax.nn.softmax(mm(h, router), axis=-1)
    vals, idx = jax.lax.top_k(probs, moe["top_k"])
    vals = vals / jnp.sum(vals, axis=-1, keepdims=True)
    held = moe["experts_held"] or moe["num_experts"]
    ids = moe["expert_offset"] + jnp.arange(held)
    return jnp.sum(jnp.where(idx[..., None] == ids, vals[..., None], 0.0),
                   axis=-2)


def experts(f, moe: dict, h, mm):
    """The held experts' gate-weighted sum at every position."""
    gates = held_gates(f["router"], moe, h, mm)
    g = mm(h, f["w_gate"], "rld,edf->rlef")
    u = mm(h, f["w_up"], "rld,edf->rlef")
    y = mm(jax.nn.silu(g) * u, f["w_down"], "rlef,efd->rled")
    return jnp.einsum("rle,rled->rld", gates, y,
                      precision=jax.lax.Precision.HIGHEST)


def eps(p, m: dict, diff: dict, x, t, valid_len, mm):
    """eps_theta(x, t) for x (R, L, d_model) float32, t (R,), and the per-row
    number of valid positions ``valid_len`` (R,): keys at or past it are
    masked out, so the valid positions never see a padded tail."""
    r, length, d = x.shape
    hd, nh, nkv = m["head_dim"], m["n_heads"], m["n_kv_heads"]
    eps_n = m["norm_eps"]
    h = x + time_embedding(p, m, diff, t, mm)[:, None, :]
    pos = jnp.arange(length)
    allowed = (pos[None, None, :] < valid_len[:, None, None])[:, None]

    def layer(h, bp):
        bp = bp["slot0"]
        a = bp["attn"]
        hn = _rms(h, bp["norm1"], eps_n)
        q = mm(hn, a["wq"]).reshape(r, length, nh, hd)
        k = mm(hn, a["wk"]).reshape(r, length, nkv, hd)
        if m.get("qk_norm"):
            q = _rms(q, a["q_norm"], eps_n)
            k = _rms(k, a["k_norm"], eps_n)
        q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
        v = mm(hn, a["wv"]).reshape(r, length, nkv, hd)
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)        # query head j reads kv head j//rep
        v = jnp.repeat(v, rep, axis=2)
        s = mm(q, k, "bqhd,bkhd->bhqk") / math.sqrt(hd)
        s = jnp.where(allowed, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = mm(w, v, "bhqk,bkhd->bqhd").reshape(r, length, nh * hd)
        h = h + mm(o, a["wo"])
        hn = _rms(h, bp["norm2"], eps_n)
        return h + experts(bp["moe"], m["moe"], hn, mm), None

    h, _ = jax.lax.scan(layer, h, p["blocks"])
    h = _rms(h, p["final_norm"], eps_n)
    return mm(h, p["eps_head"])


def logits(p, diff: dict, x0, mm):
    """Decode logits of solved embeddings x0 (..., d_model): the rounding
    through the LM head."""
    return mm(x0 / diff["x0_scale"], p["lm_head"])
