"""Expert-parallel MoE via shard_map + all_to_all (opt-in, beyond-paper).

The baseline MoE (models/layers.moe) is tensor-parallel: every device holds a
d_ff shard of EVERY expert and tokens stay put. Expert parallelism instead
shards EXPERTS across a mesh axis and moves TOKENS with all_to_all -- the
GShard/Switch production layout. Traffic per device ~ 2 x (capacity x
d_model) each way, independent of d_ff: wins when d_ff is large relative to
d_model x top_k (grok: F=32768 vs D*k=12288).

Requirements: num_experts % axis_size == 0. Routing math (top-k, capacity,
position-in-expert) matches models/layers.moe's gather dispatch; equivalence
is tested on a real 4-device CPU mesh in tests/test_expert_parallel.py
(subprocess, so the main test process keeps seeing 1 device).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig


def moe_expert_parallel(params, cfg: ModelConfig, x, mesh, axis: str = "data"):
    """Expert-parallel MoE.

    params: standard init_moe pytree {router (D,E), w_up/w_gate (E,D,F),
      w_down (E,F,D)}; expert weights sharded over ``axis`` on dim 0, router
      replicated.
    x: (B, S, D), batch sharded over ``axis``.
    Returns (out, aux) with out sharded like x.
    """
    mcfg = cfg.moe
    n_shards = mesh.shape[axis]
    e = mcfg.num_experts
    assert e % n_shards == 0, (e, n_shards)
    e_loc = e // n_shards
    k = mcfg.top_k

    in_specs = (
        {"router": P(), "w_up": P(axis), "w_gate": P(axis), "w_down": P(axis)},
        P(axis, None, None),
    )

    def _ep(p, x_loc):
        b, s, d = x_loc.shape
        n_tok = b * s
        xf = x_loc.reshape(n_tok, d)
        cap = max(1, int(mcfg.capacity_factor * s * k / e)) * b
        cap = min(cap, n_tok)

        logits = xf.astype(jnp.float32) @ p["router"]
        gates = jax.nn.softmax(logits, axis=-1)                  # (N, E)
        gate_vals, gate_idx = jax.lax.top_k(gates, k)            # (N, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        choice = jax.nn.one_hot(gate_idx, e, dtype=jnp.float32)  # (N, k, E)
        flat = choice.reshape(n_tok * k, e)
        pos = (jnp.cumsum(flat, axis=0) - flat)                  # (N*k, E)
        pos = jnp.sum(pos.reshape(n_tok, k, e) * choice, -1)     # (N, k)
        valid = pos < cap

        # local (E, cap, D) dispatch buffer
        slot = (gate_idx * cap + pos.astype(jnp.int32)).reshape(-1)
        vflat = valid.reshape(-1)
        slot = jnp.where(vflat, slot, e * cap)
        tok_ids = jnp.broadcast_to(jnp.arange(n_tok)[:, None],
                                   (n_tok, k)).reshape(-1)
        table = jnp.zeros((e * cap + 1,), jnp.int32).at[slot].set(
            jnp.where(vflat, tok_ids, 0).astype(jnp.int32))[:-1]
        occ = jnp.zeros((e * cap + 1,), jnp.bool_).at[slot].set(vflat)[:-1]
        buf = jnp.where(occ[:, None], xf[table], 0)              # (E*cap, D)
        buf = buf.reshape(n_shards, e_loc * cap, d)

        # tokens -> expert shards: recv[src] = src's slab for MY experts
        recv = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        recv = recv.reshape(n_shards, e_loc, cap, d).transpose(1, 0, 2, 3)
        recv = recv.reshape(e_loc, n_shards * cap, d)

        h = jnp.einsum("ecd,edf->ecf", recv, p["w_up"])
        g = jnp.einsum("ecd,edf->ecf", recv, p["w_gate"])
        h = (jax.nn.silu(g.astype(jnp.float32)) * h.astype(jnp.float32)
             ).astype(recv.dtype)
        out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])

        out_e = out_e.reshape(e_loc, n_shards, cap, d).transpose(1, 0, 2, 3)
        out_e = out_e.reshape(n_shards, e_loc * cap, d)
        back = jax.lax.all_to_all(out_e, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
        back = back.reshape(e * cap, d)

        gf = (gate_idx * cap + pos.astype(jnp.int32))
        gf = jnp.where(valid, gf, 0)
        got = back[gf]                                            # (N, k, D)
        w = (gate_vals * valid).astype(got.dtype)
        out = jnp.einsum("nk,nkd->nd", w, got).reshape(b, s, d)

        me = jnp.mean(gates, axis=0)
        frac = jnp.mean(jnp.sum(choice * valid[..., None], axis=1), axis=0)
        lb = e * jnp.sum(me * frac) * mcfg.load_balance_loss
        lb = jax.lax.pmean(lb, axis)
        return out, lb

    mapped = jax.shard_map(_ep, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(axis, None, None), P()))
    out, lb = mapped(params, x)
    return out, {"moe_lb": lb}
