"""Backbone assembly: init + forward for all arch families.

Layers are grouped into repeating BLOCKS and parameters are stacked with a
leading ``n_blocks`` dim; the forward pass is a single ``lax.scan`` over
blocks. This keeps HLO size O(block) instead of O(n_layers) -- essential for
compiling 64-72 layer configs for 512 devices -- and gives natural remat
boundaries. The held experts of a dropless MoE stay out of the scan: the
expert kernel takes every layer's stack whole and indexes it by the layer
(a slice taken in the scan would be copied before the kernel call).

Block layouts:
  dense / moe / ssm : block = 1 layer
  hybrid (jamba)    : block = ``attn_every`` layers, attention at the middle
                      slot, MoE MLP on odd slots (1:7 mamba:attn, 16e top-2)
  encdec (whisper)  : encoder stack (bidirectional) + decoder stack with
                      cross-attention; frontend embeddings come in via
                      ``frames`` (stub carve-out)
  vlm (paligemma)   : image-patch ``prefix`` embeddings prepended to text

Modes: 'train' (full seq), 'prefill' (full seq -> returns KV cache),
'decode' (one token against cache at ``cache_index``).
Objectives: 'ar' (causal LM) and 'diffusion' (bidirectional denoiser with
time conditioning -- the paper's eps_theta; see repro/diffusion/lm.py).
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers as L
from . import ssm as S


def _dtype(cfg: ModelConfig):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[cfg.dtype]


def block_size(cfg: ModelConfig) -> int:
    if cfg.arch_type == "hybrid":
        return cfg.attn_every
    return 1


def n_blocks(cfg: ModelConfig) -> int:
    bs = block_size(cfg)
    assert cfg.n_layers % bs == 0, (cfg.n_layers, bs)
    return cfg.n_layers // bs


def _layer_kind(cfg: ModelConfig, slot: int) -> tuple[str, str]:
    """(mixer, mlp) kinds for slot within a block."""
    if cfg.arch_type == "ssm":
        mixer = "ssm"
    elif cfg.arch_type == "hybrid":
        mixer = "attn" if slot == (cfg.attn_every // 2) else "ssm"
    else:
        mixer = "attn"
    if cfg.moe is None:
        mlp = "dense"
    elif cfg.moe_every and cfg.moe_every > 1:
        mlp = "moe" if (slot % cfg.moe_every) == 1 else "dense"
    else:
        mlp = "moe"
    if cfg.arch_type == "ssm":
        mlp = "none"  # mamba2 blocks have no separate MLP
    return mixer, mlp


# ------------------------------------------------------------------- init
def _init_block(key, cfg: ModelConfig, dtype, cross_attn: bool = False):
    p: dict[str, Any] = {}
    for slot in range(block_size(cfg)):
        mixer, mlpk = _layer_kind(cfg, slot)
        keys = jax.random.split(jax.random.fold_in(key, slot), 4)
        sp: dict[str, Any] = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
        if mixer == "attn":
            sp["attn"] = L.init_attention(keys[0], cfg, dtype)
        else:
            sp["ssm"] = S.init_ssm(keys[0], cfg, dtype)
        if cross_attn:
            sp["norm_x"] = jnp.zeros((cfg.d_model,), dtype)
            sp["cross"] = L.init_attention(keys[3], cfg, dtype)
        if mlpk != "none":
            sp["norm2"] = jnp.zeros((cfg.d_model,), dtype)
            sp["mlp" if mlpk == "dense" else "moe"] = (
                L.init_mlp(keys[1], cfg, dtype) if mlpk == "dense"
                else L.init_moe(keys[1], cfg, dtype))
        p[f"slot{slot}"] = sp
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    dtype = _dtype(cfg)
    nb = n_blocks(cfg)
    keys = jax.random.split(key, nb + 8)
    blocks = [_init_block(keys[i], cfg, dtype, cross_attn=(cfg.arch_type == "encdec"))
              for i in range(nb)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)

    p: dict[str, Any] = {
        "embed": (jax.random.normal(keys[nb], (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dtype),
        "blocks": stacked,
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = (jax.random.normal(keys[nb + 1], (cfg.d_model, cfg.vocab_size))
                        * 0.02).astype(dtype)
    if cfg.objective == "diffusion":
        te = cfg.time_emb_dim
        p["time_mlp"] = {
            "w1": (jax.random.normal(keys[nb + 2], (te, cfg.d_model)) * 0.02).astype(dtype),
            "b1": jnp.zeros((cfg.d_model,), dtype),
            "w2": (jax.random.normal(keys[nb + 3], (cfg.d_model, cfg.d_model)) * 0.02).astype(dtype),
            "b2": jnp.zeros((cfg.d_model,), dtype),
        }
        p["eps_head"] = (jax.random.normal(keys[nb + 4], (cfg.d_model, cfg.d_model)) * 0.02).astype(dtype)
    if cfg.arch_type == "encdec":
        enc_blocks = [_init_block(jax.random.fold_in(keys[nb + 5], i), cfg, dtype)
                      for i in range(cfg.encoder_layers)]
        p["encoder"] = jax.tree.map(lambda *xs: jnp.stack(xs), *enc_blocks)
        p["enc_final_norm"] = jnp.zeros((cfg.d_model,), dtype)
    return p


# ------------------------------------------------------------------ cache
def init_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype=None,
               enc_out=None, params=None) -> dict:
    """Pre-allocated decode cache. For SWA archs the attention cache is a ring
    buffer of window size. SSM slots carry (conv, state)."""
    dtype = dtype or _dtype(cfg)
    hd = cfg.resolved_head_dim
    eff_len = min(cache_len, cfg.sliding_window) if cfg.sliding_window else cache_len
    d_inner, n_heads_ssm = (S.ssm_dims(cfg) if cfg.ssm else (0, 0))

    def one_block():
        c = {}
        for slot in range(block_size(cfg)):
            mixer, _ = _layer_kind(cfg, slot)
            if mixer == "attn":
                c[f"slot{slot}"] = {
                    "k": jnp.zeros((batch, eff_len, cfg.n_kv_heads, hd), dtype),
                    "v": jnp.zeros((batch, eff_len, cfg.n_kv_heads, hd), dtype),
                }
            else:
                n = cfg.ssm.state_dim
                c[f"slot{slot}"] = {
                    "conv": jnp.zeros((batch, cfg.ssm.conv_width - 1, d_inner + 2 * n), dtype),
                    "state": jnp.zeros((batch, n_heads_ssm, cfg.ssm.head_dim, n), jnp.float32),
                }
        return c

    cache = {"blocks": jax.tree.map(lambda *xs: jnp.stack(xs),
                                    *[one_block() for _ in range(n_blocks(cfg))])}
    if cfg.arch_type == "encdec":
        # precomputed cross-attention KV per decoder block
        if enc_out is not None and params is not None:
            def cross_kv(block_p):
                sp = block_p["slot0"]["cross"]
                k = L.matmul(enc_out, sp["wk"]).reshape(batch, -1, cfg.n_kv_heads, hd)
                v = L.matmul(enc_out, sp["wv"]).reshape(batch, -1, cfg.n_kv_heads, hd)
                return {"k": k, "v": v}
            cache["cross"] = jax.vmap(cross_kv)(params["blocks"]) if False else \
                jax.lax.map(cross_kv, params["blocks"])
        else:
            cache["cross"] = {
                "k": jnp.zeros((n_blocks(cfg), batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((n_blocks(cfg), batch, cfg.encoder_seq, cfg.n_kv_heads, hd), dtype),
            }
    return cache


# ---------------------------------------------------------------- forward
def _split_experts(cfg: ModelConfig, blocks):
    """(blocks without the held-expert stacks, {slot: stacks}) for a
    dropless MoE; (blocks, None) otherwise."""
    if cfg.moe is None or not cfg.moe.dropless:
        return blocks, None
    rest, experts = dict(blocks), {}
    for slot, sp in blocks.items():
        if "moe" in sp:
            moe = sp["moe"]
            experts[slot] = {k: v for k, v in moe.items() if k != "router"}
            rest[slot] = dict(sp, moe={"router": moe["router"]})
    return rest, experts


def _apply_block(cfg: ModelConfig, bp, h, positions, *, causal, cache_b,
                 cache_index, enc_out, collect_kv=False, use_pallas=False,
                 valid_len=None, experts=None, layer=None):
    aux = {}
    new_cache_b = {} if (cache_b is not None or collect_kv) else None
    for slot in range(block_size(cfg)):
        sp = bp[f"slot{slot}"]
        mixer, mlpk = _layer_kind(cfg, slot)
        c_slot = cache_b[f"slot{slot}"] if cache_b is not None else None
        hn = L.rms_norm(h, sp["norm1"], cfg.norm_eps)
        if mixer == "attn":
            out, nc = L.attention(sp["attn"], cfg, hn, positions, causal=causal,
                                  cache=c_slot, cache_index=cache_index,
                                  return_kv=collect_kv, use_pallas=use_pallas,
                                  valid_len=valid_len)
        else:
            out, nc = S.ssm_forward(sp["ssm"], cfg, hn, cache=c_slot,
                                    use_pallas=use_pallas)
        h = h + out
        if new_cache_b is not None:
            # repro: allow[RL002] KV-cache pytree keyed by trace-static layer slot, not a compile cache
            new_cache_b[f"slot{slot}"] = nc if nc is not None else c_slot
        if "cross" in sp and enc_out is not None:
            hx = L.rms_norm(h, sp["norm_x"], cfg.norm_eps)
            b = hx.shape[0]
            hd = cfg.resolved_head_dim
            if isinstance(enc_out, dict):   # precomputed cross KV (decode)
                kv = (enc_out["k"], enc_out["v"])
            else:
                k = L.matmul(enc_out, sp["cross"]["wk"]).reshape(b, -1, cfg.n_kv_heads, hd)
                v = L.matmul(enc_out, sp["cross"]["wv"]).reshape(b, -1, cfg.n_kv_heads, hd)
                kv = (k, v)
            out, _ = L.attention(sp["cross"], cfg, hx, positions, causal=False,
                                 kv_override=kv)
            h = h + out
        if mlpk != "none":
            hn = L.rms_norm(h, sp["norm2"], cfg.norm_eps)
            if mlpk == "dense":
                h = h + L.mlp(sp["mlp"], cfg, hn)
            elif experts is not None:
                out, held = L.moe_held(sp["moe"], experts[f"slot{slot}"],
                                       layer, cfg, hn, valid_len)
                h = h + out
                aux["moe_held"] = aux.get("moe_held", 0) + held
            else:
                out, moe_aux = L.moe(sp["moe"], cfg, hn)
                h = h + out
                for k2, v2 in moe_aux.items():
                    aux[k2] = aux.get(k2, 0.0) + v2
    return h, new_cache_b, aux


def _run_encoder(params, cfg: ModelConfig, frames, unroll: int = 1):
    h = frames.astype(_dtype(cfg))
    positions = jnp.broadcast_to(jnp.arange(h.shape[1])[None], h.shape[:2])

    def body(carry, bp):
        h = carry
        h, _, _ = _apply_block(cfg, bp, h, positions, causal=False, cache_b=None,
                               cache_index=None, enc_out=None)
        return h, None

    enc_unroll = cfg.encoder_layers if (unroll is True or unroll == 0
                                        or unroll > 1) else 1
    h, _ = jax.lax.scan(body, h, params["encoder"], unroll=enc_unroll)
    return L.rms_norm(h, params["enc_final_norm"], cfg.norm_eps)


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None, prefix=None,
            frames=None, mode: str = "train", cache=None, cache_index=None,
            t_cond=None, causal: Optional[bool] = None, use_pallas: bool = False,
            remat: bool = False, unroll: int = 1, block_constraint=None,
            valid_len=None):
    """block_constraint: optional pytree (matching one stacked block's param
    subtree) of NamedShardings applied to the block params INSIDE the scan
    body -- ZeRO-3 semantics: FSDP-sharded weights are all-gathered per block
    just-in-time and freed after (EXPERIMENTS.md §Perf, grok iteration).

    valid_len: optional (B,) int per-row true length for bucket-padded
    batches; threaded to attention so padded tail keys are masked out."""
    """Returns dict(logits | eps, cache, aux), and ``moe_held`` (B,) for a
    dropless MoE: the token-expert pairs its held experts computed per row
    (at positions below ``valid_len``), summed over the layers.

    tokens: (B,S) int32; embeds: (B,S,D) continuous input (diffusion mode);
    prefix: (B,P,D) VLM patch embeddings; frames: (B,F,D) audio embeddings.
    """
    dtype = _dtype(cfg)
    if causal is None:
        causal = cfg.objective != "diffusion"

    if embeds is not None:
        h = embeds.astype(dtype)
    else:
        h = params["embed"][tokens].astype(dtype)
        if cfg.arch_type == "vlm" and mode != "decode" and prefix is not None:
            h = jnp.concatenate([prefix.astype(dtype), h], axis=1)

    b, s, _ = h.shape
    if mode == "decode":
        positions = jnp.broadcast_to(cache_index, (b, 1)).astype(jnp.int32)
    else:
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    if t_cond is not None:
        te = L.sinusoidal_embedding(t_cond, cfg.time_emb_dim).astype(dtype)
        tm = params["time_mlp"]
        te = jax.nn.silu((te @ tm["w1"] + tm["b1"]).astype(jnp.float32)).astype(dtype)
        te = (te @ tm["w2"] + tm["b2"])
        h = h + te[:, None, :] if te.shape[0] == b else h + te[None, None, :]

    enc_out = None
    if cfg.arch_type == "encdec":
        if mode == "decode":
            enc_out = "cached"  # replaced per-block from cache['cross']
        else:
            assert frames is not None
            enc_out = _run_encoder(params, cfg, frames, unroll=unroll)

    collect_kv = (mode == "prefill")
    blocks, experts = _split_experts(cfg, params["blocks"])
    # the layer index rides in the scan only where the held experts need it
    layers = None if experts is None \
        else jnp.arange(n_blocks(cfg), dtype=jnp.int32)

    def body_inner(carry, xs):
        h = carry
        bp, cache_b, cross_b, layer = xs
        if block_constraint is not None:
            bp = jax.tree.map(
                lambda w, c: w if c is None else
                jax.lax.with_sharding_constraint(w, c),
                bp, block_constraint,
                is_leaf=lambda x: x is None)
        eo = cross_b if cfg.arch_type == "encdec" and mode == "decode" else enc_out
        h, new_cache_b, aux = _apply_block(
            cfg, bp, h, positions, causal=causal, cache_b=cache_b,
            cache_index=cache_index, enc_out=eo, collect_kv=collect_kv,
            use_pallas=use_pallas, valid_len=valid_len, experts=experts,
            layer=layer)
        return h, (new_cache_b, aux)

    body = jax.checkpoint(body_inner) if remat else body_inner

    cache_blocks = cache["blocks"] if cache is not None else None
    cross_blocks = cache.get("cross") if (cache is not None and cfg.arch_type == "encdec") else None
    unroll_n = n_blocks(cfg) if (unroll is True or unroll == 0) else int(unroll)
    if cache_blocks is None:
        h, (new_blocks, aux_stack) = jax.lax.scan(
            lambda c, x: body(c, (x[0], None, None, x[1])), h,
            (blocks, layers), unroll=unroll_n)
        new_cache = None
        if collect_kv:
            new_cache = {"blocks": new_blocks}
            if cfg.arch_type == "encdec":
                hd = cfg.resolved_head_dim

                def cross_kv(block_p):
                    sp = block_p["slot0"]["cross"]
                    kk = L.matmul(enc_out, sp["wk"]).reshape(b, -1, cfg.n_kv_heads, hd)
                    vv = L.matmul(enc_out, sp["wv"]).reshape(b, -1, cfg.n_kv_heads, hd)
                    return {"k": kk, "v": vv}

                new_cache["cross"] = jax.lax.map(cross_kv, params["blocks"])
    elif cross_blocks is None:
        h, (new_blocks, aux_stack) = jax.lax.scan(
            lambda c, x: body(c, (x[0], x[1], None, x[2])), h,
            (blocks, cache_blocks, layers), unroll=unroll_n)
        new_cache = dict(cache)
        new_cache["blocks"] = new_blocks
    else:
        h, (new_blocks, aux_stack) = jax.lax.scan(
            body, h, (blocks, cache_blocks, cross_blocks, layers),
            unroll=unroll_n)
        new_cache = dict(cache)
        new_cache["blocks"] = new_blocks

    aux_stack = dict(aux_stack or {})
    held = aux_stack.pop("moe_held", None)
    aux = {k: jnp.sum(v) for k, v in aux_stack.items()}

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)
    out = {"cache": new_cache, "aux": aux, "hidden": h}
    if held is not None:
        # per row: assignments the held experts computed, over the layers
        out["moe_held"] = jnp.sum(held, axis=0, dtype=jnp.int32)
    if cfg.objective == "diffusion" and embeds is not None:
        out["eps"] = L.matmul(h, params["eps_head"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jax.lax.dot_general(h, head, (((2,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    if cfg.logit_softcap:
        logits = cfg.logit_softcap * jnp.tanh(logits / cfg.logit_softcap)
    out["logits"] = logits
    return out
