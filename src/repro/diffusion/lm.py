"""Continuous diffusion language modeling: the paper's technique as a
first-class framework feature for every backbone in the zoo.

Tokens are embedded into R^{d_model}; a forward VPSDE noises the embeddings;
the backbone (bidirectional, time-conditioned) is trained as eps_theta via the
paper's Eq. 9 loss. Generation runs ANY DEIS solver in embedding space --
each NFE is one full-sequence backbone forward -- then rounds to tokens via
the LM head (Diffusion-LM-style anchor loss keeps embeddings decodable).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core import sampler as SAMPLER
from ..core.plan import SolverPlan
from ..core.sde import SDE
from ..models import transformer as T

EMBED_SCALE = 1.0  # embeddings are ~N(0, 0.02^2) at init; rescale to unit-ish
X0_SCALE = 25.0    # x0 = embed * X0_SCALE so data std ~ 0.5


def token_embeddings(params, tokens):
    return params["embed"][tokens].astype(jnp.float32) * X0_SCALE


def diffusion_loss(params, cfg: ModelConfig, sde: SDE, tokens, key, *,
                   prefix=None, frames=None, ce_weight: float = 0.1,
                   remat: bool = False, unroll: int = 1, block_constraint=None):
    """Paper Eq. 9 (eps-matching, uniform weight) + rounding anchor CE + MoE aux."""
    b, s = tokens.shape
    k_t, k_eps = jax.random.split(key)
    t = jax.random.uniform(k_t, (b,), jnp.float32, sde.t0, sde.T)
    x0 = token_embeddings(params, tokens)
    eps = jax.random.normal(k_eps, x0.shape, jnp.float32)
    mu = sde.mu(t)[:, None, None]
    sig = sde.sigma(t)[:, None, None]
    xt = mu * x0 + sig * eps

    if cfg.arch_type == "vlm" and prefix is not None:
        xt = jnp.concatenate([prefix.astype(xt.dtype), xt], axis=1)
    out = T.forward(params, cfg, embeds=xt, t_cond=t, mode="train",
                    causal=False, frames=frames, remat=remat, unroll=unroll,
                    block_constraint=block_constraint)
    eps_pred = out["eps"].astype(jnp.float32)
    if cfg.arch_type == "vlm" and prefix is not None:
        eps_pred = eps_pred[:, prefix.shape[1]:]
    mse = jnp.mean(jnp.square(eps_pred - eps))

    # rounding anchor: decode x0_hat back to tokens through the LM head
    x0_hat = (xt[:, -s:] if cfg.arch_type == "vlm" else xt) - sig * eps_pred
    x0_hat = x0_hat / jnp.maximum(mu, 1e-4)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x0_hat / X0_SCALE) @ head.astype(jnp.float32)
    from ..training.steps import cross_entropy
    ce = cross_entropy(logits, tokens, cfg)

    aux = sum(out["aux"].values()) if out["aux"] else 0.0
    loss = mse + ce_weight * ce + aux
    return loss, {"loss": loss, "mse": mse, "ce": ce}


def _eps_forward(params, cfg: ModelConfig, x, t, *, prefix=None,
                 frames=None, use_pallas: bool = False, unroll: int = 1,
                 valid_len=None):
    """(eps, held-expert assignments per row or None) at x (B, S, D)."""
    b = x.shape[0]
    t_b = jnp.broadcast_to(t, (b,)).astype(jnp.float32)
    xin = x
    vl = valid_len
    if cfg.arch_type == "vlm" and prefix is not None:
        xin = jnp.concatenate([prefix.astype(x.dtype), x], axis=1)
        if vl is not None:
            vl = vl + prefix.shape[1]   # prefix positions are all valid
    out = T.forward(params, cfg, embeds=xin, t_cond=t_b, mode="train",
                    causal=False, frames=frames, use_pallas=use_pallas,
                    unroll=unroll, valid_len=vl)
    eps = out["eps"].astype(x.dtype)
    if cfg.arch_type == "vlm" and prefix is not None:
        eps = eps[:, prefix.shape[1]:]
    return eps, out.get("moe_held")


def make_eps_fn(params, cfg: ModelConfig, *, prefix=None, frames=None,
                use_pallas: bool = False, unroll: int = 1, valid_len=None):
    """eps_theta(x, t) closure for the DEIS solvers; x: (B, S, D), t scalar.

    ``valid_len``: optional (B,) int per-row true length for bucket-padded
    batches -- threaded to attention so a row's denoising trajectory does
    not depend on the bucketed tail padding."""
    def eps_fn(x, t):
        return _eps_forward(params, cfg, x, t, prefix=prefix, frames=frames,
                            use_pallas=use_pallas, unroll=unroll,
                            valid_len=valid_len)[0]
    return eps_fn


# Rows per eps tile in the serving executors, from the group's padded
# length S. A forward is bound by the read of the weights until a tile holds
# about TILE_TOKENS tokens: a v5e's 197 TFLOP/s over 819 GB/s is 240 FLOP
# per byte, so about 256 tokens (the MXU's 128 multiple) per bf16 weight
# read. Short buckets take as many rows as fill that (seq 32: 8 rows, seq
# 64: 4), never more than MAX_TILE_ROWS, the engine's default max_group (a
# wider tile would only add pad rows); seq 128 and longer keep ROW_TILE.
TILE_TOKENS = 256
ROW_TILE = 2
MAX_TILE_ROWS = 8


def tile_rows(seq_len: int) -> int:
    """Rows per eps tile for a group of padded length ``seq_len``. It reads
    the bucket, which every row of a group shares, and never the group's
    row count: a row rounds alike in every group of its bucket."""
    return min(MAX_TILE_ROWS, max(ROW_TILE, TILE_TOKENS // seq_len))


def make_tiled_eps_fn(params, cfg: ModelConfig, *, valid_len, mesh=None,
                      held_counts: list | None = None):
    """:func:`make_eps_fn` run one tile of :func:`tile_rows` rows at a
    time: the serving executors' eps, for ``(R, S, D)`` groups with a
    per-row ``(R,)`` ``valid_len``.

    ``held_counts``: a list to which each call appends the ``(R,)``
    token-expert pairs a dropless MoE's held experts computed per row, at
    its valid positions over every layer (nothing for other models).

    A row's eps must not depend on how many rows share the call (a served
    request decodes bitwise the same solo, stacked or sharded). XLA on a
    TPU does not give that for a batched forward: it compiles a different
    program for each row count, and on a v5e the rows of 1-, 2-, 3-, 4-
    and 8-row forwards all round differently. A loop over fixed tiles runs
    every row through the same tile program whatever the group, and a
    row's bits do not depend on its place in the tile. The tile's width is
    a function of ``S`` alone, so every group of a bucket runs one body.
    Under a request-axis ``mesh`` each device loops over its own rows
    (``shard_map``), so no row is computed twice."""
    counting = held_counts is not None and cfg.moe is not None \
        and cfg.moe.dropless

    def rows(params, x, t, vl):
        # The group is padded with copies of its first row to whole tiles
        # plus one spare tile, and the trip count is read from the data
        # (every length is >= 0). So XLA sees neither a loop that runs once
        # (it would inline the body) nor a slice that is the whole operand
        # (it would drop the slice and hoist the tile's work out of the
        # loop): every group size runs the same loop body.
        r, tile = x.shape[0], tile_rows(x.shape[1])
        spare = -(-r // tile) * tile + tile - r
        xp, tp, vp = (jnp.concatenate([a, jnp.repeat(a[:1], spare, 0)])
                      for a in (x, t, vl))
        n = (jnp.sum(vl >= 0, dtype=jnp.int32) + tile - 1) // tile

        def one(i, carry):
            def take(a):
                return jax.lax.dynamic_slice_in_dim(a, i * tile, tile)

            def put(a, v):
                return jax.lax.dynamic_update_slice_in_dim(a, v, i * tile, 0)
            vl_i = take(vp)
            e, h = _eps_forward(params, cfg, take(xp), take(tp),
                                valid_len=vl_i)
            if counting:
                return put(carry[0], e), put(carry[1], h)
            return put(carry, e)
        init = jnp.zeros_like(xp)
        if counting:
            init = (init, jnp.zeros(vp.shape, jnp.int32))
        out = jax.lax.fori_loop(0, n, one, init)
        return (out[0][:r], out[1][:r]) if counting else (out[:r], None)

    def eps_fn(x, t):
        t_b = jnp.broadcast_to(t, x.shape[:1]).astype(jnp.float32)
        if mesh is None:
            eps, held = rows(params, x, t_b, valid_len)
        else:
            from ..sharding.rules import request_axis_spec
            spec = request_axis_spec(x, mesh, 0)
            row = request_axis_spec(t_b, mesh, 0)
            eps, held = jax.shard_map(
                rows, mesh=mesh, in_specs=(P(), spec, row, row),
                out_specs=(spec, row if counting else None))(
                    params, x, t_b, valid_len)
        if counting:
            held_counts.append(held)
        return eps
    return eps_fn


def decode_tokens(params, cfg: ModelConfig, x0):
    """Round solved embeddings ``x0`` to tokens through the LM head.

    Shared by the one-shot sampler and the streaming serving engine (which
    decodes per-step partial states for streamed progress)."""
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (x0 / X0_SCALE) @ head.astype(jnp.float32)
    return jnp.argmax(logits, -1)


def sample_tokens(params, cfg: ModelConfig, plan: SolverPlan, key,
                  *, batch: int, seq_len: int, prior_std: float | None = None,
                  prefix=None, frames=None, use_pallas: bool = False,
                  hooks=None):
    """Generate token sequences with a DEIS ``SolverPlan``. Returns (tokens, x0).

    A plan carries no SDE, so ``prior_std`` must be passed explicitly
    (``sde.prior_std()``). Jit-compatible with ``plan`` as a traced pytree
    argument, so one compiled executor serves every plan with the same
    signature at fixed (batch, seq_len).
    """
    if prior_std is None:
        raise TypeError("sample_tokens requires prior_std= (use "
                        "sde.prior_std(); a plan carries no SDE to recover "
                        "it from)")
    eps_fn = make_eps_fn(params, cfg, prefix=prefix, frames=frames,
                         use_pallas=use_pallas)
    k_prior, k_solve = jax.random.split(key)
    x_T = jax.random.normal(k_prior, (batch, seq_len, cfg.d_model), jnp.float32) \
        * prior_std
    x0 = SAMPLER.sample(plan, eps_fn, x_T, k_solve, hooks=hooks)
    return decode_tokens(params, cfg, x0), x0


# ----------------------------------------------- per-request-keyed streaming
def request_keys(seeds) -> jax.Array:
    """Stack per-request PRNG keys derived from each request's own seed.

    This is the per-request reproducibility contract: request ``i`` of a
    batch draws its prior and its solve noise from ``PRNGKey(seeds[i])``
    alone, so its sample is independent of which batch it landed in.
    """
    return jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])


def init_sample_state(cfg: ModelConfig, plan: SolverPlan, keys, *,
                      seq_len: int, prior_std: float, valid_lens=None):
    """Build the stacked ``SamplerState`` for a group of requests.

    ``plan`` must be a stacked plan (:func:`repro.core.plan.stack_plans`) and
    ``keys`` a ``(R, 2)`` stack from :func:`request_keys`. Each request's key
    is split into (prior, solve) exactly as the one-shot path splits its
    single key; the prior is drawn per request with shape ``(seq_len,
    d_model)`` so row ``i`` is bit-identical to a single-request solve.

    ``valid_lens``: optional sequence of per-row true lengths (<= seq_len)
    for bucket-padded groups. Row ``i``'s prior is drawn at its TRUE length
    and zero-padded to ``seq_len``, so the prior (and hence the whole
    deterministic trajectory, with attention masking the padded keys) is
    independent of which bucket the request landed in.
    """
    split = jax.vmap(jax.random.split)(keys)          # (R, 2, 2)
    k_prior, k_solve = split[:, 0], split[:, 1]
    if valid_lens is not None and any(int(v) != seq_len for v in valid_lens):
        rows = []
        for i, lv in enumerate(valid_lens):
            lv = int(lv)
            r = jax.random.normal(k_prior[i], (lv, cfg.d_model), jnp.float32)
            rows.append(jnp.pad(r, ((0, seq_len - lv), (0, 0))))
        x_T = jnp.stack(rows) * prior_std
    else:
        x_T = jax.vmap(
            lambda kk: jax.random.normal(kk, (seq_len, cfg.d_model), jnp.float32)
        )(k_prior) * prior_std
    return SAMPLER.init_state(plan, x_T, k_solve)


def sample_tokens_stream(params, cfg: ModelConfig, plan: SolverPlan, keys, *,
                         seq_len: int, prior_std: float, hooks=None):
    """One-shot solve of a stacked per-request-keyed group. Returns
    (tokens, x0).

    This is the reference the streaming engine must reproduce: running the
    same stacked plan step-by-step (interleaved with other groups) yields the
    same per-request samples, because each row's noise comes only from its
    own key chain."""
    eps_fn = make_eps_fn(params, cfg)
    state = init_sample_state(cfg, plan, keys, seq_len=seq_len,
                              prior_std=prior_std)
    x0 = SAMPLER.sample(plan, eps_fn, state.x, state.key, hooks=hooks)
    return decode_tokens(params, cfg, x0), x0
