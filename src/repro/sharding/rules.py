"""Sharding rules engine: param/cache/batch pytrees -> PartitionSpec trees.

Baseline policy (hillclimbed variants live behind flags; see EXPERIMENTS.md
§Perf):

  * batch dims  -> all data-like mesh axes ('pod','data').
  * tensor parallel over 'model': output-feature dims of up-projections
    (wq/wk/wv/w_up/w_gate/moe experts' d_ff) and input-feature dims of
    down-projections (wo/w_down/out_proj) -- Megatron pairing, so each
    block needs one all-reduce per mixer/MLP, not per matmul.
  * FSDP over 'data' on a *second* axis of large weights (opt-in per config
    size) so optimizer states fit for the 314B/398B configs.
  * every rule checks divisibility against the mesh axis size and falls back
    to replication (whisper-tiny's 6 heads simply replicate on a 16-way
    'model' axis; its d_ff=1536 still shards).

The engine is path-pattern based and validated by tests against every arch.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


def batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _div(n: int, mesh: Mesh, axis) -> bool:
    if isinstance(axis, tuple):
        size = int(np.prod([_axis_size(mesh, a) for a in axis]))
    else:
        size = _axis_size(mesh, axis)
    return n % size == 0


def _spec(mesh: Mesh, shape, assignments: dict[int, object]) -> P:
    """Build a PartitionSpec assigning mesh axes to dims where divisible."""
    parts: list = [None] * len(shape)
    for dim, axis in assignments.items():
        d = dim % len(shape)
        if axis is not None and _div(shape[d], mesh, axis):
            parts[d] = axis
    return P(*parts)


# matched in order; first hit wins. Patterns are regexes over the "/"-joined
# tree path (e.g. "blocks/slot0/attn/wq").
def _param_rules(fsdp: bool, ff2d: bool = False):
    """fsdp: shard a second weight axis over 'data' (ZeRO-style).

    ff2d (beyond-paper §Perf lever): for FFN/MoE weights, put the 'data'
    factor on the FEED-FORWARD dim together with 'model' instead of on the
    contraction (d_model) dim. Sharding the contraction dim makes GSPMD emit
    partial-sum all-reduces of the full (tokens x d_ff) activations (~TB/step
    for grok-scale MoE); 2D-sharding d_ff keeps activations sharded and costs
    only one (tokens x d_model) all-reduce per layer.
    """
    f = "data" if fsdp else None
    ff_up = {-1: ("data", "model") if (fsdp and ff2d) else "model",
             -2: None if ff2d else f}
    ff_down = {-2: ("data", "model") if (fsdp and ff2d) else "model",
               -1: None if ff2d else f}
    return [
        (r"embed$",            lambda sh, m: _spec(m, sh, {0: "model", 1: f})),
        (r"lm_head$",          lambda sh, m: _spec(m, sh, {1: "model", 0: f})),
        (r"eps_head$",         lambda sh, m: _spec(m, sh, {1: "model"})),
        (r"(wq|wk|wv)$",       lambda sh, m: _spec(m, sh, {-1: "model", -2: f})),
        (r"(w_up|w_gate)$",    lambda sh, m: _spec(m, sh, dict(ff_up))),
        (r"wo$",               lambda sh, m: _spec(m, sh, {-2: "model", -1: f})),
        (r"(w_down|out_proj)$", lambda sh, m: _spec(m, sh, dict(ff_down))),
        (r"in_proj$",          lambda sh, m: _spec(m, sh, {-1: "model", -2: f})),
        (r"router$",           lambda sh, m: P()),
        (r"conv_w$",           lambda sh, m: _spec(m, sh, {-1: "model"})),
        (r"conv_b$",           lambda sh, m: _spec(m, sh, {-1: "model"})),
        (r"norm",              lambda sh, m: P()),
        (r"(A_log|dt_bias|D)$", lambda sh, m: P()),
        (r"time_mlp",          lambda sh, m: P()),
        (r".*",                lambda sh, m: P()),
    ]


def _path_str(path) -> str:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return "/".join(out)


def param_specs(params_shape, mesh: Mesh, fsdp: bool = False,
                ff2d: bool = False):
    """PartitionSpec tree for a params (or opt-state m/v) shape pytree."""
    rules = _param_rules(fsdp, ff2d)

    def assign(path, leaf):
        ps = _path_str(path)
        shape = leaf.shape
        if len(shape) == 0:
            return P()
        for pat, fn in rules:
            if re.search(pat, ps):
                return fn(shape, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(assign, params_shape)


def opt_state_specs(opt_state_shape, params_spec, mesh: Mesh):
    """OptState(step, m, v): moments shard like params; step replicated."""
    from ..training.optimizer import OptState
    return OptState(P(), params_spec, jax.tree.map(lambda s: s, params_spec))


def batch_specs(batch_shape, mesh: Mesh):
    """Input batch: leading dim over ('pod','data') when divisible."""
    ba = batch_axes(mesh)

    def assign(path, leaf):
        if leaf.ndim == 0:
            return P()
        if _div(leaf.shape[0], mesh, ba):
            return P(ba, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(assign, batch_shape)


def cache_specs(cache_shape, mesh: Mesh, seq_shard: bool = True):
    """Decode/prefill KV+SSM cache specs.

    Attention K/V (nb, B, S, KV, hd): batch over data axes; when the batch
    does not cover the data axes (long-context, batch=1) shard the SEQ dim
    over 'model' (flash-decode style -- XLA resolves the softmax reduction);
    otherwise shard kv-heads/hd over 'model' when divisible.
    SSM state (nb, B, H, P, N): shard heads over 'model' when divisible.
    """
    ba = batch_axes(mesh)

    def assign(path, leaf):
        ps = _path_str(path)
        sh = leaf.shape
        if leaf.ndim == 0:
            return P()
        parts: list = [None] * leaf.ndim
        # leading dim is the stacked-blocks axis for block caches ("blocks/"
        # or "cross/" prefixed); batch is dim 1 there, else dim 0.
        bdim = 1 if ps.startswith(("blocks", "cross")) else 0
        if bdim < leaf.ndim and _div(sh[bdim], mesh, ba):
            parts[bdim] = ba
        if re.search(r"/(k|v)$", ps) and leaf.ndim >= bdim + 4:
            seq_d, kv_d, hd_d = bdim + 1, bdim + 2, bdim + 3
            if _div(sh[kv_d], mesh, "model"):
                parts[kv_d] = "model"
            elif _div(sh[hd_d], mesh, "model"):
                parts[hd_d] = "model"
            elif seq_shard and _div(sh[seq_d], mesh, "model"):
                parts[seq_d] = "model"
        elif re.search(r"/state$", ps) and leaf.ndim >= bdim + 4:
            if _div(sh[bdim + 1], mesh, "model"):
                parts[bdim + 1] = "model"
        elif re.search(r"/conv$", ps) and leaf.ndim >= bdim + 3:
            if _div(sh[-1], mesh, "model"):
                parts[-1] = "model"
        return P(*parts)

    return jax.tree_util.tree_map_with_path(assign, cache_shape)


def to_shardings(spec_tree, mesh: Mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# -------------------------------------------- request-axis (serving) sharding
def request_axis_spec(leaf, mesh: Mesh, dim: int) -> P:
    """P with the data axes on ``dim`` (a leaf's request axis) when
    divisible, else replicated."""
    ba = batch_axes(mesh)
    parts: list = [None] * leaf.ndim
    if ba and dim < leaf.ndim and _div(leaf.shape[dim], mesh, ba):
        parts[dim] = ba[0] if len(ba) == 1 else ba
    return P(*parts)


def plan_specs(plan, mesh: Mesh):
    """PartitionSpec tree for a *stacked* :class:`~repro.core.plan.SolverPlan`.

    Every dynamic leaf of a stacked plan (coefficient arrays and ``ts``)
    carries the request axis leading, so each is sharded over the data-like
    mesh axes when the batch divides evenly and replicated otherwise.
    Unstacked plans (no request axis) replicate entirely. The result has the
    plan's own tree structure, so it can be passed directly as a jit
    ``in_shardings`` entry (static metadata rides in the treedef).
    """
    stacked = getattr(plan, "stacked", False)
    return jax.tree.map(
        lambda leaf: request_axis_spec(leaf, mesh, 0) if stacked else P(),
        plan)


def step_index_specs(k, mesh: Mesh) -> P:
    """Spec for the executor's step-index argument.

    A per-row ``(R,)`` step vector (post-join serving groups: each row runs
    at its own step count) shards over the data-like axes alongside the
    request-axis leaves it indexes, so the per-row coefficient gather stays
    local to each shard; a group-uniform scalar ``k`` replicates.
    """
    return request_axis_spec(k, mesh, 0) if getattr(k, "ndim", 0) else P()


def state_specs(state, mesh: Mesh):
    """PartitionSpec tree for a stacked :class:`SamplerState`.

    The request axis is sharded over the data-like mesh axes: ``x`` is
    ``(R, *inner)`` (axis 0), ``hist`` is ``(history_len, R, *inner)``
    (axis 1), the per-request key stack is ``(R, 2)`` (axis 0), the per-row
    error estimate ``err`` is ``(R,)`` (axis 0), and the step counter ``k``
    is replicated. Non-divisible (or unstacked, ``key.ndim != 2``) states
    fall back to replication leaf-wise.
    """
    from ..core.sampler import SamplerState  # local: avoid core<->sharding cycle
    stacked = state.key.ndim == 2
    return SamplerState(
        x=request_axis_spec(state.x, mesh, 0) if stacked else P(),
        hist=request_axis_spec(state.hist, mesh, 1) if stacked else P(),
        key=request_axis_spec(state.key, mesh, 0) if stacked else P(),
        k=P(),
        err=request_axis_spec(state.err, mesh, 0) if stacked else P())
