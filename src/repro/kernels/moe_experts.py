"""Grouped SwiGLU over the experts held on this chip (Pallas, Mosaic).

    out[t] = sum_e  gate[t, e] * (silu(x[t] @ Wg[e]) * (x[t] @ Wu[e])) @ Wd[e]

over the held experts ``e`` to which token ``t`` is routed (``gate[t, e]``
nonzero), with no capacity: every assignment is computed. The assignments
are laid out by expert in blocks of ``BM`` rows, each block one expert's
(a group's last block is part-filled), and the grid walks the blocks in
expert order. A block gathers its tokens from the VMEM-resident ``x`` with
a one-hot product, runs the expert's three products on the MXU, and adds
its gate-weighted rows back into the VMEM-resident ``(T, d)`` float32
output with the transposed one-hot. Both one-hot products are exact (one
nonzero term per output element), and every block has the same shape, so
a token's output depends on its own row, its gates and the weights alone:
not on which tokens share the call or where its rows land. That is the
serving invariant (a row's eps is bitwise the same solo, stacked, or in
another bucket).

The block count is fixed by the worst case (every token on ``per_token``
held experts); blocks past the used ones are skipped, and their index maps
repeat the last used block, so no weight is fetched for them. The weights
are the whole ``(layers, held, ...)`` stacks, indexed by the ``layer``
scalar: a slice of one layer, taken in XLA, would be copied before the call.

The custom call is named ``_moe_experts`` (``%_moe_experts.<n>`` on a
device trace).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import runtime

BM = 128            # assignment rows per block, all of one expert
TOKENS = 512        # tokens per kernel call; longer inputs go in chunks
F32 = jnp.float32


def default_interpret() -> bool:
    return runtime.default_interpret("moe_experts")


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def n_blocks(tokens: int, held: int, per_token: int) -> int:
    """Blocks that hold every assignment of ``tokens`` tokens routed to at
    most ``per_token`` of ``held`` experts, each expert's rows rounded up
    to whole blocks."""
    by_rows = (tokens * per_token + held * (BM - 1)) // BM
    return max(1, min(by_rows, held * _round_up(tokens, BM) // BM))


def layout(gates, blocks: int):
    """Assignment rows in expert order: ``(ids, row_gates, block_expert,
    n_used)``. ``ids[r]`` is the token of row ``r`` (-1: an empty row),
    ``block_expert[b]`` the expert of block ``b`` (past the used blocks,
    the last used one's) and ``n_used`` the blocks in use, shape (1,)."""
    t, held = gates.shape
    rows = blocks * BM
    routed = gates != 0
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    size = jnp.sum(routed, axis=0, dtype=jnp.int32)
    padded = (size + BM - 1) // BM * BM
    end = jnp.cumsum(padded, dtype=jnp.int32)
    start = end - padded
    row = jnp.where(routed, start[None, :] + rank, rows).reshape(-1)
    tok = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[:, None],
                           (t, held)).reshape(-1)
    ids = jnp.full((rows,), -1, jnp.int32).at[row].set(tok, mode="drop")
    row_gates = jnp.zeros((rows,), F32).at[row].set(
        gates.reshape(-1).astype(F32), mode="drop")
    n_used = end[-1] // BM
    first = jnp.arange(blocks, dtype=jnp.int32) * BM
    expert = jnp.minimum(jnp.sum(first[:, None] >= end[None, :], axis=1,
                                 dtype=jnp.int32), held - 1)
    last = expert[jnp.maximum(n_used - 1, 0)]
    expert = jnp.where(jnp.arange(blocks) < n_used, expert, last)
    return ids, row_gates, expert, n_used.reshape(1)


def _kernel(layer_ref, expert_ref, used_ref, x_ref, idc_ref, idr_ref,
            gate_ref, wg_ref, wu_ref, wd_ref, out_ref):
    b = pl.program_id(0)

    @pl.when(b == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(b < used_ref[0])
    def _():
        x = x_ref[...]
        t, dt = x.shape[0], x.dtype
        pick = (idc_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (BM, t), 1))
        xg = jnp.dot(pick.astype(dt), x, preferred_element_type=F32).astype(dt)
        g = jnp.dot(xg, wg_ref[...], preferred_element_type=F32)
        u = jnp.dot(xg, wu_ref[...], preferred_element_type=F32)
        h = (g * jax.nn.sigmoid(g) * u).astype(dt)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=F32)
        y = (y * gate_ref[...]).astype(dt)
        put = (jax.lax.broadcasted_iota(jnp.int32, (t, BM), 0) == idr_ref[...])
        out_ref[...] += jnp.dot(put.astype(dt), y, preferred_element_type=F32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _moe_experts(layer, expert, n_used, x, ids, row_gates, w_gate, w_up,
                 w_down, *, interpret: bool):
    t, d = x.shape
    f = w_gate.shape[-1]
    nb = expert.shape[0]

    def blk(b, layer, expert, used):          # the block, or the last used
        return jnp.minimum(b, jnp.maximum(used[0] - 1, 0))

    def wmap(b, layer, expert, used):
        return layer[0], expert[blk(b, layer, expert, used)], 0, 0

    row = pl.BlockSpec((None, BM, 1),
                       lambda b, *s: (blk(b, *s), 0, 0))
    col = pl.BlockSpec((None, 1, BM),
                       lambda b, *s: (blk(b, *s), 0, 0))
    # VMEM: x, the two up-projections and the down-projection (double
    # buffered), the float32 output and the block's temporaries
    vmem = 2 * (x.size * x.dtype.itemsize + 3 * d * f * w_gate.dtype.itemsize
                + t * d * 4) + t * d * 4 + 8 * BM * max(d, t) * 4
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nb,),
            in_specs=[
                pl.BlockSpec((t, d), lambda b, *s: (0, 0)),
                row, col, row,
                pl.BlockSpec((None, None, d, f), wmap),
                pl.BlockSpec((None, None, d, f), wmap),
                pl.BlockSpec((None, None, f, d), wmap),
            ],
            out_specs=pl.BlockSpec((t, d), lambda b, *s: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((t, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(min(max(vmem, 32 << 20), 100 << 20))),
        interpret=interpret,
        name="_moe_experts",
    )(layer, expert, n_used, x, ids.reshape(nb, BM, 1),
      ids.reshape(nb, 1, BM), row_gates.reshape(nb, BM, 1),
      w_gate, w_up, w_down)


def _call(x, gates, w_gate, w_up, w_down, layer, per_token, interpret):
    """One chunk of at most TOKENS tokens, padded to whole blocks of rows
    (the padded tokens are routed nowhere)."""
    t = x.shape[0]
    tp = _round_up(t, BM)
    xp = jnp.pad(x, ((0, tp - t), (0, 0)))
    gp = jnp.pad(gates, ((0, tp - t), (0, 0)))
    blocks = n_blocks(tp, gates.shape[1], per_token)
    ids, row_gates, expert, n_used = layout(gp, blocks)
    out = _moe_experts(jnp.reshape(layer, (1,)).astype(jnp.int32), expert,
                       n_used, xp, ids, row_gates, w_gate, w_up, w_down,
                       interpret=interpret)
    return out[:t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts(x, gates, w_gate, w_up, w_down, layer, per_token, interpret):
    t, d = x.shape
    if t <= TOKENS:
        return _call(x, gates, w_gate, w_up, w_down, layer, per_token,
                     interpret)
    n = -(-t // TOKENS)
    xs = jnp.pad(x, ((0, n * TOKENS - t), (0, 0))).reshape(n, TOKENS, d)
    gs = jnp.pad(gates, ((0, n * TOKENS - t), (0, 0))).reshape(
        n, TOKENS, gates.shape[1])
    out = jax.lax.map(lambda a: _call(a[0], a[1], w_gate, w_up, w_down,
                                      layer, per_token, interpret), (xs, gs))
    return out.reshape(n * TOKENS, d)[:t]


def _experts_fwd(x, gates, w_gate, w_up, w_down, layer, per_token,
                 interpret):
    out = _experts(x, gates, w_gate, w_up, w_down, layer, per_token,
                   interpret)
    return out, (x, gates, w_gate, w_up, w_down, layer)


def _experts_bwd(per_token, interpret, res, ct):
    x, gates, w_gate, w_up, w_down, layer = res
    _, vjp = jax.vjp(lambda *a: moe_experts_ref(*a, layer), x, gates,
                     w_gate, w_up, w_down)
    return (*vjp(ct), None)


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_experts(x, gates, w_gate, w_up, w_down, layer, *, per_token: int,
                interpret: bool | None = None):
    """Held experts' part of an MoE layer for ``x`` (T, d).

    ``gates`` (T, held) float32: the routing weight of token ``t`` on held
    expert ``e``, 0 where it is not routed there; at most ``per_token``
    nonzeros per row. ``w_gate`` / ``w_up`` (layers, held, d, f) and
    ``w_down`` (layers, held, f, d): every layer's held experts, of which
    ``layer`` (a scalar) is used. Returns (T, d) float32. Differentiable:
    the backward pass is that of :func:`moe_experts_ref`.

    ``interpret=None`` resolves via :func:`default_interpret`: the kernel
    uses Mosaic's scalar prefetch and lowers on a TPU only."""
    if interpret is None:
        interpret = default_interpret()
    return _experts(x, gates, w_gate, w_up, w_down,
                    jnp.asarray(layer, jnp.int32), per_token, interpret)


def moe_experts_ref(x, gates, w_gate, w_up, w_down, layer):
    """The kernel's arithmetic as a plain loop over the held experts, each
    over every token (weighted 0 where it is not routed), with the kernel's
    casts."""
    dt = x.dtype
    acc = jnp.zeros(x.shape, F32)
    for e in range(gates.shape[1]):
        g = jnp.dot(x, w_gate[layer, e], preferred_element_type=F32)
        u = jnp.dot(x, w_up[layer, e], preferred_element_type=F32)
        h = (g * jax.nn.sigmoid(g) * u).astype(dt)
        y = jnp.dot(h, w_down[layer, e], preferred_element_type=F32)
        acc = acc + (y * gates[:, e:e + 1].astype(F32)).astype(dt).astype(F32)
    return acc
