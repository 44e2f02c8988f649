"""Flash attention (blocked online-softmax), GQA + causal + SWA, portable.

Written against the generic Pallas API so one kernel body lowers to Mosaic
on TPU and Triton on GPU: the grid is (batch*head, q-blocks) -- both axes
parallel-safe -- and the KV walk is an in-kernel ``fori_loop`` whose
running (max, denominator, accumulator) ride in the loop carry instead of
VMEM scratch carried across grid steps (TPU grids are sequential, CUDA
thread blocks are not, so cross-grid-step scratch is the one construct
that cannot port). Q blocks are MXU-aligned (BLK x head_dim). The GQA
mapping h -> h // n_rep happens in the K/V BlockSpec index maps, so kv
heads are never materialized n_rep times in HBM.

Cross-attention / KV-cache decode: query positions are offset by
``sk - sq`` so the LAST query aligns with the last key -- a 1-token decode
against a long cache attends (causally) to the whole prefix instead of
masking everything but ``k_pos == 0``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import runtime

NEG_INF = -1e30


def default_interpret() -> bool:
    """Compiled by default; interpret only where Pallas cannot lower.

    Resolved through the shared per-kernel capability table
    (:func:`repro.kernels.runtime.default_interpret`).
    """
    return runtime.default_interpret("flash_attention")


def _kernel(q_ref, k_ref, v_ref, out_ref, *, scale, causal, window,
            blk_k, n_k_blocks, kv_len, q_off):
    i = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)          # (BQ, D)
    blk_q, d = q.shape
    q_pos = q_off + i * blk_q + jax.lax.broadcasted_iota(
        jnp.int32, (blk_q, blk_k), 0)

    def body(j, carry):
        m_prev, l_prev, acc = carry
        k = k_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(j * blk_k, blk_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = j * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, (blk_q, blk_k), 1)
        mask = k_pos < kv_len                  # KV padding
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1)
        acc_new = acc * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    init = (jnp.full((blk_q,), NEG_INF, jnp.float32),
            jnp.zeros((blk_q,), jnp.float32),
            jnp.zeros((blk_q, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_k_blocks, body, init)
    out_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(out_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool | None = None):
    """q: (B,Sq,H,D); k,v: (B,Sk,KV,D), H % KV == 0. Returns (B,Sq,H,D).

    ``interpret=None`` resolves via :func:`default_interpret` at call time
    (compiled on TPU/GPU, interpreter on CPU); pass an explicit bool to
    force either mode (tests cross-check the two).
    """
    if interpret is None:
        interpret = default_interpret()
    return _flash_jit(q, k, v, causal=causal, window=window, blk_q=blk_q,
                      blk_k=blk_k, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "blk_q", "blk_k",
                                    "interpret"))
def _flash_jit(q, k, v, *, causal: bool, window: int, blk_q: int, blk_k: int,
               interpret: bool):
    b, sq, h, d = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0, (h, kv)
    n_rep = h // kv
    scale = 1.0 / math.sqrt(d)

    blk_q = min(blk_q, max(sq, 8))
    blk_k = min(blk_k, sk)
    pq = (-sq) % blk_q
    pk = (-sk) % blk_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pq, sk + pk
    n_k_blocks = sk_p // blk_k

    # fold (B, H) into one grid axis; head axis leaves the block
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, sq_p, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * kv, sk_p, d)

    def q_map(g, i):
        return (g, i, 0)

    def kv_map(g, i):
        return ((g // h) * kv + (g % h) // n_rep, 0, 0)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        blk_k=blk_k, n_k_blocks=n_k_blocks, kv_len=sk, q_off=sk - sq)

    out = pl.pallas_call(
        kern,
        grid=(b * h, sq_p // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), q_map),
            pl.BlockSpec((1, sk_p, d), kv_map),
            pl.BlockSpec((1, sk_p, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    out = out.reshape(b, h, sq_p, d).transpose(0, 2, 1, 3)
    return out[:, :sq]
