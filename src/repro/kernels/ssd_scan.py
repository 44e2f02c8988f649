"""Mamba2 SSD chunked-scan kernel (arXiv:2405.21060), portable Pallas.

Per (batch, head) grid instance the kernel walks chunks with an in-kernel
``fori_loop``; the running state h in R^{P x N} is the loop carry, not VMEM
scratch carried across grid steps (the grid axis is parallel-safe, so the
same body lowers to Mosaic on TPU and Triton on GPU). Each chunk does three
MXU matmuls entirely on-chip:

    scores = C B^T               (L x L)
    y_intra = (scores . decay . tril) x        (L x P)
    y_inter = (C decay_in) h_prev              (L x P)
    h_new   = a_chunk h_prev + (B . decay_out)^T x

This is the hardware adaptation of the paper's CUDA selective-scan: no warp
shuffles -- the sequential dependence is carried by the loop, the quadratic
within-chunk work feeds the systolic MXU, and the (L,L,H) decay tensor that
bloats the XLA path (see EXPERIMENTS.md §Perf jamba iteration) never leaves
VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import runtime


def default_interpret() -> bool:
    """Compiled by default; interpret only where Pallas cannot lower.

    Resolved through the shared per-kernel capability table
    (:func:`repro.kernels.runtime.default_interpret`).
    """
    return runtime.default_interpret("ssd_scan")


def _kernel(x_ref, a_col_ref, a_row_ref, b_ref, c_ref, y_ref, state_out_ref,
            *, n_chunks, chunk):
    p = x_ref.shape[-1]
    n = b_ref.shape[-1]
    # causal masks over (t, u) within a chunk: u <= t, and its transpose
    row_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = col_i <= row_i
    tri_t = row_i <= col_i

    def body(cidx, h_prev):
        sl = pl.ds(cidx * chunk, chunk)
        x = x_ref[0, sl, :].astype(jnp.float32)        # (L, P)
        B = b_ref[0, sl, :].astype(jnp.float32)        # (L, N)
        C = c_ref[0, sl, :].astype(jnp.float32)        # (L, N)
        # the per-step decays arrive in both orientations, so the inclusive
        # prefix sums come out as a column and as a row by masked
        # reductions (no cumsum, no transpose: neither lowers to Mosaic)
        la_col = jnp.log(jnp.maximum(
            a_col_ref[0, sl, :].astype(jnp.float32), 1e-37))        # (L, 1)
        la_row = jnp.log(jnp.maximum(
            a_row_ref[0, pl.ds(cidx, 1), :].astype(jnp.float32), 1e-37))  # (1, L)
        cum_col = jnp.sum(jnp.where(tri, la_row, 0.0), axis=1,
                          keepdims=True)                 # (L, 1) inclusive
        cum_row = jnp.sum(jnp.where(tri_t, la_col, 0.0), axis=0,
                          keepdims=True)                 # (1, L) inclusive
        total = jnp.sum(la_row, axis=1, keepdims=True)   # (1, 1)
        # within-chunk decay matrix exp(cum_t - cum_u) for u <= t
        decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)

        scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
        w = scores * decay                             # (L, L)
        y = jax.lax.dot(w, x, preferred_element_type=jnp.float32)

        # inter-chunk from carried state
        c_in = C * jnp.exp(cum_col)                    # (L, N)
        y += jax.lax.dot_general(c_in, h_prev, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)

        # state update
        b_out = B * jnp.exp(total - cum_col)           # (L, N)
        h_new = h_prev * jnp.exp(total) + jax.lax.dot_general(
            x, b_out, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        y_ref[0, sl, :] = y.astype(y_ref.dtype)
        return h_new

    h = jax.lax.fori_loop(0, n_chunks, body,
                          jnp.zeros((p, n), jnp.float32))
    state_out_ref[0] = h.astype(state_out_ref.dtype)


def ssd_scan(x, a, B, C, *, chunk: int = 128, interpret: bool | None = None):
    """x: (Bb,S,H,P); a: (Bb,S,H); B,C: (Bb,S,N). Returns (y, final_state).

    y: (Bb,S,H,P); final_state: (Bb,H,P,N) float32.

    ``interpret=None`` resolves via :func:`default_interpret` at call time
    (compiled on TPU/GPU, interpreter on CPU); pass an explicit bool to
    force either mode (tests cross-check the two).
    """
    if interpret is None:
        interpret = default_interpret()
    return _ssd_scan_jit(x, a, B, C, chunk=chunk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_scan_jit(x, a, B, C, *, chunk: int, interpret: bool):
    bb, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = s + pad
    n_chunks = sp // chunk

    # layouts: fold (B,H) -> G for x/a; B/C shared across heads (indexed by
    # batch only in the map)
    xt = x.transpose(0, 2, 1, 3).reshape(bb * h, sp, p)
    at = a.transpose(0, 2, 1)
    a_col = at.reshape(bb * h, sp, 1)
    a_row = at.reshape(bb * h, n_chunks, chunk)

    def xa_map(g):
        return (g, 0, 0)

    def bc_map(g):
        return (g // h, 0, 0)

    kern = functools.partial(_kernel, n_chunks=n_chunks, chunk=chunk)
    y, state = pl.pallas_call(
        kern,
        grid=(bb * h,),
        in_specs=[
            pl.BlockSpec((1, sp, p), xa_map),
            pl.BlockSpec((1, sp, 1), xa_map),
            pl.BlockSpec((1, n_chunks, chunk), xa_map),
            pl.BlockSpec((1, sp, n), bc_map),
            pl.BlockSpec((1, sp, n), bc_map),
        ],
        out_specs=[
            pl.BlockSpec((1, sp, p), xa_map),
            pl.BlockSpec((1, p, n), xa_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bb * h, sp, p), x.dtype),
            jax.ShapeDtypeStruct((bb * h, p, n), jnp.float32),
        ],
        interpret=interpret,
    )(xt, a_col, a_row, B, C)
    y = y.reshape(bb, h, sp, p).transpose(0, 2, 1, 3)[:, :s]
    state = state.reshape(bb, h, p, n)
    return y, state
