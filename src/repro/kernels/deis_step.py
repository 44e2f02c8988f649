"""Fused DEIS multistep update kernel (paper Eq. 14), stacked-plan form.

    x'_row = psi_row * x_row + sum_{j<r} C_row[j] * eps_hist[j, row]
             (+ s_row * noise_row)                       [stochastic leaf]
    err_row = max_elem | sum_{j<r} E_row[j] * eps_hist[j, row] |   [error pair]

The update is memory-bound (zero MXU work): the win over XLA's un-fused form
is reading x and each eps exactly once from HBM instead of r+3 round trips
for the partial sums, the noise add and the error-pair combination. VPU-
tiled: blocks are (BLK_M, 128)-aligned in VMEM; per-row scalars (psi, C,
s, E) ride along as one small ``(R, 1, ncols)`` VMEM operand indexed by the
row grid axis, which is what lets one kernel serve a stacked serving group
whose rows carry different solver coefficients. Every block's last two
dimensions either tile (8, 128) or span the whole array -- the layout rule
Mosaic enforces -- so the kernel compiles on a TPU for any row count R.

The error output is an exact Linf: each block writes its lane-wise partial
``max |E . hist|`` as one lane-dense ``(1, BLK_D)`` row and the caller
reduces with an outer ``jnp.max`` -- f32 max is reduction-order
independent, so a row's error (and therefore early-exit retirement) is
bitwise identical between a solo solve (R=1) and any stacked grouping of
the same request.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import runtime

BLK_M = 256
BLK_D = 128


def default_interpret() -> bool:
    """Compiled by default; interpret only where Pallas cannot lower.

    Resolved through the shared per-kernel capability table
    (:func:`repro.kernels.runtime.default_interpret`): Mosaic on TPU,
    Triton on GPU, interpreter on CPU only.
    """
    return runtime.default_interpret("deis_step")


def _kernel(scal_ref, *refs, r, has_noise, has_err):
    # scal_ref: (1, 1, ncols) f32 row laid out [psi, C_0..C_{r-1}, s?, E_*?];
    # refs: x_ref (1,BM,BD), hist_ref (r,1,BM,BD), [noise_ref (1,BM,BD)],
    #       out_ref (1,BM,BD), [err_ref (1,1,1,BD)]
    x_ref = refs[0]
    hist_ref = refs[1]
    noise_ref = refs[2] if has_noise else None
    out_idx = 3 if has_noise else 2
    out_ref = refs[out_idx]
    err_ref = refs[out_idx + 1] if has_err else None

    acc = scal_ref[0, 0, 0] * x_ref[0].astype(jnp.float32)
    for j in range(r):  # static unroll; r <= 4
        acc += scal_ref[0, 0, 1 + j] * hist_ref[j, 0].astype(jnp.float32)
    if has_noise:
        acc += scal_ref[0, 0, 1 + r] * noise_ref[0].astype(jnp.float32)
    out_ref[0] = acc.astype(out_ref.dtype)

    if has_err:
        off = 1 + r + (1 if has_noise else 0)
        e = scal_ref[0, 0, off] * hist_ref[0, 0].astype(jnp.float32)
        for j in range(1, r):
            e += scal_ref[0, 0, off + j] * hist_ref[j, 0].astype(jnp.float32)
        err_ref[0, 0] = jnp.max(jnp.abs(e), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("has_err", "interpret"))
def _fused_ab_jit(scal, x, hist, noise, *, has_err: bool, interpret: bool):
    has_noise = noise is not None
    n_rows, m, d = x.shape
    r = hist.shape[0]
    ncols = scal.shape[1]
    # pad to tile multiples
    pm = (-m) % BLK_M
    pd = (-d) % BLK_D
    xp = jnp.pad(x, ((0, 0), (0, pm), (0, pd)))
    hp = jnp.pad(hist, ((0, 0), (0, 0), (0, pm), (0, pd)))
    nbm, nbd = (m + pm) // BLK_M, (d + pd) // BLK_D

    in_specs = [
        pl.BlockSpec((1, 1, ncols), lambda g, i, j: (g, 0, 0)),
        pl.BlockSpec((1, BLK_M, BLK_D), lambda g, i, j: (g, i, j)),
        pl.BlockSpec((r, 1, BLK_M, BLK_D), lambda g, i, j: (0, g, i, j)),
    ]
    operands = [scal[:, None, :], xp, hp]
    if has_noise:
        in_specs.append(pl.BlockSpec((1, BLK_M, BLK_D),
                                     lambda g, i, j: (g, i, j)))
        operands.append(jnp.pad(noise, ((0, 0), (0, pm), (0, pd))))
    out_specs = [pl.BlockSpec((1, BLK_M, BLK_D), lambda g, i, j: (g, i, j))]
    out_shape = [jax.ShapeDtypeStruct(xp.shape, x.dtype)]
    if has_err:
        out_specs.append(pl.BlockSpec((1, 1, 1, BLK_D),
                                      lambda g, i, j: (g, i, 0, j)))
        out_shape.append(jax.ShapeDtypeStruct((n_rows, nbm, 1, nbd * BLK_D),
                                              jnp.float32))

    res = pl.pallas_call(
        functools.partial(_kernel, r=r, has_noise=has_noise, has_err=has_err),
        grid=(n_rows, nbm, nbd),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*operands)
    out = res[0][:, :m, :d]
    # exact Linf: per-block partial maxima reduced by an order-independent max
    err = jnp.max(res[1], axis=(1, 2, 3)) if has_err else None
    return out, err


def fused_ab_step(x, hist, psi, coeffs, *, s=None, noise=None, err_coeffs=None,
                  interpret: bool | None = None):
    """One-HBM-round-trip stacked AB step.

    x: (R, M, D); hist: (r, R, M, D); psi: (R,); coeffs: (R, r).
    Optional stochastic leaf: s (R,) scales noise (R, M, D) (drawn by the
    caller -- PRNG semantics stay outside the kernel). Optional error pair:
    err_coeffs (R, r) yields err (R,) = per-row Linf of the embedded
    lower-order difference. Returns ``(x_new, err-or-None)``.

    ``interpret=None`` resolves via :func:`default_interpret` at call time
    (compiled on TPU/GPU, interpreter on CPU); pass an explicit bool to
    force either mode (tests cross-check the two).
    """
    if interpret is None:
        interpret = default_interpret()
    cols = [psi.astype(jnp.float32)[:, None], coeffs.astype(jnp.float32)]
    if noise is not None:
        cols.append(s.astype(jnp.float32)[:, None])
    if err_coeffs is not None:
        cols.append(err_coeffs.astype(jnp.float32))
    scal = jnp.concatenate(cols, axis=1)
    return _fused_ab_jit(scal, x, hist, noise,
                         has_err=err_coeffs is not None, interpret=interpret)


def deis_step(x, eps_hist, psi, coeffs, *, interpret: bool | None = None):
    """x: (M, D); eps_hist: (R, M, D); psi scalar; coeffs: (R,).

    Single-request deterministic form: one row of :func:`fused_ab_step`
    (the serving engine calls the stacked entry directly)."""
    if interpret is None:
        interpret = default_interpret()
    scal = jnp.concatenate([jnp.reshape(psi, (1, 1)).astype(jnp.float32),
                            coeffs.astype(jnp.float32)[None]], axis=1)
    out, _ = _fused_ab_jit(scal, x[None], eps_hist[:, None], None,
                           has_err=False, interpret=interpret)
    return out[0]
