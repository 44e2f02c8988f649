"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and holding the longest, is
solved again by the plain reference: the configuration's reference eps-net
in float32 at ``highest`` matmul precision, the reference tAB-DEIS update
(:mod:`deis_ref`) from the request's own prior, and the rounding through
the LM head. The number compared is the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position, over every sampled position.

The control puts the reference in the program's place one precision
below the configuration's (bfloat16): every matmul operand of the eps-net
rounded to float8 e4m3. It reads the same gap for the tokens it puts
first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import deis_ref

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def make_mm(precision: str):
    """Matrix product of the reference (``f32``) or of the control
    (``fp8``: operands rounded to float8 e4m3 first); accumulation in f32
    at ``highest`` precision either way."""
    if precision == "f32":
        def q(a):
            return a.astype(F32)
    elif precision == "fp8":
        def q(a):
            return a.astype(F32).astype(jnp.float8_e4m3fn).astype(F32)
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def mm(a, b, spec=None):
        a, b = q(a), q(b)
        if spec is None:
            return jnp.tensordot(a, b, axes=1, precision=HIGHEST)
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def prior(seed: int, length: int, d: int, pad_to: int, prior_std: float):
    """The request's prior x_T, as its seed defines it: the first half of
    ``split(PRNGKey(seed))`` draws a ``(length, d)`` standard normal; the
    positions past ``length`` are zero."""
    k_prior = jax.random.split(jax.random.PRNGKey(seed))[0]
    x = jax.random.normal(k_prior, (length, d), F32) * prior_std
    return jnp.pad(x, ((0, pad_to - length), (0, 0)))[None]


class Reference:
    """Solves requests with the configuration's reference model, one row
    padded to ``pad_to`` positions (the padded keys are masked, so the
    valid positions are those of a solve at the true length)."""

    def __init__(self, ref_mod, params, model: dict, diff: dict,
                 pad_to: int, precision: str = "f32"):
        self.params, self.model, self.diff = params, model, diff
        self.pad_to = pad_to
        mm, mm32 = make_mm(precision), make_mm("f32")

        @jax.jit
        def step(params, x, hist, t, length, psi, c):
            e = ref_mod.eps(params, model, diff, x, jnp.full((1,), t, F32),
                            jnp.full((1,), length, jnp.int32), mm)
            hist = jnp.concatenate([e[None], hist[:-1]], axis=0)
            return psi * x + jnp.tensordot(c, hist, axes=1,
                                           precision=HIGHEST), hist

        @jax.jit
        def gaps(params, x0, tokens, length):
            lg = ref_mod.logits(params, diff, x0[0], mm32)      # (pad_to, V)
            got = jnp.take_along_axis(lg, tokens[:, None], axis=1)[:, 0]
            valid = jnp.arange(lg.shape[0]) < length
            return (jnp.where(valid, lg.max(axis=1) - got, 0.0),
                    jnp.argmax(lg, axis=1))

        self._step, self._gaps = step, gaps

    def solve(self, seed: int, length: int, nfe: int, solver: str):
        """x_0 (1, pad_to, d) of one request."""
        order = deis_ref.solver_order(solver)
        ts = deis_ref.timesteps(self.diff, max(1, nfe))
        psi, C = deis_ref.tab_coefficients(self.diff, ts, order)
        d = self.model["d_model"]
        x = prior(seed, length, d, self.pad_to, self.diff["prior_std"])
        hist = jnp.zeros((order + 1,) + x.shape, F32)
        for k in range(len(ts) - 1):
            x, hist = self._step(self.params, x, hist, jnp.float32(ts[k]),
                                 jnp.int32(length), jnp.float32(psi[k]),
                                 jnp.asarray(C[k], F32))
        return x

    def gap(self, x0, tokens, length: int):
        """(widest gap of ``tokens`` below the best logit of x0's decode,
        the decode's own greedy tokens). A token array of the wrong shape
        or range reads an infinite gap."""
        tok = np.asarray(tokens).astype(np.int64).reshape(-1)
        vocab = self.model["vocab_size"]
        if tok.shape != (length,) or np.any(tok < 0) or np.any(tok >= vocab):
            return float("inf"), None
        padded = np.zeros(self.pad_to, np.int32)
        padded[:length] = tok
        g, best = self._gaps(self.params, x0, jnp.asarray(padded),
                             jnp.int32(length))
        return float(np.max(np.asarray(g))), np.asarray(best)[:length]


def sample(finished: list, n: int, rng: np.random.Generator) -> list:
    """The longest finished request (first by uid among equals) and
    ``n - 1`` others drawn from ``rng``. ``finished``: (uid, send, result)."""
    if not finished:
        return []
    order = sorted(finished, key=lambda f: f[0])
    longest = max(order, key=lambda f: f[1].seq_len)
    rest = [f for f in order if f is not longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(ref: Reference, picked: list, solver: str,
            control: Reference | None = None) -> dict:
    """Reference gaps of the served tokens (and of the control's tokens)."""
    gap, ctl, n_tok = 0.0, 0.0, 0
    for _uid, send, res in picked:
        x0 = ref.solve(send.seed, send.seq_len, send.nfe, solver)
        gap = max(gap, ref.gap(x0, res.tokens, send.seq_len)[0])
        n_tok += send.seq_len
        if control is not None:
            xc = control.solve(send.seed, send.seq_len, send.nfe, solver)
            _, ctl_tokens = ref.gap(xc, np.zeros(send.seq_len, np.int32),
                                    send.seq_len)
            ctl = max(ctl, ref.gap(x0, ctl_tokens, send.seq_len)[0])
    out = {"max_logit_gap": gap, "tokens_compared": n_tok,
           "requests_compared": len(picked)}
    if control is not None:
        out["control_max_logit_gap"] = ctl
    return out
