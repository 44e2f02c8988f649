"""Plain reference of the served solver: tAB-DEIS on the VP SDE.

The update from ``t_k`` to ``t_{k+1}`` is

    x_{k+1} = psi_k x_k + sum_j C[k, j] eps(x_{k-j}, t_{k-j}),

with ``psi_k = mu(t_{k+1}) / mu(t_k)`` and ``C[k, j] = mu(t_{k+1})
int_{t_k}^{t_{k+1}} l_j(t) rho'(t) dt``, where ``l_j`` is the Lagrange basis
over the last ``min(order, k) + 1`` grid times and ``rho = sigma / mu``
(Zhang & Chen, ICLR 2023, Eq. 14). The integral is taken in ``t`` with
``rho'(t) = beta(t) / (2 rho(t) alpha_bar(t))`` by Gauss-Legendre
quadrature, in float64 on the host.
"""
from __future__ import annotations

import numpy as np

_QUAD = 64


def _log_alpha_bar(diff: dict, t):
    return (-0.5 * t ** 2 * (diff["beta_max"] - diff["beta_min"])
            - t * diff["beta_min"])


def mu(diff: dict, t):
    return np.exp(0.5 * _log_alpha_bar(diff, t))


def rho(diff: dict, t):
    return np.sqrt(np.expm1(-_log_alpha_bar(diff, t)))


def timesteps(diff: dict, n: int) -> np.ndarray:
    """The decreasing grid of ``n`` steps from T to t0."""
    if diff["schedule"] != "quadratic":
        raise ValueError(f"no reference for schedule {diff['schedule']!r}")
    i = np.arange(n + 1, dtype=np.float64)
    return ((n - i) / n * np.sqrt(diff["T"])
            + i / n * np.sqrt(diff["t0"])) ** 2


def tab_coefficients(diff: dict, ts: np.ndarray, order: int):
    """(psi (n,), C (n, order + 1)) of tAB-DEIS of ``order`` on grid ts."""
    n = len(ts) - 1
    x, w = np.polynomial.legendre.leggauss(_QUAD)
    psi = mu(diff, ts[1:]) / mu(diff, ts[:-1])
    C = np.zeros((n, order + 1))
    for k in range(n):
        a, b = ts[k], ts[k + 1]
        t = 0.5 * (b - a) * x + 0.5 * (b + a)
        wt = 0.5 * (b - a) * w
        beta = diff["beta_min"] + t * (diff["beta_max"] - diff["beta_min"])
        drho = beta / (2.0 * rho(diff, t) * np.exp(_log_alpha_bar(diff, t)))
        nodes = ts[[k - j for j in range(min(order, k) + 1)]]
        for j in range(len(nodes)):
            basis = np.ones_like(t)
            for i, node in enumerate(nodes):
                if i != j:
                    basis = basis * (t - node) / (nodes[j] - node)
            C[k, j] = mu(diff, b) * np.sum(wt * basis * drho)
    return psi, C


def solver_order(solver: str) -> int:
    if not solver.startswith("tab"):
        raise ValueError(f"no reference for solver {solver!r}")
    return int(solver[3:])
