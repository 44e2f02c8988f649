"""The configuration as it is run, and its weights made from the seed.

The weights are the benchmark's, not the program's: one jitted call draws
every parameter on the device in the type it is served in, in the layout
the program's ``init_params`` declares (read by ``jax.eval_shape``, which
runs nothing), with the distributions the configuration's reference gives.
The stacked per-layer leaves are drawn one layer at a time inside the
call, so no float32 copy of a whole stack is ever held.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


def program_config(conf: dict, reduced_for_test: dict | None = None):
    """The program's ModelConfig for the configuration file ``conf``; the
    numbers under ``model`` must be those the program runs."""
    from repro.configs.base import get_config
    cfg = get_config(conf["arch"], **conf.get("overrides", {}))
    cfg = cfg.with_(objective="diffusion", **(reduced_for_test or {}))
    want = dict(conf["model"], **(reduced_for_test or {}))
    got = dataclasses.asdict(cfg)
    got["head_dim"] = cfg.resolved_head_dim
    diff = {k: (got.get(k), v) for k, v in want.items() if got.get(k) != v}
    if diff:
        raise ValueError(f"{conf['name']}: the program's config differs from "
                         f"the configuration file: {diff}")
    return cfg


def param_layout(cfg):
    """The program's parameter tree, as shapes."""
    from repro.models import transformer as T
    return jax.eval_shape(lambda k: T.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def make_weights(cfg, model: dict, init_std, seed: int):
    """All parameters, drawn on the device from ``seed`` in one jitted call.

    ``init_std(path, model)`` gives each leaf's standard deviation (0:
    zeros). Leaves under ``blocks`` carry the layer axis first and are
    drawn layer by layer."""
    layout = param_layout(cfg)
    paths, treedef = jax.tree_util.tree_flatten_with_path(layout)
    names = [tuple(getattr(k, "key", str(k)) for k in p) for p, _ in paths]
    stds = [init_std(n, model) for n in names]

    def draw(key):
        out = []
        for i, ((_, sd), name, std) in enumerate(zip(paths, names, stds)):
            k = jax.random.fold_in(key, i)
            if std == 0.0:
                out.append(jnp.zeros(sd.shape, sd.dtype))
            elif name[0] == "blocks":
                def one(j, k=k, sd=sd, std=std):
                    return (jax.random.normal(jax.random.fold_in(k, j),
                                              sd.shape[1:], jnp.float32)
                            * std).astype(sd.dtype)
                out.append(jax.lax.map(one, jnp.arange(sd.shape[0])))
            else:
                out.append((jax.random.normal(k, sd.shape, jnp.float32)
                            * std).astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(draw)(jax.random.PRNGKey(seed))
