"""The held-expert MoE path on the CPU at a tiny size: the grouped expert
kernel (interpret mode) against its plain loop, the routing, the split of
the experts into shares, and the dropless layer's per-position
independence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels.moe_experts import moe_experts, moe_experts_ref
from repro.models import layers as L
from repro.models import transformer as T

D, F, HELD, LAYERS = 64, 32, 4, 2


def _weights(dtype, key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    wg = jax.random.normal(ks[0], (LAYERS, HELD, D, F)) / 8
    wu = jax.random.normal(ks[1], (LAYERS, HELD, D, F)) / 8
    wd = jax.random.normal(ks[2], (LAYERS, HELD, F, D)) / 6
    return [w.astype(dtype) for w in (wg, wu, wd)]


def _gates(t, key=1, p=0.5):
    r = jax.random.uniform(jax.random.PRNGKey(key), (t, HELD))
    return jnp.where(r > p, r, 0.0)


@pytest.mark.parametrize("case", ["mixed", "expert_with_no_token",
                                  "all_on_one_expert", "no_token_routed",
                                  "chunks_of_512"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_the_plain_loop(case, dtype):
    t = 700 if case == "chunks_of_512" else 200
    x = jax.random.normal(jax.random.PRNGKey(2), (t, D)).astype(dtype)
    gates = _gates(t)
    if case == "expert_with_no_token":
        gates = gates.at[:, 2].set(0.0)
    elif case == "all_on_one_expert":
        gates = jnp.zeros((t, HELD)).at[:, 1].set(0.7)
    elif case == "no_token_routed":
        gates = jnp.zeros((t, HELD))
    w = _weights(dtype)
    got = moe_experts(x, gates, *w, 1, per_token=HELD, interpret=True)
    want = moe_experts_ref(x, gates, *w, 1)
    assert got.shape == (t, D) and got.dtype == jnp.float32
    # XLA:CPU may round an elementwise op (the sigmoid) differently at
    # another array shape: one unit in the last place of the dtype
    tol = 1e-6 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol * float(jnp.max(jnp.abs(want))))
    if case == "no_token_routed":
        assert not np.any(np.asarray(got))


def test_a_tokens_output_does_not_depend_on_the_others():
    """Bitwise: a token alone, the same token among others (whose routing
    moves its rows to other blocks), and the others changed."""
    x = jax.random.normal(jax.random.PRNGKey(3), (96, D)).astype(jnp.bfloat16)
    gates = _gates(96)
    w = _weights(jnp.bfloat16)

    def run(x, g):
        return np.asarray(moe_experts(x, g, *w, 0, per_token=HELD,
                                      interpret=True))
    full = run(x, gates)
    for i in (0, 17, 95):
        np.testing.assert_array_equal(run(x[i:i + 1], gates[i:i + 1])[0],
                                      full[i])
    other = run(x.at[:40].set(-x[:40]), gates.at[:40].set(gates[::-1][:40]))
    np.testing.assert_array_equal(other[40:], full[40:])


def test_gradients_are_those_of_the_plain_loop():
    x = jax.random.normal(jax.random.PRNGKey(4), (40, D))
    gates = _gates(40)
    w = _weights(jnp.float32)

    def loss(fn):
        return lambda x, g, *w: jnp.sum(jnp.sin(fn(x, g, *w)))
    got = jax.grad(loss(lambda x, g, *w: moe_experts(
        x, g, *w, 1, per_token=HELD, interpret=True)), argnums=(0, 1, 2))(
        x, gates, *w)
    want = jax.grad(loss(lambda x, g, *w: moe_experts_ref(x, g, *w, 1)),
                    argnums=(0, 1, 2))(x, gates, *w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def _cfg(held=8, offset=0, top_k=2):
    return get_config(
        "sdar_30b_a3b", n_layers=2, d_model=D, n_heads=4, n_kv_heads=2,
        head_dim=16, vocab_size=128, dtype="float32",
        moe={"num_experts": 8, "top_k": top_k, "expert_d_ff": F,
             "experts_held": held, "expert_offset": offset}).with_(
        objective="diffusion")


def test_routing_keeps_the_held_choices_renormalised():
    cfg = _cfg(held=2, offset=4, top_k=3)
    router = jax.random.normal(jax.random.PRNGKey(5), (D, 8), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (50, D), jnp.float32)
    gates, hits = L.route_held(router, cfg, x)
    probs = jax.nn.softmax(x @ router, axis=-1)
    vals, idx = jax.lax.top_k(probs, 3)
    vals = vals / vals.sum(-1, keepdims=True)
    for t in range(50):
        want = np.zeros(2)
        for v, i in zip(np.asarray(vals[t]), np.asarray(idx[t])):
            if 4 <= i < 6:
                want[i - 4] = v
        np.testing.assert_allclose(np.asarray(gates[t]), want, rtol=1e-5)
        assert int(hits[t]) == int(np.sum(want > 0))


def test_the_shares_add_up_to_the_uncut_layer():
    """8 experts held as 4 shares of 2 (a deployment over 4 chips): the
    shares' partial results add up to the layer that holds all 8, and the
    counts of the assignments they computed add up to every position's
    top-k."""
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    moe = params["blocks"]["slot0"]["moe"]
    experts = {k: v for k, v in moe.items() if k != "router"}
    router = {"router": moe["router"][1]}
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, D))
    whole, n_whole = L.moe_held(router, experts, 1, cfg, x)
    parts, n_parts = 0.0, 0
    for s in range(4):
        share = cfg.with_(moe=dataclasses.replace(
            cfg.moe, experts_held=2, expert_offset=2 * s))
        out, n = L.moe_held(router, {k: v[:, 2 * s:2 * s + 2]
                                     for k, v in experts.items()},
                            1, share, x)
        parts, n_parts = parts + out, n_parts + n
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(n_parts), np.asarray(n_whole))
    np.testing.assert_array_equal(np.asarray(n_whole), [24 * 2, 24 * 2])


def test_a_positions_output_depends_on_its_own_state_alone():
    """Dropless: no capacity is shared, so changing the padded tail leaves
    every valid position of the row bitwise as it was, and the count reads
    only the valid positions."""
    cfg = _cfg(held=4, offset=2)
    params = T.init_params(cfg, jax.random.PRNGKey(9))
    moe = params["blocks"]["slot0"]["moe"]
    experts = {k: v for k, v in moe.items() if k != "router"}
    router = {"router": moe["router"][0]}
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 16, D))
    vl = jnp.array([10, 16])
    a, na = L.moe_held(router, experts, 0, cfg, x, vl)
    b, nb = L.moe_held(router, experts, 0, cfg, x.at[0, 10:].mul(-3.0), vl)
    np.testing.assert_array_equal(np.asarray(a[0, :10]), np.asarray(b[0, :10]))
    np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))
    np.testing.assert_array_equal(np.asarray(na), np.asarray(nb))
    _, n_all = L.moe_held(router, experts, 0, cfg, x)
    assert int(na[0]) <= int(n_all[0]) and int(na[1]) == int(n_all[1])
