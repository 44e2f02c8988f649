"""Training substrate: optimizer math, checkpoint round-trip, data pipeline
determinism, loss decrease on real (synthetic-corpus) training."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.base import get_config
from repro.data.pipeline import MarkovTextSource, make_batch
from repro.models import transformer as T
from repro.training import checkpoint as CKPT
from repro.training.optimizer import AdamW, constant_schedule, cosine_schedule, global_norm
from repro.training.steps import make_train_step


def test_adamw_converges_quadratic():
    """AdamW drives a quadratic to its minimum."""
    opt = AdamW(constant_schedule(0.1), weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    target = jnp.array([1.0, 2.0])
    state = opt.init(params)
    loss = lambda p: jnp.sum((p["w"] - target) ** 2)
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(target),
                               atol=1e-2)


def test_grad_clip_bounds_update():
    opt = AdamW(constant_schedule(1.0), clip_norm=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = opt.init(params)
    huge = {"w": jnp.full(4, 1e6)}
    _, _, m = opt.update(huge, state, params)
    assert float(m["grad_norm"]) == pytest.approx(2e6, rel=1e-3)
    # effective grads clipped to norm 1 -> first Adam step is bounded
    # (bias-corrected first step is +-lr regardless, but must be finite)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-6)
    assert float(lr(100)) == pytest.approx(1e-4, rel=1e-2)
    assert float(lr(55)) < float(lr(11))


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 1000))
def test_global_norm_matches_numpy(seed):
    rng = np.random.RandomState(seed)
    tree = {"a": rng.randn(3, 4).astype(np.float32),
            "b": [rng.randn(5).astype(np.float32)]}
    got = float(global_norm(jax.tree.map(jnp.asarray, tree)))
    want = np.sqrt(sum((l ** 2).sum() for l in [tree["a"], tree["b"][0]]))
    assert got == pytest.approx(float(want), rel=1e-5)


def test_checkpoint_roundtrip_and_latest():
    cfg = get_config("gemma_2b").reduced()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 5, params, {"note": "a"})
        CKPT.save(d, 10, params, {"note": "b"})
        assert CKPT.latest_step(d) == 10
        restored, meta = CKPT.restore(d, params)
        assert meta["note"] == "b"
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


def test_checkpoint_missing_key_raises():
    with tempfile.TemporaryDirectory() as d:
        CKPT.save(d, 1, {"a": jnp.zeros(3)})
        with pytest.raises(KeyError):
            CKPT.restore(d, {"a": jnp.zeros(3), "b": jnp.zeros(2)})


def test_markov_source_deterministic_and_banded():
    src = MarkovTextSource(1024, seed=3)
    a = src.batch(7, 4, 64)
    b = src.batch(7, 4, 64)
    np.testing.assert_array_equal(a, b)
    c = src.batch(8, 4, 64)
    assert not np.array_equal(a, c)
    d = np.abs((a[:, 1:] - a[:, :-1]) % 1024)
    d = np.minimum(d, 1024 - d)
    assert (d < 16).mean() > 0.8  # banded transitions dominate


@pytest.mark.slow  # ~200s of real training across both objectives
@pytest.mark.parametrize("objective", ["ar", "diffusion"])
def test_loss_decreases_on_synthetic_corpus(objective):
    """30 steps of real training on the Markov corpus must reduce the loss --
    end-to-end: data pipeline -> model -> loss -> optimizer."""
    cfg = get_config("cifar10_scorenet").with_(objective=objective,
                                               vocab_size=256)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt = AdamW(constant_schedule(3e-4))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    src = MarkovTextSource(cfg.vocab_size, seed=0)
    rng = jax.random.PRNGKey(1)
    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in
                 make_batch(cfg, src, i, 16, 32).items()}
        rng, sub = jax.random.split(rng)
        params, opt_state, m = step(params, opt_state, batch, sub)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_kv_cache_greedy_matches_full_forward_greedy():
    """The KV-cache prefill and decode steps: greedy decoding through a
    prefilled cache (padded here to the decode length) gives the tokens of
    greedy decoding by repeated full forwards with no cache."""
    from repro.training.steps import make_decode_step, make_prefill_step
    cfg = get_config("gemma_2b").reduced().with_(objective="ar")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    prompt = np.array([1, 2, 3, 4, 5, 6, 7, 8])
    n_new, max_len = 6, 32
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg))
    logits, cache = prefill(params, {"tokens": jnp.asarray(prompt)[None]})

    def grow(leaf):         # the cache's sequence axis, padded to max_len
        if leaf.ndim >= 3 and leaf.shape[2] == len(prompt) and not (
                cfg.sliding_window and leaf.shape[2] == cfg.sliding_window):
            pad = [(0, 0)] * leaf.ndim
            pad[2] = (0, max_len - leaf.shape[2])
            return jnp.pad(leaf, pad)
        return leaf
    cache = dict(cache, blocks=jax.tree.map(grow, cache["blocks"]))
    tok = jnp.argmax(logits, -1)[:, None]
    got = [int(tok[0, 0])]
    for pos in range(len(prompt), len(prompt) + n_new - 1):
        logits, cache = decode(params, cache, tok, jnp.int32(pos))
        tok = jnp.argmax(logits, -1)[:, None]
        got.append(int(tok[0, 0]))

    # greedy via repeated FULL forwards (no cache) -- ground truth
    toks = list(prompt)
    want = []
    for _ in range(n_new):
        out = T.forward(params, cfg, tokens=jnp.asarray(toks)[None],
                        mode="train")
        nxt = int(jnp.argmax(out["logits"][0, -1]))
        want.append(nxt)
        toks.append(nxt)
    np.testing.assert_array_equal(np.asarray(got), np.array(want))
