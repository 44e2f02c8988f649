"""Per-architecture smoke tests (assignment requirement): a REDUCED variant of
each family runs one forward + one train step on CPU; output shapes asserted,
no NaNs. Full configs are exercised only via the dry-run."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ARCH_IDS, get_config
from repro.data.pipeline import make_batch, MarkovTextSource
from repro.models import transformer as T
from repro.training.optimizer import AdamW, constant_schedule
from repro.training.steps import make_train_step, make_prefill_step, make_decode_step

pytestmark = pytest.mark.slow  # model-zoo sweep: one forward + train step per architecture

ARCHS = [a for a in ARCH_IDS if a != "cifar10_scorenet"]


def _setup(arch, objective):
    cfg = get_config(arch).reduced().with_(objective=objective)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    src = MarkovTextSource(cfg.vocab_size, seed=1)
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(cfg, src, 0, batch=2, seq=32).items()}
    return cfg, params, batch


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_config_constraints(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers <= 8 and cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.num_experts <= 4


@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("objective", ["ar", "diffusion"])
def test_one_train_step(arch, objective):
    cfg, params, batch = _setup(arch, objective)
    opt = AdamW(constant_schedule(1e-3))
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    params2, opt_state2, metrics = step(params, opt_state, batch,
                                        jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"])), (arch, objective)
    assert np.isfinite(float(metrics["grad_norm"]))
    # params changed
    l0 = jax.tree.leaves(params)[0]
    l1 = jax.tree.leaves(params2)[0]
    assert l0.shape == l1.shape
    assert not np.allclose(np.asarray(l0, np.float32), np.asarray(l1, np.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    cfg, params, batch = _setup(arch, "ar")
    out = T.forward(params, cfg, tokens=batch["tokens"], mode="train",
                    prefix=batch.get("prefix"), frames=batch.get("frames"))
    b, s = batch["tokens"].shape
    extra = cfg.prefix_tokens if cfg.arch_type == "vlm" else 0
    assert out["logits"].shape == (b, s + extra, cfg.vocab_size)
    assert np.isfinite(np.asarray(out["logits"], np.float32)).all()


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_then_decode_matches_full_forward(arch):
    """KV-cache correctness: decode logits == full-forward logits at the last
    position (MoE capacity raised so no tokens drop; the comparison is exact
    semantics, not approximation)."""
    cfg, params, batch = _setup(arch, "ar")
    if cfg.moe is not None:
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=100.0))
    tok = batch["tokens"]
    b, s1 = tok.shape
    s = s1 - 1
    kw = {k: batch[k] for k in ("prefix", "frames") if k in batch}
    full = T.forward(params, cfg, tokens=tok, mode="train", **kw)
    pf = T.forward(params, cfg, tokens=tok[:, :s], mode="prefill", **kw)
    cache = dict(pf["cache"])
    p = cfg.prefix_tokens if cfg.arch_type == "vlm" else 0

    def pad_kv(path, leaf):
        name = jax.tree_util.keystr(path)
        is_kv = name.endswith("['k']") or name.endswith("['v']")
        if is_kv and leaf.ndim == 5 and not (
                cfg.sliding_window and leaf.shape[2] == cfg.sliding_window):
            padw = [(0, 0)] * 5
            padw[2] = (0, 1)
            return jnp.pad(leaf, padw)
        return leaf

    cache["blocks"] = jax.tree_util.tree_map_with_path(pad_kv, cache["blocks"])
    dec = T.forward(params, cfg, tokens=tok[:, s:], mode="decode",
                    cache=cache, cache_index=jnp.int32(s + p))
    a = np.asarray(full["logits"][:, -1], np.float32)
    b_ = np.asarray(dec["logits"][:, -1], np.float32)
    np.testing.assert_allclose(a, b_, rtol=2e-3, atol=2e-3 * np.abs(a).max())


def test_swa_ring_buffer_decode_matches_full():
    """Sliding-window ring cache: long decode sequence, window < seq."""
    cfg = get_config("h2o_danube_3_4b").reduced().with_(objective="ar")
    assert cfg.sliding_window == 16
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, 41), 0, cfg.vocab_size)
    s = 40
    full = T.forward(params, cfg, tokens=tok, mode="train")
    pf = T.forward(params, cfg, tokens=tok[:, :s], mode="prefill")
    dec = T.forward(params, cfg, tokens=tok[:, s:], mode="decode",
                    cache=pf["cache"], cache_index=jnp.int32(s))
    np.testing.assert_allclose(np.asarray(full["logits"][:, -1], np.float32),
                               np.asarray(dec["logits"][:, -1], np.float32),
                               rtol=2e-3, atol=1e-3)


def test_hybrid_layer_pattern():
    cfg = get_config("jamba_1p5_large")
    kinds = ["attn" if cfg.is_attn_layer(i) else "ssm"
             for i in range(cfg.attn_every)]
    assert kinds.count("attn") == 1  # 1:7 attention:mamba (arXiv:2403.19887)
    assert cfg.is_moe_layer(1) and not cfg.is_moe_layer(0)


def test_moe_capacity_drops_are_bounded():
    """With capacity_factor=1.0 some tokens drop but output stays finite and
    the load-balance loss is positive."""
    cfg = get_config("mixtral_8x7b").reduced().with_(objective="ar")
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, capacity_factor=1.0))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    out = T.forward(params, cfg, tokens=tok, mode="train")
    assert np.isfinite(np.asarray(out["logits"], np.float32)).all()
    assert float(out["aux"]["moe_lb"]) > 0


def test_diffusion_lm_sampling_roundtrip():
    """Train-free check: DEIS sampling through a random reduced backbone
    produces tokens of the right shape with finite embeddings."""
    from repro.core import VPSDE, get_timesteps, make_plan
    from repro.diffusion import lm as DLM
    cfg = get_config("gemma_2b").reduced()  # diffusion objective default off;
    cfg = cfg.with_(objective="diffusion")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    sde = VPSDE()
    plan = make_plan("tab2", sde, get_timesteps(sde, 6, "quadratic"))
    toks, x0 = DLM.sample_tokens(params, cfg, plan, jax.random.PRNGKey(1),
                                 batch=2, seq_len=16,
                                 prior_std=sde.prior_std())
    assert toks.shape == (2, 16)
    assert np.isfinite(np.asarray(x0)).all()


@pytest.mark.parametrize("arch", ["gemma_2b", "h2o_danube_3_4b", "mamba2_2p7b"])
def test_pallas_kernel_routing_matches_xla(arch):
    """use_pallas=True routes attention/SSD through the Pallas kernels
    (interpret mode on CPU) and must match the XLA path."""
    cfg = get_config(arch).reduced().with_(objective="ar")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab_size)
    a = T.forward(params, cfg, tokens=tok, mode="train")["logits"]
    b = T.forward(params, cfg, tokens=tok, mode="train",
                  use_pallas=True)["logits"]
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("seq_len,rows", [(8, 8), (16, 8), (32, 8), (64, 4),
                                          (128, 2), (256, 2)])
def test_tile_rows_fill_the_ridge_from_the_bucket(seq_len, rows):
    """An eps tile holds about 256 tokens, 2 to 8 rows, chosen from the
    bucket's length; the lowered eps loop slices that many rows per trip
    whatever the group's size (seq 128 and 256 keep the 2-row body)."""
    import re
    from repro.diffusion import lm as DLM
    assert DLM.tile_rows(seq_len) == rows
    cfg = get_config("h2o_danube_3_4b").reduced().with_(
        objective="diffusion", n_layers=1)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    d = cfg.d_model
    for r in (1, 3, 8):
        text = jax.jit(lambda p, x, t, vl: DLM.make_tiled_eps_fn(
            p, cfg, valid_len=vl)(x, t)).lower(
                params, jax.ShapeDtypeStruct((r, seq_len, d), jnp.float32),
                jax.ShapeDtypeStruct((r,), jnp.float32),
                jax.ShapeDtypeStruct((r,), jnp.int32)).as_text()
        padded = -(-r // rows) * rows + rows     # whole tiles + a spare
        sliced = re.findall(
            rf"dynamic_slice .*\(tensor<{padded}x{seq_len}x{d}xf32>.*"
            rf"-> tensor<(\d+)x{seq_len}x{d}xf32>", text)
        assert sliced == [str(rows)]


@pytest.mark.parametrize("seq_len", [32, 64, 128])
@pytest.mark.parametrize("width", [256, 3840])
def test_diffusion_eps_row_is_batch_invariant_bf16(width, seq_len):
    """A row's served eps in bf16 does not depend on how many rows share
    the call -- the serving invariant (stacked row == solo row, bitwise) at
    the served dtype. The batched forward breaks it at d_model 3840 even on
    the CPU (its time MLP is a (B, D) product, which rounds differently at
    B = 1 than at B = 4); the serving executors' eps, a loop over fixed
    tiles of rows, holds at every group size, at each bucket's tile width
    (8, 4 and 2 rows at seq 32, 64 and 128)."""
    from repro.diffusion import lm as DLM
    cfg = get_config("h2o_danube_3_4b").reduced().with_(
        objective="diffusion", dtype="bfloat16", n_layers=1, d_model=width)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, seq_len, width),
                          jnp.float32)
    t = jnp.linspace(0.3, 0.9, 8, dtype=jnp.float32)
    lens = jnp.full((8,), seq_len, jnp.int32)
    eps = jax.jit(lambda p, x, t, vl: DLM.make_tiled_eps_fn(
        p, cfg, valid_len=vl)(x, t))
    full = np.asarray(eps(params, x, t, lens))
    for i in range(8):
        np.testing.assert_array_equal(
            full[i], np.asarray(eps(params, x[i:i + 1], t[i:i + 1],
                                    lens[i:i + 1]))[0])
    # every group size: a row's place in its tile, part-filled tiles
    for r in range(2, 8):
        np.testing.assert_array_equal(
            full[:r], np.asarray(eps(params, x[:r], t[:r], lens[:r])))
    # a one-tile group still runs the loop: XLA inlines a loop it can see
    # runs once, which would give small groups a program of their own
    for r in sorted({1, DLM.tile_rows(seq_len)}):
        assert "while(" in eps.lower(params, x[:r], t[:r],
                                     lens[:r]).compile().as_text()
