"""Expert-parallel MoE (shard_map + all_to_all) vs the single-device MoE.

Runs in a SUBPROCESS with 4 fake CPU devices so the main pytest process keeps
its single-device view (the smoke-test constraint). The subprocess asserts
numerical equality against models/layers.moe on identical weights/tokens.
"""
import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess with fake multi-device CPU mesh

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs.base import get_config
from repro.models import layers as L
from repro.sharding.expert_parallel import moe_expert_parallel

cfg = get_config("mixtral_8x7b").reduced().with_(objective="ar")
cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, num_experts=4, top_k=2,
                                        capacity_factor=100.0))
params = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
mesh = jax.make_mesh((4,), ("data",))
b, s, d = 4, 32, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (b, s, d))

# reference: per-row single-device MoE (cap factor high => no drops)
ref, aux_ref = L.moe(params, cfg, x)

with jax.set_mesh(mesh):
    out, aux = moe_expert_parallel(params, cfg, x, mesh, axis="data")
err = float(jnp.abs(out - ref).max())
print("max err:", err)
assert err < 2e-4, err
# load-balance stat within tolerance (expert-parallel averages over shards)
assert abs(float(aux["moe_lb"]) - float(aux_ref["moe_lb"])) < 1e-3
print("EXPERT_PARALLEL_OK")
"""


@pytest.mark.parametrize("_", [0])
def test_expert_parallel_matches_single_device(_, tmp_path):
    script = tmp_path / "ep_check.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(os.path.join(
                   os.path.dirname(__file__), "..", "src")))
    res = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=600, env=env)
    assert "EXPERT_PARALLEL_OK" in res.stdout, (res.stdout, res.stderr[-3000:])
