"""Expert-parallel shard_map MoE vs the local reference.

Runs in a SUBPROCESS with 8 forced host devices (the main test process must
keep the single real CPU device — see conftest note), asserting that the
all_to_all scatter/gather path reproduces the local dense-dispatch MoE.
"""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.config import get_arch, reduced_config
from repro.models import Model
from repro.models.moe import moe_forward
from repro.distributed.moe_parallel import expert_parallel_moe

cfg = reduced_config(get_arch("qwen2-moe-a2.7b"))
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=8.0))
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
model = Model(cfg, expert_pad_multiple=4)
params = model.init_params(jax.random.PRNGKey(0))
moe_p = jax.tree.map(lambda a: a[0], params["blocks"]["pos0"])["moe"]
x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (4, 32, cfg.d_model))

y_ref, aux_ref = moe_forward(moe_p, cfg, x)
for beta, use_kernel in [(1, False), (4, False), (1, True)]:
    with mesh:
        y, aux = expert_parallel_moe(moe_p, cfg, x, mesh, beta=beta,
                                     use_kernel=use_kernel)
    err = float(jnp.abs(y - y_ref).max())
    cnt_err = int(jnp.abs(aux["expert_counts"]
                          - aux_ref["expert_counts"]).max())
    assert err < 5e-4, (beta, use_kernel, err)
    assert cnt_err == 0, (beta, use_kernel)
    print(f"beta={beta} kernel={use_kernel} err={err:.2e} OK")

# the grouped EP variant is DROPLESS: it must equal the all-experts
# oracle even at capacity_factor=1.0 (where the a2a capacity path drops)
from repro.distributed.moe_parallel import expert_parallel_moe_grouped
from repro.models.moe import moe_forward_oracle
cfg1 = dataclasses.replace(cfg, moe=dataclasses.replace(
    cfg.moe, capacity_factor=1.0))
y_or = moe_forward_oracle(moe_p, cfg1, x)
for beta, use_kernel in [(1, False), (4, False), (1, True)]:
    with mesh:
        yg, auxg = expert_parallel_moe_grouped(
            moe_p, cfg1, x, mesh, beta=beta, use_kernel=use_kernel)
    err = float(jnp.abs(yg - y_or).max())
    assert err < 5e-5, ("grouped", beta, use_kernel, err)
    cnt_err = int(jnp.abs(auxg["expert_counts"]
                          - aux_ref["expert_counts"]).max())
    assert cnt_err == 0, ("grouped", beta, use_kernel)
    print(f"grouped beta={beta} kernel={use_kernel} err={err:.2e} OK")
print("ALL OK")
"""


def test_expert_parallel_matches_local():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={**os.environ,
             "PYTHONPATH": str(Path(__file__).parent.parent / "src"),
             "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=560)
    assert "ALL OK" in res.stdout, res.stdout + "\n" + res.stderr[-3000:]
