"""Dry-run path on an 8-fake-device mesh in a subprocess (fast twin of the
512-device production dry-run; the full sweep artifacts live in
experiments/dryrun/)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax, jax.numpy as jnp
from repro.configs import get_arch
from repro.distributed.sharding import (DEFAULT_RULES,
                                        mesh_context, shard_params_tree)
from repro.models.transformer import Model, shapes_and_axes
from repro.train.train_step import make_train_step, batch_shardings
from repro.train.optimizer import OptConfig, adamw_init, opt_state_shardings
from repro.roofline.analysis import collective_bytes

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 3)
spec = get_arch(sys.argv[1])
model = Model(spec.smoke_config)
shapes, axes = shapes_and_axes(model)
p_shard = shard_params_tree(shapes, axes, mesh, DEFAULT_RULES)
opt_cfg = OptConfig()
o_shapes = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), shapes)
o_shard = opt_state_shardings(shapes, axes, mesh, DEFAULT_RULES, opt_cfg)
batch = spec.input_specs("train_4k", smoke=True)
b_shard = batch_shardings(batch, mesh, DEFAULT_RULES)
fn = make_train_step(model, mesh, DEFAULT_RULES, opt_cfg)
from repro.distributed.sharding import named_sharding, Axes
rep = named_sharding(Axes(), mesh, DEFAULT_RULES)
jitted = jax.jit(fn, in_shardings=(p_shard, o_shard, b_shard, rep),
                 out_shardings=(p_shard, o_shard,
                                {"loss": rep, "gnorm": rep, "lr": rep}))
low = jitted.lower(shapes, o_shapes, batch, jax.ShapeDtypeStruct((), jnp.int32))
comp = low.compile()
ma = comp.memory_analysis()
coll = collective_bytes(comp.as_text())
print(json.dumps({"ok": True,
                  "arg_bytes": int(ma.argument_size_in_bytes),
                  "collectives": coll}))
"""


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "deepseek-v2-lite-16b",
                                  "jamba-1.5-large-398b", "rwkv6-3b",
                                  "seamless-m4t-medium"])
def test_multipod_lower_compile_smoke(arch):
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", SCRIPT, arch],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"]
    # DP over pod+data must produce gradient all-reduces
    assert "all-reduce" in res["collectives"]
