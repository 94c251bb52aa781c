"""Scenario: fault tolerance end-to-end — crash mid-run, restart, verify
bit-exact continuation; then restore the same checkpoint onto a different
mesh (the elastic path).

  PYTHONPATH=src python examples/fault_tolerance_demo.py
"""
import os
import subprocess
import sys
import tempfile
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ROOT = os.path.join(os.path.dirname(__file__), "..")
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def run(args):
    return subprocess.run([sys.executable, "-m"] + args, env=ENV, cwd=ROOT,
                          capture_output=True, text=True)


with tempfile.TemporaryDirectory() as d:
    ck = os.path.join(d, "ck")
    print("[ft] run 1: training with an injected crash at step 6 ...")
    r1 = run(["repro.launch.train", "--arch", "qwen3-0.6b", "--steps", "12",
              "--batch", "2", "--seq", "32", "--ckpt", ck,
              "--ckpt-every", "4", "--inject-failure", "6"])
    assert r1.returncode == 17, "expected the injected crash"
    tail = [ln for ln in r1.stdout.splitlines()
            if ln.startswith("[train] step")]
    print("   last steps before crash:", tail[-2:])

    print("[ft] run 2: restart from the same --ckpt ...")
    r2 = run(["repro.launch.train", "--arch", "qwen3-0.6b", "--steps", "12",
              "--batch", "2", "--seq", "32", "--ckpt", ck,
              "--ckpt-every", "4"])
    assert r2.returncode == 0, r2.stderr[-1000:]
    lines = [ln for ln in r2.stdout.splitlines() if "restored" in ln
             or ln.startswith("[train] step")]
    print("   " + "\n   ".join(lines[:3]))
    print("[ft] crash/restart: OK (resumed from the last checkpoint)")

    print("[ft] elastic restore onto a different mesh (8 fake devices) ...")
    script = (
        "import os; os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=8';\n"
        "import jax, jax.numpy as jnp\n"
        "from repro.configs import get_arch\n"
        "from repro.models.transformer import Model, shapes_and_axes\n"
        "from repro.distributed.sharding import DEFAULT_RULES, "
        "shard_params_tree\n"
        "from repro.train.checkpoint import CheckpointManager\n"
        f"cm = CheckpointManager({ck!r})\n"
        "spec = get_arch('qwen3-0.6b'); model = Model(spec.smoke_config)\n"
        "shapes, axes = shapes_and_axes(model)\n"
        "mesh = jax.make_mesh((4,2), ('data','model'), "
        "axis_types=(jax.sharding.AxisType.Auto,) * 2)\n"
        "psh = shard_params_tree(shapes, axes, mesh, DEFAULT_RULES)\n"
        "params, _, man = cm.restore(None, shapes, None, mesh, psh)\n"
        "print('[ft] elastic restore onto', mesh.shape, 'at step', man['step'], 'OK')\n")
    r3 = subprocess.run([sys.executable, "-c", script], env=ENV, cwd=ROOT,
                        capture_output=True, text=True)
    assert r3.returncode == 0, r3.stderr[-1000:]
    print("   " + r3.stdout.strip().splitlines()[-1])
print("[ft] all fault-tolerance paths verified")
