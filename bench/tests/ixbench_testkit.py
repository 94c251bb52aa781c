"""Helpers shared by the benchmark's tests (not a test module itself)."""
from __future__ import annotations

import contextlib
import copy
import io
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "name": "tiny",
    "source": "a test graph",
    "generator": "powerlaw_cluster",
    "vertices": 400,
    "m_per_node": 8,
    "tri_p": 0.3,
    "graph_seed": 0,
    "chips": 1,
    "reduced": [],
}


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def bench_copy(tmp: pathlib.Path, configs: dict, workloads: list) -> pathlib.Path:
    """A checkout root under ``tmp`` holding the benchmark's files, plus
    the given configuration files and cells added as a later change would
    add them: new files, new entries."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__",
                                                  "tests"))
    s = copy.deepcopy(spec())
    for name, cfg in configs.items():
        path = f"bench/configs/{name}.json"
        (tmp / path).write_text(json.dumps(dict(cfg, name=name)))
        s["configs"].append({"name": name, "source": cfg["source"],
                             "file": path, "reduced": cfg["reduced"],
                             "why": "test"})
    s["workloads"] += workloads
    (tmp / "BENCHMARK.json").write_text(json.dumps(s))
    return tmp


def cell(config: str, traffic: str, chips: int = 1) -> dict:
    return {"name": f"{config}.{traffic}", "config": config,
            "traffic": traffic, "chips": chips, "why": "test"}


@contextlib.contextmanager
def restored_jax_cache():
    """The harness turns JAX's persistent compilation cache on in its
    process; put the test process's settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()


def run_tiny(root: pathlib.Path, name: str, seed: int = 7,
             seconds: float = 0.2, trace: bool = False) -> dict:
    """One run of a cell on the CPU: the harness with its look for a chip
    replaced by one that accepts any device and gives no peaks."""
    from ixbench import harness
    check = harness.check_devices
    harness.check_devices = lambda root, devices, chips: {}
    try:
        with restored_jax_cache():
            return harness.run_cell(root, name, seed, seconds, trace,
                                    time.perf_counter(), log=io.StringIO())
    finally:
        harness.check_devices = check
