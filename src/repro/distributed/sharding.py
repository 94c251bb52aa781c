"""Logical-axis sharding rules with divisibility-aware resolution.

Every parameter/activation carries *logical* axis names (Axes('experts',
'd_ff', 'embed'), ...). ``ShardingRules`` maps logical names to mesh axes;
resolution drops a mesh axis whenever the dimension does not divide the
axis size (e.g. 4 KV heads on a 16-way 'model' axis => replicated), so
every config lowers on every mesh without hand-tuning.

Meshes (launch/mesh.py):
  single pod  (16, 16)      axes ('data', 'model')
  multi pod   (2, 16, 16)   axes ('pod', 'data', 'model')

Conventions:
  batch      -> ('pod', 'data')   pure DP
  embed      -> None (replicated); FSDP_RULES shards it over ('data',)
  heads/q    -> 'model'           Megatron TP
  kv_heads   -> 'model' (drops to replication when #kv % axis != 0)
  d_ff       -> 'model'
  experts    -> 'model'           expert parallelism
  vocab      -> 'model'           sharded embeddings + logits
  kv_seq     -> 'model'           sequence-sharded decode KV caches
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def make_mining_mesh(shards: int | None = None, axis: str = "mine", *,
                     devices=None) -> Mesh:
    """1-D device mesh for data-parallel pattern mining (mining.shard).

    ``shards=None`` takes every visible device; an explicit count uses the
    first ``shards`` devices (a strict prefix keeps the mesh deterministic,
    so cache signatures and psum groups are stable across runs). The mining
    axis is the only axis — wavefront sharding is pure DP over the level-1
    edge feed, there is no model axis to compose with.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = int(shards) if shards else len(devs)
    if n < 1:
        raise ValueError(f"mining mesh needs >= 1 shard, got {n}")
    if n > len(devs):
        raise ValueError(
            f"mining mesh wants {n} shards but only {len(devs)} device(s) "
            f"are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devs[:n])


class Axes(tuple):
    """Logical axes annotation; subclassing tuple but treated as a pytree
    leaf in the axes trees (axes trees only ever contain Axes leaves, and we
    always flatten with is_leaf=is_axes)."""

    def __new__(cls, *names):
        return super().__new__(cls, names)


def is_axes(x) -> bool:
    return isinstance(x, Axes)


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """logical axis name -> mesh axis (str), tuple of mesh axes, or None."""

    rules: dict

    def get(self, name: str):
        return self.rules.get(name, None)

    def replace(self, **kw) -> "ShardingRules":
        d = dict(self.rules)
        d.update(kw)
        return ShardingRules(d)


DEFAULT_RULES = ShardingRules({
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "d_ff": "model",
    "experts": "model",
    "kv_seq": "model",          # decode caches: sequence-sharded (flash-decode)
    "state": None,              # SSM / RWKV recurrent state dims
    "conv": None,
    "opt": ("data", "pod"),     # ZeRO extra sharding for optimizer state
    "moe_groups": ("pod", "data"),  # sort-dispatch token groups (local sort)
    "moe_cap": ("pod", "data"),     # expert capacity dim after the a2a
    "bh": ("pod", "data", "model"),  # merged batch x heads (rwkv wkv)
})

FSDP_RULES = DEFAULT_RULES.replace(embed=("data",))


def _axis_sizes(mesh) -> dict[str, int]:
    # works for both Mesh and AbstractMesh (no .devices on the latter)
    return dict(mesh.shape)


def logical_to_physical(axes: Axes, mesh: Mesh, rules: ShardingRules,
                        shape: tuple | None = None) -> P:
    """Resolve logical axes to a PartitionSpec, dropping non-dividing axes."""
    sizes = _axis_sizes(mesh)
    used: set[str] = set()
    spec = []
    for d, name in enumerate(axes):
        mesh_axes = rules.get(name) if name else None
        if mesh_axes is None:
            spec.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        picked = []
        prod = 1
        for ax in mesh_axes:
            if ax not in sizes or ax in used:
                continue
            nxt = prod * sizes[ax]
            if shape is not None and shape[d] % nxt != 0:
                continue
            picked.append(ax)
            prod = nxt
        used.update(picked)
        spec.append(tuple(picked) if len(picked) > 1
                    else (picked[0] if picked else None))
    return P(*spec)


def named_sharding(axes: Axes, mesh: Mesh, rules: ShardingRules,
                   shape: tuple | None = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_physical(axes, mesh, rules, shape))


def shard_params_tree(param_shapes, param_axes, mesh: Mesh,
                      rules: ShardingRules):
    """ShapeDtypeStruct tree + Axes tree -> NamedSharding tree."""
    flat_s, treedef = jax.tree.flatten(param_shapes)
    flat_a = jax.tree.flatten(param_axes, is_leaf=is_axes)[0]
    assert len(flat_s) == len(flat_a), "param/axes trees out of sync"
    out = [named_sharding(a, mesh, rules, tuple(s.shape))
           for s, a in zip(flat_s, flat_a)]
    return jax.tree.unflatten(treedef, out)


def input_sharding(mesh: Mesh, rules: ShardingRules, *names) -> NamedSharding:
    return named_sharding(Axes(*names), mesh, rules)


# ---------------------------------------------------------------------------
# mesh context: lets model code write constrain(x, 'batch','seq','embed')
# without plumbing the mesh through every function signature.
# ---------------------------------------------------------------------------

_TLS = threading.local()


@contextlib.contextmanager
def mesh_context(mesh: Mesh | None, rules: ShardingRules = DEFAULT_RULES):
    prev = getattr(_TLS, "ctx", None)
    _TLS.ctx = (mesh, rules) if mesh is not None else None
    try:
        yield
    finally:
        _TLS.ctx = prev


def current_mesh():
    ctx = getattr(_TLS, "ctx", None)
    return ctx if ctx is not None else (None, DEFAULT_RULES)


def constrain(x, *names):
    """Logical sharding constraint; no-op when no mesh context is active."""
    mesh, rules = current_mesh()
    if mesh is None:
        return x
    spec = logical_to_physical(Axes(*names), mesh, rules, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def with_sharding_constraint(x, axes: Axes, mesh: Mesh | None = None,
                             rules: ShardingRules = DEFAULT_RULES):
    if mesh is None:
        return constrain(x, *axes)
    spec = logical_to_physical(axes, mesh, rules, tuple(x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
