"""Device mesh management.

Replaces the reference's NCCL ring registry (platform/collective_helper.h:62
NCCLCommContext keyed ring_id->comm) with named jax.sharding.Mesh axes:
ring_id -> axis name is the only mapping collectives need; XLA routes the
collectives over ICI/DCN according to the mesh's device layout.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass
class MeshConfig:
    """Logical mesh shape: ordered (axis_name, size) pairs. size -1 = infer
    from the device count (at most one)."""

    axes: List[Tuple[str, int]]

    def resolve(self, n_devices: int) -> List[Tuple[str, int]]:
        fixed = 1
        infer_idx = None
        for i, (name, size) in enumerate(self.axes):
            if size == -1:
                infer_idx = i
            else:
                fixed *= size
        axes = list(self.axes)
        if infer_idx is not None:
            axes[infer_idx] = (axes[infer_idx][0], max(n_devices // fixed, 1))
        return axes


def build_mesh(config: MeshConfig | Sequence[Tuple[str, int]],
               devices: Optional[Sequence] = None) -> Mesh:
    if not isinstance(config, MeshConfig):
        config = MeshConfig(list(config))
    devices = list(devices if devices is not None else jax.devices())
    axes = config.resolve(len(devices))
    shape = tuple(s for _, s in axes)
    names = tuple(n for n, _ in axes)
    total = int(np.prod(shape))
    if total > len(devices):
        raise ValueError(
            f"mesh axes {axes} need {total} devices, have {len(devices)}")
    had_inferred = any(s == -1 for _, s in config.axes)
    if had_inferred and total != len(devices):
        # an inferred axis must tile the device count exactly — silently
        # running on a subset would skew per-device batch math
        raise ValueError(
            f"mesh axes {axes} (with inferred size) cover {total} of "
            f"{len(devices)} devices — sizes must tile the device count")
    dev_array = np.array(devices[:total]).reshape(shape)
    return Mesh(dev_array, names)


_current_mesh: Optional[Mesh] = None


def current_mesh() -> Optional[Mesh]:
    return _current_mesh


@contextlib.contextmanager
def mesh_guard(mesh: Mesh):
    global _current_mesh
    old = _current_mesh
    _current_mesh = mesh
    try:
        yield mesh
    finally:
        _current_mesh = old


def spec_for(var_sharding: Optional[Sequence[Optional[str]]]) -> P:
    """Convert a per-dim axis-name tuple (None = replicated dim) to a
    PartitionSpec."""
    if var_sharding is None:
        return P()
    return P(*var_sharding)


def named_sharding(mesh: Mesh, var_sharding=None) -> NamedSharding:
    return NamedSharding(mesh, spec_for(var_sharding))


def aval_of(x) -> jax.ShapeDtypeStruct:
    """Abstract value of a scope variable (or anything array-like)."""
    import jax.numpy as jnp

    a = jnp.asarray(x) if not hasattr(x, "shape") else x
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def feed_aval(shape, dt) -> jax.ShapeDtypeStruct:
    """Abstract value for a feed signature entry; 'bfloat16' has no numpy
    dtype and must map to the jax one."""
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if str(dt) == "bfloat16" else np.dtype(dt)
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def jit_shard_map(per_rank, mesh: Mesh, in_specs, out_specs,
                  donate_argnums=()):
    """shard_map (no varying-manual-axes check) + jit. The single
    wrapping point for the executor / pipeline / grad-merge per-rank
    executables."""
    wrapped = jax.shard_map(per_rank, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return jax.jit(wrapped, donate_argnums=donate_argnums)


def probe_produced_state(fn, mutable_avals, const_avals, feed_avals,
                         fallback):
    """Discover which persistable names ``fn`` actually produces by
    abstract evaluation (shapes the shard_map out_specs pytree before
    tracing). Falls back to ``fallback`` when the probe itself cannot
    run (e.g. collectives that need a bound axis context)."""
    key_aval = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    try:
        _, state_shape = jax.eval_shape(fn, mutable_avals, const_avals,
                                        feed_avals, key_aval)
        return sorted(state_shape.keys())
    except Exception:
        return list(fallback)
