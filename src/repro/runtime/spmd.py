"""shard_map / mesh construction, device probes and the compile cache.

Every call site in the repo goes through here; nothing else imports the
raw shard_map / mesh-typing APIs (enforced by
tests/test_runtime.py::test_no_raw_shard_map_outside_runtime).
"""
from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def api_info() -> dict:
    """The JAX entry points this module wraps — for verify scripts."""
    return {"jax_version": jax.__version__,
            "shard_map_impl": "jax.shard_map"}


# --- shard_map --------------------------------------------------------------

def shard_map(f, mesh, in_specs, out_specs, *,
              check_vma: Optional[bool] = None,
              axis_names: Optional[Any] = None):
    """``jax.shard_map`` over ``mesh``.

    ``axis_names``: the set of mesh axes that are manual inside ``f``
    (None => all of them).
    """
    kwargs: dict[str, Any] = {}
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


# --- mesh construction ------------------------------------------------------

def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types: Any = "auto", devices=None):
    """``jax.make_mesh`` with every axis of one type.

    axis_types: "auto" (default) / "explicit", applied to every axis, or an
    explicit tuple passed through verbatim.
    """
    if axis_types == "auto":
        axis_types = (AxisType.Auto,) * len(axis_names)
    elif axis_types == "explicit":
        axis_types = (AxisType.Explicit,) * len(axis_names)
    kwargs: dict[str, Any] = {"axis_types": tuple(axis_types)}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``, or {} when XLA offers no analysis."""
    return compiled.cost_analysis() or {}


def axis_index(axis_name) -> Any:
    """``jax.lax.axis_index`` gateway (raw spelling is banned outside
    ``repro.runtime`` by the API-hygiene grep gate, alongside raw
    all_to_all — collective addressing goes through the runtime layer)."""
    return jax.lax.axis_index(axis_name)


def device_count() -> int:
    """How many devices the backend exposes."""
    return len(jax.devices())


def device_kind() -> str:
    """Kind string of device 0 (e.g. 'cpu', 'TPU v4', 'NVIDIA H100')."""
    return str(jax.devices()[0].device_kind)


#: Memory budget assumed for a host (CPU) device, which reports none. It
#: is fixed so the derived values it feeds (the pair-capacity heuristic)
#: are the same in every process: host/sharded bit-parity needs that.
HOST_DEVICE_MEMORY = 8 << 30


def device_memory_bytes() -> int:
    """Per-device memory budget in bytes.

    Accelerators report ``bytes_limit`` via ``memory_stats()``, and an
    accelerator that reports none is an error: the capacity this feeds
    is part of the graph's identity, so it is never guessed there. Host
    devices report nothing and get :data:`HOST_DEVICE_MEMORY`.
    """
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    if stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    if dev.platform != "cpu":
        raise RuntimeError(
            f"{dev.platform} device {dev.device_kind!r} reports no "
            f"bytes_limit in memory_stats() ({sorted(stats)})")
    return HOST_DEVICE_MEMORY


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and this sets
    nothing. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``, never a temporary directory, a pid or a
    time, so a later run of the checkout finds what an earlier one
    compiled. Call it first
    thing in a script; library imports never call it. Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    path = os.path.join(checkout, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def make_proc_mesh(num_procs: int = 0, axis_name: str = "proc",
                   devices=None) -> Mesh:
    """1-D mesh over all (or exactly the first ``num_procs``) devices.

    This subsumes the per-module "build a 1-D mesh over available devices"
    boilerplate the generators / analysis / launch layers used to carry.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    if num_procs:
        if len(devs) < num_procs:
            raise ValueError(
                f"need {num_procs} devices, have {len(devs)}")
        devs = devs[:num_procs]
    return Mesh(np.array(devs), (axis_name,))


def ensure_mesh(mesh: Optional[Mesh], num_procs: int = 0,
                axis_name: str = "proc") -> Mesh:
    """Return ``mesh`` unchanged, or a fresh 1-D device mesh when None."""
    if mesh is not None:
        return mesh
    return make_proc_mesh(num_procs, axis_name)


def mesh_size(mesh: Mesh) -> int:
    """Total device count of a mesh (product over all axes)."""
    return int(np.prod(list(mesh.shape.values()))) if mesh.shape else 1
