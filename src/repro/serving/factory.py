"""One engine-construction factory for the whole repo (DESIGN.md §14).

``serve.build_engine`` / ``serve.build_lanes`` / ``benchmarks.common
.engine`` used to each carry their own copy of the config -> params ->
EngineConfig -> KVRMEngine plumbing; all three now delegate here.

``build(...)`` returns a list of engine lanes (or a :class:`Gateway`
over them with ``gateway=True``):

  * ``mesh_spec='DxM'`` — D device-backed lanes, M-way tensor-parallel
    each (DESIGN.md §4), params initialized once and placed per lane;
  * ``lanes=N`` — N logical single-device lanes sharing one param set
    (the gateway's data-parallel shape on a single device; composes with
    ``prefix_cache=True`` for affinity routing, unlike sharded lanes);
  * default — one single-device engine, seed-exact.

``arch`` is an architecture id, which builds its reduced preset (the CPU
default of tests and examples), or a :class:`ModelConfig`, which builds
exactly that model — e.g. ``get_config(arch).replace(n_layers=4)`` at its
published widths on a chip.

Params are cached per (config, seed). They are initialized by one jitted
program on the default device, so the f32 draws behind the bf16 weights
stay inside the compiled init instead of materializing eagerly.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Union

import jax

from repro.configs import ModelConfig, get_reduced
from repro.core.engine import EngineConfig, KVRMEngine
from repro.launch import mesh as mesh_mod
from repro.models import registry

_PARAM_CACHE = {}


def resolve_config(arch: Union[str, ModelConfig]) -> ModelConfig:
    """An arch id's reduced preset, or the given config unchanged."""
    return arch if isinstance(arch, ModelConfig) else get_reduced(arch)


@partial(jax.jit, static_argnums=1)
def _init_params(key, cfg: ModelConfig):
    return registry.init_params(key, cfg)


def cached_params(arch: Union[str, ModelConfig], seed: int = 0):
    cfg = resolve_config(arch)
    key = (cfg, seed)
    if key not in _PARAM_CACHE:
        _PARAM_CACHE[key] = _init_params(jax.random.PRNGKey(seed), cfg)
    return _PARAM_CACHE[key]


def build(arch: Union[str, ModelConfig] = "qwen2.5-32b", *,
          mode: str = "paged_merge",
          batch: int = 8, max_seq: int = 256, near_window: Optional[int] = None,
          block_tokens: int = 8, pool_budget: float = 1.0, seed: int = 0,
          mesh_spec: str = "1x1", lanes: int = 0, mesh=None, params=None,
          gateway: bool = False, gateway_kw: Optional[dict] = None,
          **engine_kw):
    """Build engine lanes (list) or a Gateway over them.

    ``mesh`` (a jax Mesh or None) overrides ``mesh_spec`` for a single
    explicitly-placed lane; ``lanes=N > 0`` replicates the single-device
    lane N times (mutually exclusive with a multi-lane mesh_spec).
    Remaining ``engine_kw`` pass through to :class:`EngineConfig`.
    """
    cfg = resolve_config(arch)
    # legacy spelling from pre-§14 call sites
    pool_budget = engine_kw.pop("pool_budget_frac", pool_budget)
    if params is None:
        params = cached_params(cfg, seed)
    if mesh is not None:
        meshes: List = [mesh]
    else:
        meshes = mesh_mod.lane_meshes_for_spec(mesh_spec)
    if lanes:
        if len(meshes) != 1:
            raise ValueError(
                f"lanes={lanes} needs a single-lane mesh_spec, got "
                f"{mesh_spec!r} ({len(meshes)} lanes)")
        meshes = meshes * lanes
    engines = [KVRMEngine(cfg, params, EngineConfig(
        mode=mode, batch=batch, max_seq=max_seq, near_window=near_window,
        block_tokens=block_tokens, pool_budget_frac=pool_budget,
        mesh=lane_mesh, **engine_kw)) for lane_mesh in meshes]
    if gateway:
        from repro.serving.gateway import Gateway
        return Gateway(engines, **(gateway_kw or {}))
    return engines
