"""Mesh construction and the compile cache.

FUNCTIONS, not module-level constants — importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before any jax
initialization).

One mesh convention: every mesh the repo builds has ``AxisType.Auto``
axes, so sharding is steered by ``with_sharding_constraint`` and GSPMD
propagation (``jax.make_mesh`` defaults to ``Explicit`` axes, under which
those constraints and the FSDP embedding gather are rejected).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.configs.base import MeshPlan, MULTI_POD, SINGLE_POD

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> Mesh:
    """A mesh with Auto axes (the repo's one mesh convention).  ``devices``
    pins the device order (e.g. the survivors of a lost pipeline stage);
    without it jax picks a topology-aware order."""
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(tuple(shape), tuple(axes), axis_types=types)
    return Mesh(np.array(devices).reshape(tuple(shape)), tuple(axes),
                axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    plan = plan_for(multi_pod=multi_pod)
    return make_mesh(plan.shape, plan.axes)


def plan_for(*, multi_pod: bool = False) -> MeshPlan:
    return MULTI_POD if multi_pod else SINGLE_POD


def mesh_for_devices(*, multi_pod: bool = False
                     ) -> Tuple[Optional[Mesh], MeshPlan]:
    """The launchers' mesh rule: the production mesh when the device count
    matches it exactly, otherwise a (data, model) mesh over the local
    devices, and no mesh at all on one device."""
    n = len(jax.devices())
    prod = plan_for(multi_pod=multi_pod)
    if n == prod.num_devices:
        return make_production_mesh(multi_pod=multi_pod), prod
    if n == 1:
        return None, MeshPlan((1,), ("data",))
    d = 2 if n % 2 == 0 else 1
    plan = MeshPlan((d, n // d), ("data", "model"))
    return make_mesh(plan.shape, plan.axes), plan


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as is (jax reads
    it itself); otherwise the cache lives at the fixed ``<repo>/.jax_cache``
    so that a later run of the same checkout finds it again.  Nothing is
    set when the cache is switched off (``JAX_ENABLE_COMPILATION_CACHE=
    false``).  Called by the entry points, never at import time."""
    if (jax.config.jax_enable_compilation_cache
            and not os.environ.get("JAX_COMPILATION_CACHE_DIR")):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO_ROOT, ".jax_cache"))
