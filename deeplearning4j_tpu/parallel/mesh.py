"""Device mesh construction helpers.

The TPU-native replacement for the reference's cluster topology plumbing
(Spark executor placement / Akka cluster membership, SURVEY.md §2.4): a
`jax.sharding.Mesh` over ICI-connected devices with named axes. Axis naming
convention used across the framework:
  - "data"  : data parallelism (batch sharding; the ParameterAveraging axis)
  - "model" : tensor parallelism (weight sharding)
  - "seq"   : sequence/context parallelism (ring attention)
  - "pipe"  : pipeline stages
  - "expert": expert parallelism
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def default_mesh(n_devices: Optional[int] = None, axis: str = DATA_AXIS) -> Mesh:
    """1-D mesh over the first n local devices."""
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        # devs[:n] would quietly build a smaller mesh than was asked for
        raise ValueError(f"mesh of {n} devices asked for, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (axis,))


def mesh_2d(data: int, model: int,
            axes: Tuple[str, str] = (DATA_AXIS, MODEL_AXIS)) -> Mesh:
    devs = jax.devices()
    if data * model > len(devs):
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, "
                         f"have {len(devs)}")
    grid = np.asarray(devs[:data * model]).reshape(data, model)
    return Mesh(grid, axes)


def make_mesh(shape: dict) -> Mesh:
    """Build a mesh from {axis_name: size}; sizes must multiply to <= #devices."""
    sizes = [int(s) for s in shape.values()]
    total = int(np.prod(sizes))
    devs = jax.devices()
    if total > len(devs):
        raise ValueError(f"mesh {shape} needs {total} devices, have {len(devs)}")
    grid = np.asarray(devs[:total]).reshape(sizes)
    return Mesh(grid, tuple(shape.keys()))


def hybrid_mesh(dcn_shape: dict, ici_shape: dict) -> Mesh:
    """Multi-slice mesh: DCN axes outermost (across slices), ICI axes within.

    The multi-pod topology the reference reaches with Spark executor
    placement across hosts (SURVEY.md §2.4: driver -> executors over TCP) is
    expressed here as mesh geometry: axes in ``dcn_shape`` vary across TPU
    slices (collectives on them ride the data-center network) and axes in
    ``ici_shape`` vary within a slice (collectives ride ICI). Shard weights
    over ICI axes and batch over DCN axes so the per-step all-reduce volume
    crossing DCN is the small gradient-sum, never activations — the
    scaling-book recipe.

    On hardware, devices carry ``slice_index``; devices of one slice form one
    row-block. On single-slice (or CPU test) topologies, contiguous blocks of
    ``prod(ici_shape)`` devices stand in for slices so the same code runs
    under `--xla_force_host_platform_device_count`.
    """
    dcn_axes, ici_axes = tuple(dcn_shape), tuple(ici_shape)
    overlap = set(dcn_axes) & set(ici_axes)
    if overlap:
        raise ValueError(f"axis names must be unique across dcn/ici: {overlap}")
    n_slices = int(np.prod([int(s) for s in dcn_shape.values()]))
    per_slice = int(np.prod([int(s) for s in ici_shape.values()]))
    devs = jax.devices()
    if n_slices * per_slice > len(devs):
        raise ValueError(f"hybrid mesh {dcn_shape}x{ici_shape} needs "
                         f"{n_slices * per_slice} devices, have {len(devs)}")
    by_slice: dict = {}
    for d in devs:
        by_slice.setdefault(getattr(d, "slice_index", None) or 0, []).append(d)
    usable = [sorted(v, key=lambda d: d.id)[:per_slice]
              for _, v in sorted(by_slice.items())
              if len(v) >= per_slice][:n_slices]
    if len(usable) < n_slices:
        if len(by_slice) > 1:
            # real multi-slice hardware whose layout can't host this
            # geometry: refuse rather than silently letting an "ICI" axis
            # span slices (its collectives would ride DCN)
            raise ValueError(
                f"hybrid mesh {dcn_shape}x{ici_shape} does not fit the "
                f"slice layout {[len(v) for v in by_slice.values()]} "
                f"(need {n_slices} slices of >= {per_slice} devices)")
        # pseudo-slices: contiguous device blocks (single-slice / CPU test)
        if n_slices > 1:
            import warnings
            warnings.warn(
                f"hybrid_mesh: requested {n_slices} slices but only one "
                f"real slice is present — falling back to pseudo-slice "
                f"contiguous blocks, so the '{'/'.join(dcn_axes)}' DCN "
                f"axis actually rides ICI. Fine for tests; on real "
                f"hardware check the pod topology.", stacklevel=2)
        return make_mesh({**dcn_shape, **ici_shape})
    grid = np.asarray(usable).reshape(
        [int(s) for s in dcn_shape.values()] +
        [int(s) for s in ici_shape.values()])
    return Mesh(grid, dcn_axes + ici_axes)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    return NamedSharding(mesh, P(axis))
