"""Production meshes.  A function, not a constant — importing this module
never touches jax device state.

Also home of :class:`MeshSpec`, the serializable description of how the DSE
hot path (stage-2 batched surrogate, stage-4 batched netsim) shards its
candidate axis across devices, plus the pad/unpad helpers that make any
batch size divisible by the mesh extent.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import numpy as np

__all__ = ["make_mesh", "make_production_mesh", "plan_for_mesh",
           "N_DEVICES", "MeshSpec", "padded_size", "shard_pad", "shard_unpad",
           "bucket_size", "bucket_pad"]

N_DEVICES = {"single": 256, "multi": 512}


def _validate_mesh_shape(shape, axes):
    """Raise (naming the numbers) instead of building a wrong-shaped mesh."""
    for extent, name in zip(shape, axes):
        if extent < 1:
            raise ValueError(
                f"mesh axis {name!r} has extent {extent}; every axis needs "
                f"extent >= 1 (shape={tuple(shape)})")
    needed = math.prod(shape)
    available = jax.device_count()
    if needed > available:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {needed} devices but only "
            f"{available} are available (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={needed} "
            f"to simulate more on CPU)")


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``.

    Raises ``ValueError`` (with both numbers named) for zero-extent axes or
    shapes larger than the available device count instead of letting jax
    build a sharding that silently misassigns data."""
    _validate_mesh_shape(shape, axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


# --------------------------------------------------------------------------
# MeshSpec: serializable sharding request for the DSE hot path
# --------------------------------------------------------------------------

#: (scenario_axis, devices) -> jax Mesh; meshes are hashable jit keys, so a
#: stable identity per process keeps the sharded-engine jit caches warm.
_MESH_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """How to shard the DSE candidate axis across the device mesh.

    ``devices`` is the candidate-axis extent (``--devices N`` on the CLI);
    ``scenario_axis`` is a second, data-parallel axis campaigns use to spread
    scenario groups.  The candidate batch is sharded over *both* axes (a
    campaign's concatenated per-scenario blocks land on different device
    groups), so the total shard count is ``devices * scenario_axis``.

    The spec is plain data — safe to serialize into scenario dicts and
    checkpoint manifests — and deliberately *not* part of ``SearchSpec``:
    search state is mesh-agnostic, which is what lets a checkpoint written
    on N devices resume bit-identically on M (see ``runtime/elastic.py``).
    """

    devices: int = 1
    scenario_axis: int = 1

    def __post_init__(self):
        if self.devices < 1:
            raise ValueError(
                f"MeshSpec candidate axis has size {self.devices}; "
                f"need >= 1 device")
        if self.scenario_axis < 1:
            raise ValueError(
                f"MeshSpec scenario axis has size {self.scenario_axis}; "
                f"need >= 1")

    @property
    def shard_axis(self) -> int:
        """Total candidate-axis shard count (both mesh axes combined)."""
        return self.devices * self.scenario_axis

    def is_single(self) -> bool:
        """True when this spec is the serial single-device path."""
        return self.shard_axis == 1

    def build(self):
        """The (cached) jax Mesh: shape (scenario_axis, devices)."""
        key = (self.scenario_axis, self.devices)
        mesh = _MESH_CACHE.get(key)
        if mesh is None:
            mesh = make_mesh(key, ("scenario", "cand"))
            _MESH_CACHE[key] = mesh
        return mesh

    def to_dict(self) -> dict:
        return {"devices": self.devices, "scenario_axis": self.scenario_axis}

    @classmethod
    def from_dict(cls, d: dict) -> "MeshSpec":
        return cls(devices=int(d.get("devices", 1)),
                   scenario_axis=int(d.get("scenario_axis", 1)))

    @classmethod
    def coerce(cls, value) -> Optional["MeshSpec"]:
        """None | int | dict | MeshSpec -> Optional[MeshSpec]."""
        if value is None or isinstance(value, cls):
            return value
        if isinstance(value, int):
            return cls(devices=value)
        if isinstance(value, dict):
            return cls.from_dict(value)
        raise TypeError(f"cannot build a MeshSpec from {value!r}")


def padded_size(n: int, k: int) -> int:
    """Smallest multiple of ``k`` that is >= ``n`` (>= ``k`` when n == 0)."""
    if k < 1:
        raise ValueError(f"shard count {k} must be >= 1")
    return k * max(1, -(-n // k))


def shard_pad(a: np.ndarray, k: int, axis: int = 0) -> np.ndarray:
    """Pad ``a`` along ``axis`` to a multiple of ``k`` by replicating row 0.

    Pad rows are throwaway duplicates of an existing candidate: every engine
    scan is rowwise-independent, so they cannot perturb real rows, and the
    host strips them with :func:`shard_unpad` before pricing — a padded run
    is bit-identical to the unpadded one."""
    n = a.shape[axis]
    pad = padded_size(n, k) - n
    if pad == 0:
        return a
    fill = np.repeat(np.take(a, [0], axis=axis), pad, axis=axis)
    return np.concatenate([np.asarray(a), fill], axis=axis)


def bucket_size(n: int, k: int = 1) -> int:
    """The candidate-axis width a device call of ``n`` rows pads to: the
    next multiple of 8 up to 16, then the next of 24, 32, 48, 64, 96, ...
    (powers of two and 1.5x powers of two), then up to a multiple of the
    shard count ``k``.  One rule for every stage-2 and stage-4 call, so a
    search whose generations ask for any width from 1 to 48 compiles five
    programs (8, 16, 24, 32, 48); above 16 rows a bucket is under a third
    pad."""
    if n <= 16:
        size = 8 * max(1, -(-n // 8))
    else:
        size = 1 << (n - 1).bit_length()
        if 4 * n <= 3 * size:
            size = size * 3 // 4
    return padded_size(size, k)


def bucket_pad(a: np.ndarray, k: int = 1, axis: int = 0) -> np.ndarray:
    """Pad ``a`` along ``axis`` to ``bucket_size`` by replicating row 0,
    as :func:`shard_pad` does; the caller strips the pad rows."""
    return shard_pad(a, bucket_size(a.shape[axis], k), axis)


def shard_unpad(a, n: int, axis: int = 0):
    """Strip pad rows: the first ``n`` entries of ``a`` along ``axis``."""
    index = (slice(None),) * axis + (slice(0, n),)
    return a[index]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh(data: int = 1, model: int = 1):
    return make_mesh((data, model), ("data", "model"))


def plan_for_mesh(mesh):
    from repro.models.config import MULTI_POD_PLAN, SINGLE_POD_PLAN
    return MULTI_POD_PLAN if "pod" in mesh.axis_names else SINGLE_POD_PLAN
