"""Device-mesh construction.

TPU-first replacement for the reference's process-group world
(python/ray/train/torch/config.py:65 `_setup_torch_process_group`): the
unit of parallelism is a `jax.sharding.Mesh` over named axes, not a flat
rank list. Axis names follow the scaling-book convention:

- ``dp``   pure data parallelism (params replicated)
- ``fsdp`` data parallelism with parameter sharding (ZeRO-3 analog —
           the reference delegates this to torch FSDP,
           python/ray/train/torch/train_loop_utils.py:184; in GSPMD it is
           just a mesh axis params are sharded over)
- ``pp``   pipeline parallelism (stage axis; see parallel/pipeline.py)
- ``tp``   tensor (megatron) parallelism
- ``sp``   sequence/context parallelism (ring attention axis)
- ``ep``   expert parallelism (MoE)

Mesh axis order matters on hardware: axes that carry the heaviest
collectives (tp, sp) must map to minor / adjacent ICI dimensions, so they
come LAST in the axis tuple (jax device order is minor-to-major locality
in reverse order of the mesh shape tuple's last axes).

Multi-slice (SURVEY §5.8 plane 3): the ``dcn`` axis is OUTERMOST — it
spans TPU slices connected by data-center network, so only the lightest
per-step collective (the data-parallel gradient all-reduce) crosses it;
fsdp/tp/sp stay inside a slice on ICI. Build such meshes with
``create_hybrid_mesh``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

AXIS_ORDER = ("dcn", "dp", "pp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh: axis name -> size. -1 means 'absorb remaining'.

    Example::

        MeshSpec(dp=-1, tp=4)   # on 32 devices -> {"dp": 8, "tp": 4}
    """

    dcn: int = 1
    dp: int = 1
    pp: int = 1
    fsdp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wildcards = [a for a, s in sizes.items() if s == -1]
        if len(wildcards) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wildcards}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcards:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {fixed}")
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes


def mesh_shape_for(n_devices: int,
                   tp: int = 1,
                   sp: int = 1,
                   fsdp: Optional[int] = None) -> Dict[str, int]:
    """Heuristic mesh for n_devices: tp/sp as asked, rest fsdp (or dp)."""
    rest = n_devices // (tp * sp)
    if rest * tp * sp != n_devices:
        raise ValueError(f"tp*sp={tp * sp} must divide n_devices={n_devices}")
    if fsdp is None:
        return {"dp": 1, "pp": 1, "fsdp": rest, "ep": 1, "sp": sp,
                "tp": tp}
    if rest % fsdp:
        raise ValueError(f"fsdp={fsdp} must divide {rest}")
    return {"dp": rest // fsdp, "pp": 1, "fsdp": fsdp, "ep": 1, "sp": sp,
            "tp": tp}


def create_mesh(axis_sizes: Dict[str, int],
                devices: Optional[Sequence] = None,
                allow_split_physical_axes: bool = False):
    """Build a `jax.sharding.Mesh` with AXIS_ORDER-ordered named axes.

    Uses `mesh_utils.create_device_mesh` when the full device set is used so
    the logical mesh is laid out along physical ICI topology (keeps tp/sp
    collectives on-wire neighbors); falls back to reshape for subsets.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    names = tuple(a for a in AXIS_ORDER if axis_sizes.get(a, 1) >= 1)
    shape = tuple(axis_sizes.get(a, 1) for a in names)
    if math.prod(shape) != len(devices):
        raise ValueError(
            f"mesh shape {dict(zip(names, shape))} != {len(devices)} devices")
    try:
        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes)
    except AssertionError:
        # create_device_mesh asserts that TPU devices fill a whole
        # physical (sub-)torus. A subset that does not (three of a 2x2)
        # has no ICI layout to follow and is laid out in the order
        # given; a mesh shape the full torus cannot carry still raises.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def create_hybrid_mesh(axis_sizes: Dict[str, int],
                       devices: Optional[Sequence] = None):
    """Multi-slice mesh: the outer ``dcn`` axis spans slices (DCN links);
    every other axis stays within one slice (ICI).

    On real multi-slice TPU hardware the device→mesh layout comes from
    ``mesh_utils.create_hybrid_device_mesh`` (keyed on each device's
    ``slice_index``); elsewhere (CPU worlds, single-slice ICI) devices are
    grouped contiguously so process-local devices form a slice — the
    layout the driver's virtual multi-process worlds produce.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    num_slices = int(axis_sizes.get("dcn", 1))
    names = tuple(a for a in AXIS_ORDER if axis_sizes.get(a, 1) >= 1)
    ici_names = tuple(a for a in names if a != "dcn")
    ici_shape = tuple(axis_sizes.get(a, 1) for a in ici_names)
    if num_slices * math.prod(ici_shape) != len(devices):
        raise ValueError(
            f"hybrid mesh dcn={num_slices} x ici={dict(zip(ici_names, ici_shape))} "
            f"!= {len(devices)} devices")
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if len(slice_ids) == num_slices and None not in slice_ids:
        dev_array = mesh_utils.create_hybrid_device_mesh(
            (1, *ici_shape),
            (num_slices, *([1] * len(ici_shape))),
            devices=devices).reshape((num_slices, *ici_shape))
    else:
        dev_array = np.asarray(devices).reshape((num_slices, *ici_shape))
    return Mesh(dev_array, ("dcn", *ici_names))


def auto_mesh(spec: Optional[MeshSpec] = None,
              devices: Optional[Sequence] = None):
    """Mesh from a MeshSpec (default: all devices on the fsdp axis)."""
    import jax

    if devices is None:
        devices = jax.devices()
    if spec is None:
        spec = MeshSpec(fsdp=-1)
    return create_mesh(spec.resolve(len(devices)), devices)


def local_mesh():
    """Single-process mesh over addressable devices, all on fsdp."""
    import jax

    devs = jax.local_devices()
    return create_mesh({"fsdp": len(devs)}, devs)
