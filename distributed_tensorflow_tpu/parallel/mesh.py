"""Device-mesh construction.

Replaces the reference's ``tf.train.ClusterSpec`` + ``replica_device_setter``
placement model (``demo2/train.py:18-29``): instead of pinning variables to
parameter-server processes and ops to worker processes, all devices form a
``jax.sharding.Mesh``; parameters are replicated (or sharded) across it and
XLA inserts ICI collectives where shardings demand.

Axis conventions (room for every strategy even though the reference only
exercises DP — SURVEY §2.3):
  * ``data``  — batch (data-parallel) axis
  * ``model`` — tensor-parallel axis (optional second mesh dim)
"""

from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh


def unit_mesh_init(init_fn, *args):
    """Run a parameter-init function inside a trivial 1×1×1
    ('data','pipe','model') shard_map on one LOCAL device and return host
    numpy — the standard way to get GLOBAL-shape params for modules that
    query ``lax.axis_size`` (TP/MoE). All three framework axis names are
    bound (each size 1) so a module parameterized on ANY of them — e.g.
    ``ep_axis='pipe'`` — initializes without an unbound-axis error.
    The shard_map is jitted as a whole: eager shard_map dispatches every
    primitive as its own program (one compile + launch each).
    Multi-process safe (local device + shared seed ⇒ identical host trees)."""
    from jax.sharding import PartitionSpec as P

    mesh1 = Mesh(
        np.asarray(jax.local_devices()[:1]).reshape(1, 1, 1),
        ("data", "pipe", "model"),
    )
    fn = jax.jit(
        jax.shard_map(
            init_fn,
            mesh=mesh1,
            in_specs=tuple(P() for _ in args),
            out_specs=P(),
            check_vma=False,
        )
    )
    return jax.device_get(fn(*args))


def make_mesh3(
    num_devices: int | None = None,
    pipeline_parallel: int = 1,
    model_parallel: int = 1,
    devices=None,
) -> Mesh:
    """Build a ('data', 'pipe', 'model') mesh for 3D parallelism
    (DP × PP × TP, ``parallel/three_d.py``). 'model' is the innermost axis —
    the tensor-parallel all-reduces are the most frequent collective, so they
    get the contiguous-neighbor ICI links; pipeline hops are next; the
    data-parallel gradient mean (once per step) crosses the outermost axis."""
    devices = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    n = len(devices)
    inner = pipeline_parallel * model_parallel
    if n % inner:
        raise ValueError(
            f"{n} devices not divisible by pipeline_parallel*model_parallel={inner}"
        )
    arr = np.array(devices).reshape(n // inner, pipeline_parallel, model_parallel)
    return Mesh(arr, axis_names=("data", "pipe", "model"))


def make_mesh(
    num_devices: int | None = None,
    model_parallel: int = 1,
    devices=None,
) -> Mesh:
    """Build a ('data', 'model') mesh over local (or given) devices.

    ``model_parallel=1`` (the default, and all the reference needs) yields a
    pure data-parallel mesh."""
    devices = list(devices if devices is not None else jax.devices())
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"requested {num_devices} devices but only {len(devices)} available"
            )
        devices = devices[:num_devices]
    n = len(devices)
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by model_parallel={model_parallel}")
    arr = np.array(devices).reshape(n // model_parallel, model_parallel)
    return Mesh(arr, axis_names=("data", "model"))


