"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import; smoke
tests and benches must keep seeing 1 device).

Topology targets (TPU v5e-class):
  single pod : 16 x 16 = 256 chips, axes ('data', 'model')
  multi pod  : 2 x 16 x 16 = 512 chips, axes ('pod', 'data', 'model') —
               'pod' is the DCN-grade axis (extra DP by default, pipeline
               stage axis optionally; see distributed/pipeline.py).
"""

from __future__ import annotations

from typing import List, Tuple

import jax
from jax.sharding import AxisType

from repro.analysis import roofline as RL


def make_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis `AxisType.Auto`.

    jax 0.9 defaults to Explicit axes, under which sharding is part of each
    array's type and ops such as the embedding gather refuse operands whose
    sharding they cannot infer. The serving and training steps are written
    for GSPMD propagation (Auto): placement comes from in/out shardings and
    `with_sharding_constraint`, never from the types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    return make_mesh((data, model), ("data", "model"))


def parse_mesh_arg(spec: str) -> Tuple[int, int]:
    """'2,4' or 'data=2,model=4' -> (data, model). The serving launchers'
    `--mesh` grammar (CPU runs force devices via
    XLA_FLAGS=--xla_force_host_platform_device_count=N first)."""
    parts = [p.strip() for p in spec.split(",") if p.strip()]
    if len(parts) != 2:
        raise ValueError(f"--mesh expects 'data,model', got {spec!r}")
    vals = {}
    for i, p in enumerate(parts):
        if "=" in p:
            k, v = p.split("=", 1)
            vals[k.strip()] = int(v)
        else:
            vals[("data", "model")[i]] = int(p)
    if set(vals) != {"data", "model"} or min(vals.values()) < 1:
        raise ValueError(f"--mesh expects positive data,model sizes, "
                         f"got {spec!r}")
    return vals["data"], vals["model"]


def replica_meshes(data: int, model: int, n_replicas: int) -> List:
    """Split a (data, model) device grid into `n_replicas` disjoint
    submeshes along the DATA axis — one serving-engine replica per
    data-parallel submesh (serve.router). Each replica keeps the full
    'model' axis (TP stays intact); the data axis divides evenly or this
    raises (uneven replicas would skew the router's load signal)."""
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    if data % n_replicas:
        raise ValueError(f"data axis {data} does not divide into "
                         f"{n_replicas} replicas")
    import numpy as np
    from jax.sharding import Mesh
    need = data * model
    devices = jax.devices()
    if len(devices) < need:
        raise ValueError(f"mesh {data}x{model} needs {need} devices, "
                         f"have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_replicas,
                                              data // n_replicas, model)
    return [Mesh(grid[i], ("data", "model")) for i in range(n_replicas)]


def process_meshes(data: int, model: int, n_replicas: int) -> List:
    """`replica_meshes` for ONE PROCESS of a fleet: the same disjoint
    (data, model) submesh split, but over `jax.local_devices()` — the
    devices THIS process owns after `jax.distributed.initialize` — so
    each fleet process serves its own replicas on its own chips and the
    data plane never crosses the process boundary. In a single-process
    run local_devices == devices and this degenerates to replica_meshes
    exactly (same grid, same meshes), which is what keeps
    DistributedBackend token-identical to ShardedBackend."""
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    if data % n_replicas:
        raise ValueError(f"data axis {data} does not divide into "
                         f"{n_replicas} replicas")
    import numpy as np
    from jax.sharding import Mesh
    need = data * model
    devices = jax.local_devices()
    if len(devices) < need:
        raise ValueError(
            f"process mesh {data}x{model} needs {need} LOCAL devices, "
            f"process {jax.process_index()} has {len(devices)} "
            f"(of {jax.device_count()} global)")
    grid = np.asarray(devices[:need]).reshape(n_replicas,
                                              data // n_replicas, model)
    return [Mesh(grid[i], ("data", "model")) for i in range(n_replicas)]


def fleet_topology(data: int, model: int, n_replicas: int) -> dict:
    """Resolved process -> devices -> replica-mesh map for THIS process,
    JSON-safe — what `launch.serve --dry-run` prints per fleet process so
    a misconfigured coordinator (wrong num_processes, short device
    count, uneven replica split) fails loudly BEFORE weight packing."""
    meshes = process_meshes(data, model, n_replicas)
    return {
        "process_index": jax.process_index(),
        "num_processes": jax.process_count(),
        "local_devices": [str(d) for d in jax.local_devices()],
        "global_device_count": jax.device_count(),
        "mesh_shape": [data, model],
        "n_replicas": n_replicas,
        "replica_meshes": [
            {"replica": i,
             "shape": dict(zip(m.axis_names,
                               (int(s) for s in m.devices.shape))),
             "devices": [str(d) for d in m.devices.flat]}
            for i, m in enumerate(meshes)],
    }


def plan_fleet_topology(n_processes: int, devices_per_process: int,
                        data: int, model: int, n_replicas: int) -> dict:
    """Arithmetic-only fleet plan: the same constraints `process_meshes`
    enforces live, checked WITHOUT touching jax device state — so
    `launch.serve --dry-run --processes N` can validate a local-fleet
    launch (which spawns workers with their own forced device counts)
    from the coordinator process, before any worker or weight pack
    exists. Raises ValueError exactly where process_meshes would."""
    if n_processes < 1:
        raise ValueError(f"n_processes must be >= 1, got {n_processes}")
    if n_replicas < 1:
        raise ValueError(f"n_replicas must be >= 1, got {n_replicas}")
    if data % n_replicas:
        raise ValueError(f"data axis {data} does not divide into "
                         f"{n_replicas} replicas")
    need = data * model
    if devices_per_process < need:
        raise ValueError(
            f"process mesh {data}x{model} needs {need} devices per "
            f"process, plan gives each of {n_processes} processes "
            f"{devices_per_process}")
    per = data // n_replicas
    procs = []
    for p in range(n_processes):
        devs = [f"cpu:{p}:{i}" for i in range(devices_per_process)]
        procs.append({
            "process_index": p,
            "local_devices": devs,
            "replica_meshes": [
                {"replica": r,
                 "shape": {"data": per, "model": model},
                 "devices": devs[r * per * model:(r + 1) * per * model]}
                for r in range(n_replicas)],
        })
    return {
        "num_processes": n_processes,
        "devices_per_process": devices_per_process,
        "global_device_count": n_processes * devices_per_process,
        "mesh_shape": [data, model],
        "n_replicas": n_replicas,
        "processes": procs,
    }


# Hardware constants for the dry-run roofline (TPU v5e, per chip); the
# bf16 and HBM peaks come from analysis.roofline's published-peaks table
PEAK_BF16_FLOPS = RL.PEAKS["TPU v5 lite"].peak_flops    # FLOP/s
PEAK_INT8_OPS = 394e12          # OP/s
HBM_BW = RL.PEAKS["TPU v5 lite"].peak_bw                # B/s
ICI_BW_PER_LINK = 50e9          # B/s per link (~ per-axis-neighbor)
HBM_BYTES = 16 * 2 ** 30        # 16 GiB
