"""Production meshes (TPU v5e target) and hierarchical worker views.

``make_production_mesh`` is the mandated entry point: 16×16 = 256 chips per
pod, 2 pods = 512 chips multi-pod.  Decentralized training additionally uses
a *derived view* of the same devices (DESIGN.md §4): the ``data`` axis splits
into (worker × fsdp) so that giant architectures keep fewer, internally-FSDP-
sharded replicas.  Functions only — importing this module never touches jax
device state.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


@dataclasses.dataclass(frozen=True)
class TrainAxes:
    """Axis names of the (possibly hierarchical) training mesh view."""
    pod: Optional[str]      # "pod" on the multi-pod mesh, else None
    worker: str             # gossip axis
    fsdp: Optional[str]     # intra-worker parameter sharding, None if f == 1
    model: str              # tensor/expert parallel

    @property
    def worker_axes(self) -> Tuple[str, ...]:
        return ((self.pod,) if self.pod else ()) + (self.worker,)

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        out = self.worker_axes
        return out + ((self.fsdp,) if self.fsdp else ())


def hierarchical_view(mesh: Mesh, workers: int, fsdp: int) -> Tuple[Mesh, TrainAxes]:
    """Split the mesh's ``data`` axis into (worker, fsdp) — same devices.

    The physical device array is exactly the production mesh's; only the
    logical axis naming changes, so every dry-run still runs on the mandated
    16×16 / 2×16×16 topology.
    """
    names = mesh.axis_names
    devs = np.asarray(mesh.devices)
    data_size = mesh.shape["data"]
    if workers * fsdp != data_size:
        raise ValueError(f"workers*fsdp must equal data axis ({data_size}), "
                         f"got {workers}×{fsdp}")
    multi_pod = "pod" in names
    model = mesh.shape["model"]
    if multi_pod:
        new = devs.reshape(mesh.shape["pod"], workers, fsdp, model)
        new_names = ("pod", "worker", "fsdp", "model")
    else:
        new = devs.reshape(workers, fsdp, model)
        new_names = ("worker", "fsdp", "model")
    if fsdp == 1:
        new = new.squeeze(axis=-2)
        new_names = tuple(n for n in new_names if n != "fsdp")
    view = Mesh(new, new_names, axis_types=(AxisType.Auto,) * len(new_names))
    axes = TrainAxes(pod="pod" if multi_pod else None, worker="worker",
                     fsdp="fsdp" if fsdp > 1 else None, model="model")
    return view, axes


# Per-architecture (workers, fsdp) split of the 16-wide data axis, sized so a
# worker replica (params + grads + remat'd activations) fits v5e HBM.
# Rationale in EXPERIMENTS.md §Dry-run.
WORKER_FSDP: Dict[str, Tuple[int, int]] = {
    "deepseek-67b": (4, 4),
    "rwkv6-1.6b": (16, 1),
    "minicpm-2b": (16, 1),
    "musicgen-large": (16, 1),
    "grok-1-314b": (2, 8),
    "mistral-nemo-12b": (16, 1),
    "arctic-480b": (2, 8),
    "llava-next-mistral-7b": (16, 1),
    "recurrentgemma-2b": (16, 1),
    "qwen3-8b": (16, 1),
}

# Gradient-accumulation microbatches for activation-heavy train configs.
MICROBATCH: Dict[str, int] = {
    "deepseek-67b": 2,
    "grok-1-314b": 2,
    # arctic: fp32 grad-accumulation buffers (2x replica bytes/128 devices)
    # cost more than the activations microbatching saves — measured in
    # EXPERIMENTS.md §Perf; single batch + remat is strictly better.
}


def train_view(arch: str, *, multi_pod: bool = False) -> Tuple[Mesh, TrainAxes, int]:
    """(mesh view, axes, total workers) for an arch's training dry-run."""
    w, f = WORKER_FSDP.get(arch, (16, 1))
    base = make_production_mesh(multi_pod=multi_pod)
    view, axes = hierarchical_view(base, w, f)
    n_workers = w * (2 if multi_pod else 1)
    return view, axes, n_workers
