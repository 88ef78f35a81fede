"""Shared Pallas-kernel runtime knobs."""

import contextlib
import contextvars
import os

import jax

_KERNEL_MESH = contextvars.ContextVar("dstpu_kernel_mesh", default=None)


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Name the device mesh the code traced inside runs over.

    XLA partitions ordinary ops over a mesh by itself; a Mosaic kernel it
    cannot ("Mosaic kernels cannot be automatically partitioned"), so a kernel
    reached under a mesh of several devices maps itself over it with
    ``jax.shard_map``. The training engine wraps the model's apply in this
    context; outside it kernels run unmapped, as on one device."""
    token = _KERNEL_MESH.set(mesh)
    try:
        yield
    finally:
        _KERNEL_MESH.reset(token)


def open_mesh_axes():
    """``(mesh, axes)`` for the ``jax.shard_map`` a kernel wraps itself in:
    the axes of a ``kernel_mesh`` of several devices that no enclosing
    ``shard_map`` has made manual yet — all of them, since Mosaic lowers only
    where every mesh axis is manual. Inside an enclosing ``shard_map`` the mesh
    is None, which is how ``shard_map`` is told to use the one in context.
    ``axes`` is empty outside a ``kernel_mesh`` and on one device: no wrap."""
    mesh = _KERNEL_MESH.get()
    if mesh is None or mesh.size == 1:
        return None, ()
    manual = jax.sharding.get_abstract_mesh().manual_axes
    return (None if manual else mesh,
            tuple(a for a in mesh.axis_names if a not in manual))


def one_device() -> bool:
    """Is no mesh of several devices in sight of the code being traced:
    neither a ``kernel_mesh``, nor JAX's own (``jax.set_mesh``, a
    ``shard_map``), nor the process's topology (``comm/topology.py``: the
    training engine's and ``InferenceEngine``'s, which places its weights
    on it and names no mesh where it traces)? What a kernel asks that has
    no mapped form."""
    from ..comm.topology import get_topology

    mesh, topo = _KERNEL_MESH.get(), get_topology(required=False)
    return ((mesh is None or mesh.size == 1)
            and jax.sharding.get_abstract_mesh().size <= 1
            and (topo is None or topo.world_size == 1))


def pallas_interpret() -> bool:
    """Should Pallas kernels run under the interpreter?

    Default: interpret everywhere except a real TPU backend.
    ``DSTPU_PALLAS_INTERPRET`` overrides (case-insensitive): ``0/false/no``
    forces the real Mosaic kernel — used by the TPU-lowering export tests on
    CPU hosts — and ``1/true/yes`` forces the interpreter on TPU (debugging).
    Empty or unrecognized values mean "unset" (the backend heuristic), so
    ``DSTPU_PALLAS_INTERPRET= python ...`` behaves like clearing the var.
    """
    ov = os.environ.get("DSTPU_PALLAS_INTERPRET", "").strip().lower()
    if ov in ("0", "false", "no"):
        return False
    if ov in ("1", "true", "yes"):
        return True
    return jax.default_backend() != "tpu"
