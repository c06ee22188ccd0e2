"""Mesh-sharded batched registration — data-parallel serving over a pod.

``engine.batch.register_batch`` compiles one ``jit(vmap)`` program pinned to
a single device; this module runs that same program on every device of a
``jax.sharding.Mesh`` instead, each device on its own shard of the batch,
so a pod of N accelerators serves N shards of a registration batch
concurrently (Budelmann et al. and Brunn et al. — see PAPERS.md — both get
intra-operative latencies from scaling the *loop* across devices, not just
the kernel).

The program runs under ``shard_map`` over the batch axes of
``repro.distributed.sharding.REGISTRATION_RULES``: every per-pair array is
local to its device, so no collective is needed, GSPMD never has to
partition the loop, and the Pallas kernels (which Mosaic cannot partition
automatically) run as ordinary per-device calls.  The per-device program is
the one-device program at batch ``B / n``.

Non-divisible batches are padded (repeating the last pair) up to the batch
multiple of the mesh; ``register_batch`` strips the pad rows on return.
Callers driving ``compile_sharded_batch`` directly get the *padded* outputs
and can mask the synthetic rows with ``batch_mask``.
``make_registration_mesh()`` works on real accelerators and on fake CPU
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` exported
before jax is imported), which is how CI exercises this path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from repro.distributed.sharding import REGISTRATION_RULES

__all__ = [
    "VOLUME_AXES",
    "GRID_AXES",
    "LOSS_AXES",
    "make_registration_mesh",
    "batch_multiple",
    "pad_batch",
    "batch_mask",
    "lane_sharding",
    "local_lane",
    "compile_lane_program",
    "compile_sharded_batch",
]

# Logical axes (REGISTRATION_RULES names) of the three result trees.
VOLUME_AXES = ("batch", "vol_x", "vol_y", "vol_z")
GRID_AXES = ("batch", "grid_x", "grid_y", "grid_z", "disp")
LOSS_AXES = ("batch", "level")


def make_registration_mesh(num_devices=None, *, devices=None):
    """A 1-D ``("data",)`` mesh over the local devices (default: all).

    The axis is named ``"data"`` because that is the name REGISTRATION_RULES
    (and therefore ``batch_multiple`` / ``compile_sharded_batch``) binds the
    batch axis to.  Works identically on a real accelerator pod and on fake
    host devices: export
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before*
    importing jax to rehearse the 8-way layout on a laptop or in CI.
    """
    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    n = len(devs) if num_devices is None else int(num_devices)
    if not 1 <= n <= len(devs):
        raise ValueError(
            f"need {n} devices for a registration mesh, have {len(devs)}; "
            "on CPU export XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(n, 2)} before importing jax to fake a pod")
    return jax.make_mesh((n,), ("data",), devices=devs[:n],
                         axis_types=(AxisType.Auto,))


def batch_multiple(mesh) -> int:
    """Shard count of the batch axis — what batch sizes must pad up to."""
    axes = REGISTRATION_RULES(mesh.axis_names)["batch"]
    axes = (axes,) if isinstance(axes, str) else tuple(axes or ())
    return math.prod(mesh.shape[a] for a in axes if a in mesh.shape) or 1


def pad_batch(x, multiple):
    """Pad the leading axis up to ``multiple`` by repeating the last entry.

    Returns ``(padded, orig_b)``; callers strip results back to ``orig_b``
    rows (see ``batch_mask`` for the validity mask).  Repeating a real pair
    (rather than zero-filling) keeps the padded rows numerically ordinary —
    no similarity term ever sees a degenerate all-zero volume.
    """
    b = x.shape[0]
    if b == 0:
        # x[-1:] on an empty leading axis repeats nothing — padding would
        # silently return an empty array and the batched program would fail
        # much later with an opaque shape error
        raise ValueError(
            "pad_batch got an empty batch (leading axis 0); there is no "
            "last entry to repeat — supply at least one pair")
    pad = (-b) % int(multiple)
    if pad:
        x = jnp.concatenate([x, jnp.repeat(x[-1:], pad, axis=0)], axis=0)
    return x, b


def batch_mask(orig_b, padded_b):
    """Boolean ``(padded_b,)`` mask: True for real rows, False for padding.

    ``register_batch`` strips pad rows itself; this is for callers that use
    ``compile_sharded_batch`` directly and therefore
    hold padded outputs (e.g. to exclude synthetic rows from aggregate
    loss/quality statistics without a host round-trip).
    """
    return jnp.arange(int(padded_b)) < int(orig_b)


def lane_sharding(mesh):
    """The batch-over-data ``NamedSharding`` for a leading lane/batch axis.

    Used as a pytree-prefix placement: ``jax.device_put(state,
    lane_sharding(mesh))`` shards every leaf of a lane-array state dict
    (``engine.batch.compile_level_chunk``'s operand) along its leading lane
    axis, replicating everything per-lane — the same placement
    ``REGISTRATION_RULES`` gives ``register_batch``'s batch axis, so the
    serving scheduler's chunked loop and the monolithic sharded pipeline
    distribute identically.  Lane widths should be a multiple of
    ``batch_multiple(mesh)`` for an even split.
    """
    return NamedSharding(mesh, REGISTRATION_RULES(mesh.axis_names).spec(
        ("batch",)))


def local_lane(i, width, mesh):
    """Lane ``i`` of the whole lane array as an index into this device's
    ``width`` lanes, inside a :func:`compile_lane_program` body; ``width``
    (past the end: an update with ``mode="drop"`` leaves the block as it
    is) where another device holds lane ``i``."""
    axes = lane_sharding(mesh).spec[0]
    if axes is None:
        return i
    j = i - jax.lax.axis_index(axes) * width
    return jnp.where((j >= 0) & (j < width), j, width)


def compile_lane_program(fn, mesh, lanes, donate_argnums=()):
    """``jit(shard_map(fn))`` over the mesh's batch axes.

    ``lanes`` says of each positional argument whether it is lane-leading
    (every leaf split along its leading axis, batch-over-data) or
    replicated (a lane index, one pair).  Every output is lane-leading and
    stays distributed.  Each device runs ``fn`` on its own ``W / n`` lanes,
    so the Pallas kernels inside are per-device calls: Mosaic cannot
    partition a kernel automatically, which a GSPMD-partitioned program
    would ask of it.
    """
    sh = lane_sharding(mesh)
    shardings = tuple(sh if lane else NamedSharding(mesh, PartitionSpec())
                      for lane in lanes)
    body = jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(s.spec for s in shardings),
                         out_specs=sh.spec, check_vma=False)
    return jax.jit(body, in_shardings=shardings, out_shardings=sh,
                   donate_argnums=donate_argnums)


def compile_sharded_batch(batched, mesh):
    """``jit(shard_map(batched))`` over the mesh's batch axes.

    ``batched`` is the one-device batch program, ``(F, M) -> outputs`` with
    every input and output batch-leading (``engine.batch._compiled_batch``
    builds it as ``vmap`` of the per-pair pipeline).  Each device runs it on
    its ``B / n`` rows; ``in_shardings`` place the incoming stacks batch-
    over-data (uncommitted host arrays are transferred shard-by-shard, never
    materialised whole on one device) and the outputs stay distributed.
    The batch must be a multiple of :func:`batch_multiple` (``pad_batch``).
    """
    return compile_lane_program(batched, mesh, (True, True))
