"""Production mesh construction.

Never touches jax device state at import time: everything is a function.
Single pod = (16, 16) ("data", "model") = 256 chips (TPU v5e pod slice);
multi-pod adds a leading "pod" axis -> (2, 16, 16) = 512 chips.  The FSDP /
batch dimension is ("pod", "data") combined; "model" carries TP / EP / head
sharding.  Designed so "pod" generalizes to N pods (1000+ nodes): the pod
axis only ever composes with "data", so growing it is a resharding-free
batch-dimension extension.
"""

from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


class MeshCapacityError(ValueError):
    """A requested mesh shape does not fit the available devices."""


def make_host_mesh(data: int | None = None, model: int = 1, *,
                   devices=None):
    """Small ("data", "model") mesh over local devices (tests / local runs).

    ``data`` defaults to ``len(devices) // model``.  ``devices`` (default:
    ``jax.devices()``) restricts the mesh to a subset -- the sharded-restore
    benchmark builds 1/2/4/8-device meshes on one forced-8-device host this
    way.  A shape that cannot fit raises the named ``MeshCapacityError``
    (requested vs. available) instead of an opaque ``make_mesh`` failure or
    a zero-sized axis.
    """
    devs = list(jax.devices() if devices is None else devices)
    n = len(devs)
    if model < 1:
        raise MeshCapacityError(f"model axis must be >= 1, got {model}")
    if data is None:
        if model > n:
            raise MeshCapacityError(
                f"requested model={model} but only {n} device(s) are "
                f"available; the data axis would be {n} // {model} = 0")
        data = n // model
    if data < 1:
        raise MeshCapacityError(f"data axis must be >= 1, got {data}")
    if data * model > n:
        raise MeshCapacityError(
            f"mesh (data={data}, model={model}) needs {data * model} "
            f"device(s) but only {n} are available")
    if devices is None and data * model == n:
        # Full-host mesh: let make_mesh pick the device order (it optimizes
        # for the physical topology on real accelerators).
        return jax.make_mesh((data, model), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    grid = np.array(devs[:data * model]).reshape(data, model)
    return jax.sharding.Mesh(grid, ("data", "model"))


def batch_axes(mesh) -> tuple:
    """The composite FSDP/batch mesh axes present in ``mesh``."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def model_axis(mesh):
    return "model" if "model" in mesh.axis_names else None


def forced_host_devices_env(n: int, *, single_threaded: bool = False,
                            base_env: "dict | None" = None) -> dict:
    """Environment for a subprocess that should see ``n`` host devices.

    ``XLA_FLAGS`` must be set before jax is imported, so multi-device CPU
    tests and, on the CPU, the sharded-restore benchmark run in
    subprocesses built with this.  It is for the CPU only: on a TPU a
    child process could not reach the chip its parent holds.
    ``single_threaded`` additionally pins each device's compiled
    executables to one thread, so wall-clock scaling across devices
    reflects device count rather than the host's intra-op thread pool.
    """
    env = dict(base_env) if base_env is not None else {}
    flags = [f"--xla_force_host_platform_device_count={n}"]
    if single_threaded:
        flags.append("--xla_cpu_multi_thread_eigen=false")
        env["OMP_NUM_THREADS"] = "1"
        env["OPENBLAS_NUM_THREADS"] = "1"
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env
