"""REPRO_FORCE_MESH: forced host-device meshes for tests, CI and benches.

Setting ``REPRO_FORCE_MESH=DxM`` (e.g. ``2x2``) asks a process to run on a
forced-CPU mesh of D data-parallel x M model (bank-shard) host devices, so
the engine's 2D `PartitionPlan` — batch over "data", template-bank class
rows over "model" — is exercised end to end without TPUs. The tier-1 CI
matrix runs a ``2x2`` entry and the serving-bench smoke adds a sharded row
through the same switch.

Two-phase by necessity: ``--xla_force_host_platform_device_count`` is read
when jax initialises its CPU backend, so the flag must be in ``XLA_FLAGS``
*before* anything touches jax devices, while building the mesh obviously
needs jax. Hence:

    from repro.distributed import forcemesh   # imports NO jax
    forcemesh.apply_xla_flags()               # phase 1: before jax init
    ...
    forcemesh.install()                       # phase 2: mesh -> context

`tests/conftest.py` runs phase 1 at import and phase 2 at session start;
the benchmarks run both at the top of `main()` (jax untouched until then).
"""
from __future__ import annotations

import os

ENV = "REPRO_FORCE_MESH"
_FLAG = "--xla_force_host_platform_device_count"


def parse(spec: str) -> tuple[int, int]:
    """"2x2" -> (data=2, model=2); raises ValueError on malformed specs."""
    try:
        d, m = spec.lower().split("x")
        d, m = int(d), int(m)
    except ValueError:
        raise ValueError(
            f"{ENV} must look like 'DxM' (e.g. 2x2), got {spec!r}") from None
    if d < 1 or m < 1:
        raise ValueError(f"{ENV} axes must be >= 1, got {spec!r}")
    return d, m


def env_spec() -> tuple[int, int] | None:
    """The (data, model) shape requested via the environment, or None."""
    spec = os.environ.get(ENV, "").strip()
    return parse(spec) if spec else None


def apply_xla_flags(spec: tuple[int, int] | None = None) -> bool:
    """Phase 1: put the forced host-device count into ``XLA_FLAGS``.

    MUST run before jax initialises its backend (first device/array use).
    Returns True when a forced mesh is requested. Idempotent; an existing
    forced count in ``XLA_FLAGS`` is left alone (the caller set it — e.g.
    the subprocess test helpers).
    """
    spec = spec if spec is not None else env_spec()
    if spec is None:
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={spec[0] * spec[1]}".strip()
    return True


def install(spec: tuple[int, int] | None = None):
    """Phase 2: build the (data=D, model=M) mesh and install it into
    `repro.distributed.context`. Imports jax — call only after phase 1.

    Returns the mesh, or None when no forced mesh is requested.
    """
    spec = spec if spec is not None else env_spec()
    if spec is None:
        return None
    import jax

    from repro.distributed import context
    from repro.launch.mesh import make_mesh

    d, m = spec
    if len(jax.devices()) < d * m:
        raise RuntimeError(
            f"{ENV}={d}x{m} needs {d * m} devices but jax initialised "
            f"{len(jax.devices())}; apply_xla_flags() must run before "
            "anything touches jax")
    mesh = make_mesh((d, m), ("data", "model"))
    context.set_mesh_axes("data", "model", mesh)
    return mesh
