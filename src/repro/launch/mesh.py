"""Production mesh construction.

Defined as a FUNCTION (never a module-level constant) so importing this
module never touches jax device state — required by the dry-run, whose
very first lines set ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax initialization.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh with Auto axis types (tests, benchmarks)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def data_axes(mesh) -> tuple[str, ...]:
    """Axes that shard the batch: ('pod','data') when a pod axis exists."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_data_mesh(n_shards: int | None = None):
    """1-D ``('data',)`` mesh for the data-parallel preprocessing engine.

    Each device on the axis is one Piper *instance*: it streams a disjoint
    slice of the dataset through loop ① with purely local vocabulary
    state, and the instances' states meet only in the final
    ``vocab.merge`` tree-reduce. Defaults to every visible device; pass
    ``n_shards`` to use a prefix of them (benchmark shard sweeps).
    """
    n = len(jax.devices()) if n_shards is None else n_shards
    return make_mesh((n,), ("data",))
