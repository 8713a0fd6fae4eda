"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The production target is TPU v5e pods:
16x16 = 256 chips per pod (data x model), 2 pods = 512 chips with a
leading "pod" axis for cross-pod data parallelism.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1, devices=None):
    """``devices`` (all of them by default) as (data, model) — used by
    the servers, examples, tests, and single-host training."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    assert n % model_axis == 0, (n, model_axis)
    return _make_mesh((n // model_axis, model_axis), ("data", "model"),
                      devices)


def make_pe_mesh(n_pes: int):
    """Whatever devices exist, as (pe, data): a leading ``pe`` axis with
    one slot per DORA PE — the jax-side twin of ``core.mesh.DoraMesh``,
    where each mesh PE's replay/dispatch work shards onto its own device
    row.  ``n_pes`` must divide the available device count."""
    if n_pes < 1:
        raise ValueError(f"n_pes must be >= 1, got {n_pes}")
    n = len(jax.devices())
    if n % n_pes:
        raise ValueError(f"n_pes={n_pes} does not divide the "
                         f"{n} available devices")
    return _make_mesh((n_pes, n // n_pes), ("pe", "data"))
