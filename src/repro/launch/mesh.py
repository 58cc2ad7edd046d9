"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
overrides the host device count via XLA_FLAGS before first jax init, while
unit tests / benches must see the single real CPU device.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16) — the "pod"
axis is pure DP (batch + gradient all-reduce only; base weights are
replicated per pod so no inter-pod weight traffic — DESIGN.md §4).
"""
from __future__ import annotations

from repro.sharding.rules import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (possibly fake) local devices exist —
    used by CPU tests that exercise the sharded code paths."""
    return auto_mesh((data, model), ("data", "model"))
