"""Production mesh construction.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model").

``model`` is the context-parallel (CP) axis — FlashCP distributes sequence
tokens over it; parameters are additionally fully sharded over every axis
(FSDP, runtime/sharding.py).  A *function*, not a module constant: importing
this module must never touch JAX device state.  Construction goes through
:func:`repro.compat.make_mesh` (Auto axis types).
"""

from __future__ import annotations

from repro.compat import make_mesh

__all__ = ["make_production_mesh", "make_local_mesh", "make_group_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, *, devices=None):
    """Small mesh for tests/examples on host devices.

    ``devices``: optional explicit device list — the elastic recovery
    path (DESIGN.md §Recovery) rebuilds the mesh over the *surviving*
    devices after a host loss, so the grid must not silently fall back
    to the default (dead hosts included) enumeration.
    """
    return make_mesh((data, model), ("data", "model"), devices=devices)


def make_group_mesh(data: int, model: int, cp_degree: int, *, devices=None):
    """Re-tile a ``data x model`` device grid into CP subgroups.

    The adaptive dispatcher (DESIGN.md §Dispatch) runs each step at a CP
    degree sized to the batch's document-length profile: the same
    ``data * model`` devices are re-tiled into ``data * model / cp_degree``
    groups of ``cp_degree`` devices, keeping the canonical ("data",
    "model") axis names so every downstream consumer (FSDP parameter
    layout, batch specs, CP attention islands) works unchanged — the
    group axis *is* the "data" axis of the re-tiled mesh.

    ``cp_degree`` must divide the ``model`` axis so each subgroup is a
    contiguous slice of a single CP row (physically adjacent devices on
    the production torus) and never straddles a data row.

    ``devices``: optional explicit device list (elastic recovery re-tiles
    the *surviving* grid after a host loss, DESIGN.md §Recovery).
    """
    if cp_degree < 1 or model % cp_degree:
        raise ValueError(
            f"cp_degree {cp_degree} does not divide model axis {model}")
    return make_mesh((data * model // cp_degree, cp_degree),
                     ("data", "model"), devices=devices)
