"""Shared plumbing for the sequence-parallel entry points (ring, ulysses)."""

from __future__ import annotations

__all__ = [
    "SEQ_AXIS",
    "resolve_sp_mesh",
    "check_divisible",
    "pcast_varying",
]

#: canonical sequence-parallel axis name
SEQ_AXIS = "sp"


def resolve_sp_mesh(mesh, axis_name: str):
    """Default to a 1-D mesh over all devices when none is given."""
    if mesh is None:
        import jax

        from ..parallel.mesh import make_mesh

        mesh = make_mesh({axis_name: len(jax.devices())})
    return mesh


def pcast_varying(t, axis_name: str):
    """Mark a shard_map-internal constant as varying over ``axis_name``.

    Constants born inside ``shard_map`` are device-invariant; a loop carry
    that later passes through ``ppermute`` becomes varying, so the initial
    carry must be marked too (jax >= 0.8 VMA checking). Older jax versions
    lack ``pcast`` — there the check does not exist either, so pass-through
    is correct."""
    import jax

    try:
        return jax.lax.pcast(t, (axis_name,), to="varying")
    except (AttributeError, TypeError):
        return t


def check_divisible(n: int, axis_name: str, **named_lengths: int) -> None:
    """Require every named length to divide by the axis size; the error
    names the offending operand (not just whichever was checked first)."""
    bad = {name: l for name, l in named_lengths.items() if l % n}
    if bad:
        detail = ", ".join(f"{name}={l}" for name, l in bad.items())
        raise ValueError(
            f"{detail} must divide by the {axis_name!r} axis size {n}"
        )
