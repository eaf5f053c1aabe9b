"""The one rule by which every Pallas call site picks kernel or reference.

Each ``ops.py`` calls :func:`choose` with its call-site name, the caller's
``impl`` and ``why_not``: None when the kernel can take this call, else
the reason it cannot (a shape off the block grid, a block too large for
VMEM, a primitive Mosaic does not lower).

  * ``impl="ref"``    — the jnp reference.
  * ``impl="kernel"`` — the Pallas kernel: compiled on TPU, interpreted
    elsewhere (the CPU parity tests).  Raises :class:`KernelUnsupported`
    when ``why_not`` is set; it never drops to the reference.
  * ``impl="auto"``   — the compiled kernel on TPU when ``why_not`` is
    None, the jnp reference otherwise.  Never the interpreter.

Every resolution made while tracing is counted per call site
(:func:`resolutions`), so a run can report what each site resolved to; a
site that picks between jnp paths counts its pick with :func:`record`.
"""
from __future__ import annotations

import collections

import jax

IMPLS = ("auto", "kernel", "ref")

#: Bytes of VMEM a kernel's pipelined blocks and in-kernel temporaries may
#: take: under the 16 MiB scoped-VMEM default of a v5e TensorCore, with
#: room left for the compiler's own spills.
VMEM_BUDGET = 12 * 2 ** 20

_RESOLVED: dict = collections.defaultdict(collections.Counter)


class KernelUnsupported(ValueError):
    """``impl="kernel"`` was asked for a call the kernel cannot take."""


def backend() -> str:
    """The platform the call sites compile for (tests steer it here)."""
    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def vmem_why_not(nbytes: int, what: str) -> str | None:
    """``why_not`` for a kernel whose VMEM footprint is ``nbytes``."""
    if nbytes <= VMEM_BUDGET:
        return None
    return (f"{what} needs {nbytes / 2 ** 20:.1f} MiB of VMEM, over the "
            f"{VMEM_BUDGET / 2 ** 20:.0f} MiB budget")


def choose(site: str, impl: str, why_not: str | None = None):
    """-> ``(use_kernel, interpret)`` for one call at ``site``."""
    if impl not in IMPLS:
        raise ValueError(f"{site}: impl must be one of {IMPLS}, got {impl!r}")
    tpu = on_tpu()
    if impl == "kernel":
        if why_not is not None:
            raise KernelUnsupported(f"{site}: {why_not}")
        use, interpret, how = True, not tpu, (
            "kernel" if tpu else "kernel (interpret)")
    elif impl == "auto" and tpu and why_not is None:
        use, interpret, how = True, False, "kernel"
    else:
        use, interpret = False, False
        if impl == "ref":
            how = "ref (asked)"
        elif not tpu:
            how = f"ref (backend {backend()})"
        else:
            how = f"ref ({why_not})"
    record(site, how)
    return use, interpret


def record(site: str, how: str) -> None:
    """Count one resolution ``how`` at ``site``."""
    _RESOLVED[site][how] += 1


def resolutions() -> dict:
    """``{site: {resolution: count}}`` of the calls traced so far."""
    return {site: dict(c) for site, c in sorted(_RESOLVED.items())}


def reset_resolutions() -> None:
    _RESOLVED.clear()
