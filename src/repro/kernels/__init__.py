"""Pallas TPU kernels: <name>/kernel.py (the pallas_call), ops.py (the
jitted layout/padding wrapper the model calls) and ref.py (the jnp oracle).
"""
from __future__ import annotations

_warned = False


def auto_interpret() -> bool:
    """The `interpret=None` default of every kernel wrapper: compile for the
    chip when the default backend is a TPU, else run the Pallas interpreter.
    Interpreting is said once per process (a Mosaic kernel that only the
    interpreter accepts is how a chip-only refusal hides)."""
    global _warned
    import jax
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if not _warned:
        _warned = True
        from repro.obs.log import get_logger
        get_logger("repro.kernels").warning(
            "Pallas kernels run in interpret mode", backend=backend)
    return True
