"""Pallas kernels of the scan's hot path (``gwas_dot``, ``tstat``)."""
from __future__ import annotations

import jax

__all__ = ["pallas_interpret"]


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    The CPU backend interprets them (the test suite validates kernel bodies
    that way); the TPU compiles them through Mosaic.  Any other backend is
    an error: the kernels are written for the TPU, and quietly running the
    interpreter on an accelerator would hide that the compiled kernel never
    ran.
    """
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither"
    )
