"""The platforms the kernels run on.

On a TPU the serving path runs the compiled Pallas kernels; on the CPU the
pure-jnp references in ``ref.py`` (the kernels themselves are checked
against those in interpret mode by the tests).  The 2:4 GEMM dispatch is
``sparse.apply._run_nm``: ``jax.lax.platform_dependent`` picks the branch
when the program is lowered, and lowering for any other platform is an
error - a GPU or an unknown plug-in must not slip onto a reference path
unnoticed.  ``backend()`` serves code that must choose while tracing (an
algorithm whose collectives differ per platform), never at import.
"""
from __future__ import annotations

import jax

SUPPORTED_PLATFORMS = ("cpu", "tpu")


def backend() -> str:
    """The default backend's platform, "cpu" or "tpu"; anything else is an
    error (the serving path has kernels for TPU and references for CPU)."""
    platform = jax.default_backend()
    if platform not in SUPPORTED_PLATFORMS:
        raise RuntimeError(
            f"platform {platform!r} is not supported: the kernels run on "
            "'tpu' and their references on 'cpu' (set JAX_PLATFORMS)")
    return platform

