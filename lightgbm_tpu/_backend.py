"""The one backend query. Kernel selection (Pallas vs XLA formulations),
the growth-mode / histogram-dtype ``auto`` policies, row re-blocking and
buffer donation all key off the platform jax actually initialised —
asked once, here, with no ``except``: a backend that fails to
initialise is an error the caller must see, never a quiet "not a TPU"
that sends a chip run down the CPU formulations."""

from __future__ import annotations


def platform() -> str:
    """``jax.default_backend()``: "tpu", "cpu", ... Initialises the
    backend on first call."""
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"
