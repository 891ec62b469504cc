"""Pallas kernels: each package holds the kernel, its public op and a
pure-jnp reference the tests compare against."""

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  The ops run their kernels
    natively there and in Pallas interpret mode everywhere else."""
    return jax.default_backend() == "tpu"
