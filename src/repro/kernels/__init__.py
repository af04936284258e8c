"""Pallas TPU kernels, each with a jnp reference (ref.py) and a jitted
wrapper (ops.py). A wrapper compiles its kernel for the TPU and runs it
in the Pallas interpreter on any other backend."""
import jax


def interpret_mode() -> bool:
    """True off the TPU, where a Pallas kernel can only be interpreted."""
    return jax.default_backend() != "tpu"
