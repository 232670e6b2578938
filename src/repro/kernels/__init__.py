"""Pallas TPU kernels: ``ops.py`` holds the jitted wrappers, ``ref.py``
the pure-jnp oracles the tests compare them with."""
import jax


def default_interpret() -> bool:
    """Pallas interpret mode exactly when JAX's default backend is the CPU.

    Every kernel resolves ``interpret=None`` through this, so a kernel on a
    TPU always runs compiled; the CPU has no Mosaic backend and can only
    interpret.  A compile for a described (not attached) TPU passes
    ``interpret=False`` itself, since the default backend there is the CPU.
    """
    return jax.default_backend() == "cpu"
