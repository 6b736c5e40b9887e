"""Pallas kernels (GEMM, flash attention, RMSNorm) and their jnp oracles."""
import jax


def interpret_mode() -> bool:
    """Pallas interpret mode for the default backend: Mosaic on a TPU, the
    interpreter on the CPU (tests). Any other backend is an error rather
    than a silent, slow interpreter run."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels need a TPU (Mosaic) or the CPU (interpret mode); "
        f"the default backend is {backend!r}")
