"""paddle_tpu.ops.pallas — hand-written TPU kernels (Pallas/Mosaic).

The capability counterpart of the reference's fused CUDA kernel library
(paddle/phi/kernels/fusion/gpu/, fusion/cutlass/ — fused attention, rope,
rms_norm, MoE dispatch). On TPU the hot ops are Pallas kernels; every entry
point keeps a pure-XLA fallback so the same code runs on the CPU test mesh.
"""
import jax


def on_tpu() -> bool:
    """Whether kernels compile for the chip (else: interpret mode / the
    jnp formulation). No try/except — a backend that fails to
    initialise must raise, not quietly turn every kernel off."""
    return jax.default_backend() == "tpu"


from . import flash_attention  # noqa: E402
