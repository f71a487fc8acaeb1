"""dalle_pytorch_tpu: a TPU-native (JAX/XLA/Pallas/pjit) framework with the
capabilities of DALLE-pytorch (discrete VAE + autoregressive text->image
transformer + CLIP), re-designed TPU-first.

Public API mirrors the reference package surface
(`/root/reference/dalle_pytorch/__init__.py:1-2`): DALLE, CLIP, DiscreteVAE,
plus pretrained-VAE import wrappers.
"""

from dalle_pytorch_tpu.version import __version__
from dalle_pytorch_tpu._lazy import lazy_exports

_EXPORTS = {
    "CLIP": "models.clip",
    "DALLE": "models.dalle",
    "DiscreteVAE": "models.dvae",
    "OpenAIDiscreteVAE": "models.vae_io",
    "VQGanVAE": "models.vae_io",
}
__getattr__, __dir__ = lazy_exports(__name__, _EXPORTS)

__all__ = [
    "DALLE",
    "CLIP",
    "DiscreteVAE",
    "OpenAIDiscreteVAE",
    "VQGanVAE",
    "__version__",
]
