"""Diagnose a real OpenAI dVAE / taming VQGAN checkpoint against this
framework's converters.

The in-repo golden tests for the pretrained-VAE bridges run against
synthetic checkpoints (the container has no egress to download the real
ones — `tests/test_openai_vae.py`), so the exact key layout of the
*released* files has never been seen by this code. This script is the
field diagnostic for that residual risk: point it at real files and it
validates structure inference, round-trips an encode/decode, and prints
shapes — BEFORE you spend a training run on it.

Usage:
  python scripts/check_pretrained_vae.py --openai ~/.cache/dalle
  python scripts/check_pretrained_vae.py --vqgan model.ckpt config.yaml
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def check_openai(cache_dir: str) -> int:
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.vae_io import OpenAIDiscreteVAE

    print(f"loading OpenAI dVAE from {cache_dir} ...")
    try:
        vae = OpenAIDiscreteVAE(cache_dir=cache_dir)
    except FileNotFoundError as e:
        print(f"FAIL: {e}")
        return 1
    except Exception as e:
        print(f"FAIL: converter could not ingest the checkpoint structure: "
              f"{type(e).__name__}: {e}")
        print("-> please report this with the state-dict key listing")
        return 1

    print(f"  image_size={vae.image_size} num_layers={vae.num_layers} "
          f"num_tokens={vae.num_tokens}")
    img = jnp.zeros((1, vae.image_size, vae.image_size, 3), jnp.float32) + 0.5
    toks = vae.get_codebook_indices(img)
    print(f"  encode: {img.shape} -> tokens {toks.shape} "
          f"(range [{int(toks.min())}, {int(toks.max())}])")
    assert toks.shape[1] == (vae.image_size // (2 ** vae.num_layers)) ** 2
    out = vae.decode(toks)
    print(f"  decode: tokens -> {out.shape} "
          f"(range [{float(out.min()):.3f}, {float(out.max()):.3f}])")
    assert out.shape[1] == vae.image_size
    print("OK: OpenAI dVAE converter handles this checkpoint")
    return 0


def check_vqgan(model_path: str, config_path: str) -> int:
    import jax.numpy as jnp

    from dalle_pytorch_tpu.models.vae_io import VQGanVAE

    print(f"loading VQGAN from {model_path} ...")
    try:
        vae = VQGanVAE(model_path, config_path)
    except Exception as e:
        print(f"FAIL: {type(e).__name__}: {e}")
        return 1
    img = jnp.zeros((1, vae.image_size, vae.image_size, 3), jnp.float32) + 0.5
    toks = vae.get_codebook_indices(img)
    out = vae.decode(toks)
    print(f"  encode {img.shape} -> {toks.shape}; decode -> {out.shape}")
    print("OK: VQGAN converter handles this checkpoint")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--openai", metavar="CACHE_DIR",
                    help="directory holding encoder.pkl / decoder.pkl")
    ap.add_argument("--vqgan", nargs=2, metavar=("MODEL", "CONFIG"))
    args = ap.parse_args()
    if not args.openai and not args.vqgan:
        ap.error("pass --openai and/or --vqgan")
    rc = 0
    if args.openai:
        rc |= check_openai(args.openai)
    if args.vqgan:
        rc |= check_vqgan(*args.vqgan)
    return rc


if __name__ == "__main__":
    sys.exit(main())
