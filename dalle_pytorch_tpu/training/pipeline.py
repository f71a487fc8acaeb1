"""Shared trainer plumbing: building tokenizers/datasets/models from config,
sharding states over the mesh, and checkpoint payload assembly.

This is the glue the reference keeps inline in its entry scripts
(`/root/reference/train_dalle.py:119-330`, `generate.py:70-107`),
factored so the CLIs stay thin and the pieces are testable.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Any, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from dalle_pytorch_tpu.data.tokenizer import get_tokenizer
from dalle_pytorch_tpu.models.dvae import DiscreteVAE
from dalle_pytorch_tpu.models.dalle import DALLE
from dalle_pytorch_tpu.models.transformer import FLASH_RESIDUALS, LAYER_RESIDUALS
from dalle_pytorch_tpu.training.config import TrainConfig, VaeConfig, config_to_dict
from dalle_pytorch_tpu.training.checkpoint import save_params_npz, load_params_npz
from dalle_pytorch_tpu.version import __version__


def build_tokenizer(cfg: TrainConfig):
    return get_tokenizer(
        bpe_path=cfg.bpe_path, hug=cfg.hug, chinese=cfg.chinese, yttm=cfg.yttm,
        native=getattr(cfg, "native", False), byte=getattr(cfg, "byte", False),
    )


def build_dataset(cfg: TrainConfig, tokenizer, image_size: int):
    """folder | 'rainbow[:N]' builtin | wds tar shards."""
    if cfg.wds:
        from dalle_pytorch_tpu.data.webdataset import TarImageTextDataset

        cols = [c.strip() for c in cfg.wds.split(",")]
        img_key, txt_key = (cols + ["jpg", "txt"])[:2]
        assert cfg.image_text_folder, "--image_text_folder must point at shards"
        return TarImageTextDataset(
            cfg.image_text_folder,
            image_key=img_key,
            text_key=txt_key,
            text_len=cfg.model.text_seq_len,
            image_size=image_size,
            truncate_captions=cfg.truncate_captions,
            resize_ratio=cfg.resize_ratio,
            tokenizer=tokenizer,
        )
    folder = cfg.image_text_folder or "rainbow"
    if folder.startswith("rainbow"):
        from dalle_pytorch_tpu.data.rainbow import RainbowDataset

        n = int(folder.split(":")[1]) if ":" in folder else 1024

        class _RainbowAdapter:
            def __init__(self):
                self.ds = RainbowDataset(num_samples=n, image_size=image_size)

            def __len__(self):
                return len(self.ds)

            def batches(self, batch_size, shuffle_seed=None, shard=(0, 1), **kw):
                return self.ds.batches(
                    batch_size,
                    tokenizer,
                    cfg.model.text_seq_len,
                    shuffle_seed=shuffle_seed,
                    shard=shard,
                    **kw,
                )

        return _RainbowAdapter()
    from dalle_pytorch_tpu.data.loader import TextImageDataset

    return TextImageDataset(
        folder,
        text_len=cfg.model.text_seq_len,
        image_size=image_size,
        truncate_captions=cfg.truncate_captions,
        resize_ratio=cfg.resize_ratio,
        tokenizer=tokenizer,
        class_name_json=cfg.class_name_json,
    )


def vae_from_config(vcfg: VaeConfig, dtype=jnp.float32) -> DiscreteVAE:
    return DiscreteVAE(
        image_size=vcfg.image_size,
        num_tokens=vcfg.num_tokens,
        codebook_dim=vcfg.codebook_dim,
        num_layers=vcfg.num_layers,
        num_resnet_blocks=vcfg.num_resnet_blocks,
        hidden_dim=vcfg.hidden_dim,
        channels=vcfg.channels,
        smooth_l1_loss=vcfg.smooth_l1_loss,
        temperature=vcfg.temperature,
        straight_through=vcfg.straight_through,
        reinmax=vcfg.reinmax,
        kl_div_loss_weight=vcfg.kl_loss_weight,
        dtype=dtype,
    )


def dvae_hparams(vae: DiscreteVAE) -> dict:
    return {
        "image_size": vae.image_size,
        "num_tokens": vae.num_tokens,
        "codebook_dim": vae.codebook_dim,
        "num_layers": vae.num_layers,
        "num_resnet_blocks": vae.num_resnet_blocks,
        "hidden_dim": vae.hidden_dim,
        "channels": vae.channels,
        "smooth_l1_loss": vae.smooth_l1_loss,
        "temperature": vae.temperature,
        "straight_through": vae.straight_through,
        "reinmax": vae.reinmax,
        "kl_div_loss_weight": vae.kl_div_loss_weight,
    }


def dvae_from_hparams(h: dict, dtype=jnp.float32) -> DiscreteVAE:
    return DiscreteVAE(
        image_size=h["image_size"],
        num_tokens=h["num_tokens"],
        codebook_dim=h["codebook_dim"],
        num_layers=h["num_layers"],
        num_resnet_blocks=h.get("num_resnet_blocks", 0),
        hidden_dim=h["hidden_dim"],
        channels=h.get("channels", 3),
        smooth_l1_loss=h.get("smooth_l1_loss", False),
        temperature=h.get("temperature", 0.9),
        straight_through=h.get("straight_through", False),
        reinmax=h.get("reinmax", False),
        kl_div_loss_weight=h.get("kl_div_loss_weight", 0.0),
        dtype=dtype,
    )


def save_vae_checkpoint(path: str, vae: DiscreteVAE, params, epoch: int = 0):
    """Single-file dVAE ckpt ({hparams, weights}, `train_vae.py:203-223`)."""
    hparams = dvae_hparams(vae)
    save_params_npz(
        path,
        params,
        metadata={
            "type": "DiscreteVAE",
            "version": __version__,
            "epoch": epoch,
            "hparams": hparams,
        },
    )


def load_vae_checkpoint(path: str, dtype=jnp.float32) -> Tuple[DiscreteVAE, Any]:
    params, meta = load_params_npz(path)
    assert meta.get("type") == "DiscreteVAE", f"{path} is not a dVAE checkpoint"
    vae = dvae_from_hparams(meta["hparams"], dtype=dtype)
    params = jax.tree.map(jnp.asarray, params)
    return vae, params


def build_vae(cfg: TrainConfig, dtype=jnp.float32):
    """VAE reconstitution precedence (`train_dalle.py:139-186`):
    --vae_path (trained dVAE) | --taming (VQGAN) | OpenAI pretrained."""
    if cfg.vae_path:
        return load_vae_checkpoint(cfg.vae_path, dtype=dtype)
    if cfg.taming:
        from dalle_pytorch_tpu.models.vae_io import VQGanVAE

        assert cfg.vqgan_model_path and cfg.vqgan_config_path
        return VQGanVAE(cfg.vqgan_model_path, cfg.vqgan_config_path), None
    from dalle_pytorch_tpu.models.vae_io import OpenAIDiscreteVAE

    return OpenAIDiscreteVAE(), None


# ready-to-use jax.checkpoint_policies predicates (the module's other
# attributes are factories that require arguments), and the two names
# `resolve_remat_policy` builds from a factory itself
REMAT_POLICIES = frozenset(
    {
        FLASH_RESIDUALS,
        LAYER_RESIDUALS,
        "everything_saveable",
        "nothing_saveable",
        "dots_saveable",
        "dots_with_no_batch_dims_saveable",
        "checkpoint_dots",
        "checkpoint_dots_with_no_batch_dims",
    }
)


def dalle_from_config(
    cfg: TrainConfig,
    num_image_tokens: int,
    image_fmap_size: int,
    vocab_size: int,
    sp_mesh=None,
) -> DALLE:
    """`sp_mesh`: pass the trainer's mesh when cfg.mesh.sp > 1 — the model
    then runs ring attention (sequence-parallel over the "sp" axis) for
    long-context training; with sp == 1 the mesh axis is inert and the
    configured attn_impl ("auto"/"dense"/"flash") applies."""
    m = cfg.model
    remat_policy = getattr(m, "remat_policy", None)
    if remat_policy is not None and remat_policy not in REMAT_POLICIES:
        # jax.checkpoint_policies also contains policy FACTORIES
        # (save_only_these_names, ...) that need arguments — passing one
        # directly as a policy silently disables remat, so only the
        # ready-to-use predicates are accepted here
        raise ValueError(
            f"unknown model.remat_policy {remat_policy!r}; valid names: "
            f"{sorted(REMAT_POLICIES)}"
        )
    attn_impl = m.attn_impl
    # a Pallas call is a single-device program: on a multi-device mesh the
    # flash kernel must be shard_mapped, which needs the mesh itself
    train_mesh = None
    if sp_mesh is not None and sp_mesh.size > 1 and "pp" not in sp_mesh.axis_names:
        train_mesh = sp_mesh  # (the pp trunk runs inside gpipe's own shard_map)
    executor = getattr(m, "executor", "unrolled")
    if executor not in ("unrolled", "scan"):
        raise ValueError(
            f"unknown model.executor {executor!r}; valid: unrolled, scan"
        )
    if executor == "scan" and sp_mesh is not None and sp_mesh.shape.get("sp", 1) > 1:
        raise ValueError(
            'model.executor="scan" has not been validated with ring '
            "attention (mesh.sp>1); use the unrolled executor for "
            "sequence-parallel training"
        )
    if sp_mesh is not None and sp_mesh.shape.get("sp", 1) > 1:
        if attn_impl in ("auto", "ring"):
            attn_impl = "ring"
        else:
            raise ValueError(
                f'mesh.sp={sp_mesh.shape["sp"]} requires ring attention, but '
                f"model.attn_impl={attn_impl!r} was set explicitly; use "
                '"ring" or "auto" (or set mesh.sp=1)'
            )
        if m.stable_softmax:
            raise ValueError(
                "ring attention (mesh.sp > 1) is incompatible with "
                "model.stable_softmax; its streaming accumulator is already "
                "max-subtracted"
            )
        sp = sp_mesh.shape["sp"]
        # transformer sequence = bos-padded text truncated back to
        # text_seq_len, plus the image grid (models/dalle.py __call__)
        total_seq = m.text_seq_len + image_fmap_size**2
        if total_seq % sp:
            raise ValueError(
                f"sequence length {total_seq} (text_seq_len {m.text_seq_len} "
                f"+ {image_fmap_size}^2 image tokens) must be divisible by "
                f"mesh.sp={sp} for ring attention; adjust text_seq_len"
            )
    else:
        if attn_impl == "ring":
            raise ValueError(
                'model.attn_impl="ring" needs a sequence-parallel mesh: set '
                "mesh.sp>1 in the trainer (generate/decode paths never use "
                "ring attention — KV-cached decode serves long-context "
                "models there)"
            )
        sp_mesh = None  # inert axis: don't thread a mesh the model won't use
    return DALLE(
        dim=m.dim,
        depth=m.depth,
        heads=m.heads,
        dim_head=m.dim_head,
        num_image_tokens=num_image_tokens,
        image_fmap_size=image_fmap_size,
        num_text_tokens=vocab_size,
        text_seq_len=m.text_seq_len,
        reversible=m.reversible,
        reversible_impl=getattr(m, "reversible_impl", "remat"),
        remat_policy=remat_policy,
        attn_dropout=m.attn_dropout,
        ff_dropout=m.ff_dropout,
        attn_types=m.attn_types_tuple(),
        loss_img_weight=m.loss_img_weight,
        stable=m.stable_softmax,
        sandwich_norm=m.sandwich_norm,
        shift_tokens=m.shift_tokens,
        rotary_emb=m.rotary_emb,
        shared_attn_ids=m.shared_attn_ids_tuple(),
        shared_ff_ids=m.shared_ff_ids_tuple(),
        share_input_output_emb=m.share_input_output_emb,
        text_loss_coeff=cfg.text_loss_coeff,
        img_loss_coeff=cfg.img_loss_coeff,
        text_loss_coeff_inv=cfg.text_loss_coeff_inv,
        img_loss_coeff_inv=cfg.img_loss_coeff_inv,
        attn_impl=attn_impl,
        sp_mesh=sp_mesh,
        train_mesh=train_mesh,
        executor=executor,
        fused_ce=getattr(m, "fused_ce", False),
        dtype=jnp.bfloat16 if cfg.bf16 else jnp.float32,
    )


def save_dalle_checkpoint(
    path: str,
    cfg: TrainConfig,
    dalle_params,
    vae_params,
    epoch: int,
    vae_class_name: str,
    vae_hparams: Optional[dict] = None,
    opt_state: Any = None,
    train_meta: Optional[dict] = None,
):
    """Portable single-file DALLE ckpt carrying the reference's payload
    ({hparams, vae_params, epoch, version, vae_class_name, weights,
    opt_state, scheduler_state}, `train_dalle.py:432-439,472-479`).
    `vae_hparams` records the ACTUAL frozen VAE geometry (not cfg.vae,
    which may be stale when the VAE came from --vae_path). `opt_state`
    is stored as leaves in tree-flatten order — restorable into any
    optimizer with the same structure (i.e. the same config).
    `train_meta` carries scheduler/global-step state for exact resume."""
    trees = {"dalle": dalle_params}
    if vae_params is not None:
        trees["vae"] = vae_params
    if opt_state is not None:
        leaves = jax.tree_util.tree_leaves(opt_state)
        trees["opt"] = {f"{i:04d}": np.asarray(leaf) for i, leaf in enumerate(leaves)}
    save_params_npz(
        path,
        trees,
        metadata={
            "type": "DALLE",
            "version": __version__,
            "epoch": epoch,
            "vae_class_name": vae_class_name,
            "vae_hparams": vae_hparams,
            "config": config_to_dict(cfg),
            "train": train_meta or {},
        },
    )


def load_dalle_checkpoint(path: str):
    """Returns (cfg, dalle_params, vae_params_or_None, metadata,
    opt_leaves_or_None). Restore the optimizer with
    `restore_opt_state(fresh_opt_state, opt_leaves)`."""
    params, meta = load_params_npz(path)
    assert meta.get("type") == "DALLE", f"{path} is not a DALLE checkpoint"
    cfg = TrainConfig()
    from dalle_pytorch_tpu.training.config import _merge_dict

    _merge_dict(cfg, meta["config"])
    dalle_params = jax.tree.map(jnp.asarray, params["dalle"])
    vae_params = (
        jax.tree.map(jnp.asarray, params["vae"]) if "vae" in params else None
    )
    opt_leaves = None
    if "opt" in params:
        # numeric sort: lexicographic would scramble order past 9999 leaves
        opt_leaves = [params["opt"][k] for k in sorted(params["opt"], key=int)]
    return cfg, dalle_params, vae_params, meta, opt_leaves


def restore_opt_state(fresh_opt_state: Any, opt_leaves):
    """Rebuild a saved optimizer state into `fresh_opt_state`'s structure
    (the resume half of the reference's `opt.load_state_dict`,
    `/root/reference/train_dalle.py:330-338`). Returns the restored state,
    or `fresh_opt_state` unchanged (with a warning) on mismatch — e.g.
    when resuming with a changed optimizer config."""
    if opt_leaves is None:
        return fresh_opt_state
    treedef = jax.tree_util.tree_structure(fresh_opt_state)
    fresh_leaves = jax.tree_util.tree_leaves(fresh_opt_state)
    if len(fresh_leaves) != len(opt_leaves) or any(
        jnp.shape(a) != jnp.shape(b) for a, b in zip(fresh_leaves, opt_leaves)
    ):
        print(
            "WARNING: checkpoint optimizer state does not match the current "
            "optimizer (config changed?) — starting with a fresh optimizer"
        )
        return fresh_opt_state
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.asarray(leaf) for leaf in opt_leaves]
    )


def clip_hparams(clip) -> dict:
    return {
        "dim_text": clip.dim_text,
        "dim_image": clip.dim_image,
        "dim_latent": clip.dim_latent,
        "num_text_tokens": clip.num_text_tokens,
        "text_enc_depth": clip.text_enc_depth,
        "text_seq_len": clip.text_seq_len,
        "text_heads": clip.text_heads,
        "num_visual_tokens": clip.num_visual_tokens,
        "visual_enc_depth": clip.visual_enc_depth,
        "visual_heads": clip.visual_heads,
        "visual_image_size": clip.visual_image_size,
        "visual_patch_size": clip.visual_patch_size,
        "channels": clip.channels,
        # param-layout-affecting: a scan-trained CLIP must reload as scan
        "executor": clip.executor,
    }


def save_clip_checkpoint(path: str, clip, params) -> None:
    """Single-file CLIP checkpoint (hparams + weights), the same logical
    payload shape as the reference's `.pt` saves (`train_dalle.py:432-479`)."""
    save_params_npz(path, params, metadata={"clip_hparams": clip_hparams(clip)})


def load_clip_checkpoint(path: str, dtype=jnp.float32):
    from dalle_pytorch_tpu.models.clip import CLIP

    params, metadata = load_params_npz(path)
    clip = CLIP(dtype=dtype, **metadata["clip_hparams"])
    return clip, params
