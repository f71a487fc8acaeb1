import io
import json
import os
import tarfile
from pathlib import Path

import numpy as np
import pytest

from dalle_pytorch_tpu.data.tokenizer import (
    ByteTokenizer,
    SimpleTokenizer,
    get_tokenizer,
)
from dalle_pytorch_tpu.data.rainbow import RainbowDataset, COLORS, SHAPES
from dalle_pytorch_tpu.data.loader import (
    TextImageDataset,
    ImageFolderDataset,
    MnistDataset,
    random_resized_crop,
)
from dalle_pytorch_tpu.data.webdataset import TarImageTextDataset, expand_shards


class TestByteTokenizer:
    def test_roundtrip(self):
        tok = ByteTokenizer()
        ids = tok.tokenize(["small orange circle", "big blue square"], 32)
        assert ids.shape == (2, 32)
        assert ids.dtype == np.int32
        assert (ids >= 0).all()
        assert tok.decode(ids[0]) == "small orange circle"

    def test_overflow_raises_unless_truncate(self):
        tok = ByteTokenizer()
        with pytest.raises(RuntimeError, match="too long"):
            tok.tokenize("a" * 100, 8)
        out = tok.tokenize("a" * 100, 8, truncate_text=True)
        assert out.shape == (1, 8)

    def test_zero_reserved_for_padding(self):
        tok = ByteTokenizer()
        ids = tok.tokenize("hi", 8)[0]
        assert ids[0] != 0 and ids[1] != 0 and (ids[2:] == 0).all()


class TestSimpleTokenizer:
    @pytest.fixture
    def bpe_file(self, tmp_path):
        # tiny CLIP-format merges file: header line + merges
        merges = ["#version: test", "h e", "l l", "he ll", "hell o</w>", "o k</w>"]
        p = tmp_path / "merges.txt"
        p.write_text("\n".join(merges))
        return p

    def test_encode_decode_roundtrip(self, bpe_file):
        tok = SimpleTokenizer(bpe_file)
        ids = tok.encode("hello ok")
        assert len(ids) > 0
        assert tok.decode(ids) == "hello ok"

    def test_merges_reduce_token_count(self, bpe_file):
        tok = SimpleTokenizer(bpe_file)
        # 'hello' fully merges via the chain -> single token
        assert len(tok.encode("hello")) == 1

    def test_vocab_layout(self, bpe_file):
        tok = SimpleTokenizer(bpe_file)
        assert tok.vocab_size == 512 + 5 + 2

    def test_get_tokenizer_dispatch(self, bpe_file):
        # no flags -> the shipped CLIP-scale 32k default vocab (8k is the
        # fallback when the 32k model is absent; no silent ByteTokenizer
        # degradation either way)
        from dalle_pytorch_tpu.data.tokenizer import NativeBPETokenizer

        default = get_tokenizer()
        assert isinstance(default, NativeBPETokenizer)
        assert default.vocab_size == 32768
        ids = default.tokenize("small red circle", context_length=8)
        assert default.decode(ids[0]) == "small red circle"
        assert isinstance(get_tokenizer(bpe_path=str(bpe_file)), SimpleTokenizer)

    def test_default_raises_when_native_build_fails(self, monkeypatch, tmp_path):
        """The default vocabulary size is a model width: a native library
        that cannot be built is an error naming the compiler command, never
        a smaller vocabulary or the byte tokenizer."""
        import dalle_pytorch_tpu.data.native_bpe as nb

        monkeypatch.setattr(nb, "_lib", None)
        monkeypatch.setattr(nb, "_BUILD_DIR", tmp_path / "build")
        monkeypatch.setenv("CXX", "no-such-compiler")
        with pytest.raises(RuntimeError, match="no-such-compiler .*-shared"):
            get_tokenizer()

    def test_byte_tokenizer_by_explicit_choice(self):
        assert isinstance(get_tokenizer(byte=True), ByteTokenizer)

    def test_native_library_is_keyed_by_source_hash(self, monkeypatch, tmp_path):
        """A stale or foreign binary in native/build/ never stands in for
        the committed bpe.cpp: the library file is named by the source's
        hash and rebuilt when that changes."""
        import dalle_pytorch_tpu.data.native_bpe as nb

        built = nb._build_library()
        assert built.exists() and built == nb._library_path()
        src = tmp_path / "bpe.cpp"
        src.write_bytes(nb._SRC.read_bytes() + b"\n// edited\n")
        monkeypatch.setattr(nb, "_SRC", src)
        monkeypatch.setattr(nb, "_BUILD_DIR", tmp_path / "build")
        (tmp_path / "build").mkdir()
        (tmp_path / "build" / "libdalle_bpe.so").write_bytes(b"foreign")
        rebuilt = nb._build_library()
        assert rebuilt.name != built.name and rebuilt.stat().st_size > 1000


class TestRainbow:
    def test_deterministic(self):
        d1 = RainbowDataset(num_samples=16, seed=3)
        d2 = RainbowDataset(num_samples=16, seed=3)
        np.testing.assert_array_equal(d1.image(5), d2.image(5))
        assert d1.caption(5) == d2.caption(5)

    def test_images_valid(self):
        ds = RainbowDataset(num_samples=8, image_size=32)
        for i in range(8):
            img = ds.image(i)
            assert img.shape == (32, 32, 3)
            assert img.min() >= 0 and img.max() <= 1
            assert img.max() > 0.25  # shape actually drawn (textures dim to 0.3)
            words = ds.caption(i).split()
            assert any(w in COLORS for w in words)
            assert any(w in SHAPES for w in words)

    def test_batches_sharded(self):
        ds = RainbowDataset(num_samples=32)
        tok = ByteTokenizer()
        b0 = list(ds.batches(4, tok, 24, shard=(0, 2)))
        b1 = list(ds.batches(4, tok, 24, shard=(1, 2)))
        assert len(b0) == len(b1) == 4
        assert b0[0]["images"].shape == (4, 32, 32, 3)
        assert b0[0]["text"].shape == (4, 24)
        assert not np.array_equal(b0[0]["images"], b1[0]["images"])


@pytest.fixture
def image_folder(tmp_path):
    from PIL import Image

    for cls, color in [("red_things", (255, 0, 0)), ("blue_things", (0, 0, 255))]:
        d = tmp_path / "train" / cls
        d.mkdir(parents=True)
        for i in range(3):
            Image.new("RGB", (40, 50), color).save(d / f"im{i}.png")
    # one paired-caption image
    cap = tmp_path / "train" / "red_things" / "special.png"
    Image.new("RGB", (40, 40), (255, 255, 0)).save(cap)
    cap.with_suffix(".txt").write_text("a special yellow image")
    return tmp_path / "train"


class TestFolderDataset:
    def test_captions_from_dirs_and_txt(self, image_folder):
        ds = ImageFolderDataset(str(image_folder))
        caps = {ds.get(i)[0] for i in range(len(ds))}
        assert "red things" in caps and "blue things" in caps
        assert "a special yellow image" in caps

    def test_class_name_json(self, image_folder, tmp_path):
        mapping = tmp_path / "map.json"
        mapping.write_text(json.dumps({"red_things": "crimson objects"}))
        ds = ImageFolderDataset(str(image_folder), class_name_json=str(mapping))
        caps = {ds.get(i)[0] for i in range(len(ds))}
        assert "crimson objects" in caps

    def test_imagenet_wnid_dirs_caption_out_of_the_box(self, tmp_path):
        # wnid-named class dirs resolve through the shipped
        # data/imagenet_classes.json with no --class_name_json flag
        # (reference vendors the same mapping, `loader.py:43-54`)
        from PIL import Image

        for wnid in ("n01440764", "n01443537"):
            d = tmp_path / wnid
            d.mkdir(parents=True)
            Image.new("RGB", (8, 8), (0, 128, 0)).save(d / "x.png")
        ds = ImageFolderDataset(str(tmp_path))
        caps = {ds.get(i)[0] for i in range(len(ds))}
        assert caps == {"tench", "goldfish"}

    def test_unknown_wnid_falls_back_to_dir_name(self, tmp_path):
        from PIL import Image

        d = tmp_path / "n99999999"
        d.mkdir(parents=True)
        Image.new("RGB", (8, 8), (0, 0, 0)).save(d / "x.png")
        ds = ImageFolderDataset(str(tmp_path))
        assert ds.get(0)[0] == "n99999999"

    def test_pipeline_batches(self, image_folder):
        ds = TextImageDataset(
            str(image_folder), text_len=16, image_size=32,
            truncate_captions=True,
        )
        batches = list(ds.batches(2, shuffle_seed=0))
        assert len(batches) == 3
        assert batches[0]["images"].shape == (2, 32, 32, 3)
        assert batches[0]["images"].dtype == np.float32
        assert batches[0]["text"].shape == (2, 16)

    def test_corrupt_image_fallback(self, image_folder):
        bad = image_folder / "red_things" / "corrupt.png"
        bad.write_bytes(b"not an image")
        ds = TextImageDataset(str(image_folder), text_len=8, image_size=16,
                              truncate_captions=True)
        # consuming every sample must not raise
        n = sum(b["text"].shape[0] for b in ds.batches(1, drop_last=False))
        assert n == len(ds)


class TestMnist:
    @pytest.fixture
    def mnist_dir(self, tmp_path):
        import struct

        imgs = np.random.RandomState(0).randint(0, 255, (4, 28, 28), np.uint8)
        lbls = np.asarray([0, 5, 9, 3], np.uint8)
        with open(tmp_path / "train-images-idx3-ubyte", "wb") as f:
            f.write(struct.pack(">IIII", 2051, 4, 28, 28))
            f.write(imgs.tobytes())
        with open(tmp_path / "train-labels-idx1-ubyte", "wb") as f:
            f.write(struct.pack(">II", 2049, 4))
            f.write(lbls.tobytes())
        return tmp_path

    def test_idx_loading(self, mnist_dir):
        ds = MnistDataset(str(mnist_dir), train=True)
        assert len(ds) == 4
        cap, img = ds.get(1)
        assert cap == "five"
        assert img.shape == (28, 28, 3)


class TestWebdataset:
    @pytest.fixture
    def tar_shards(self, tmp_path):
        from PIL import Image

        for s in range(2):
            with tarfile.open(tmp_path / f"shard-{s:04d}.tar", "w") as tar:
                for i in range(3):
                    key = f"sample{s}{i}"
                    buf = io.BytesIO()
                    Image.new("RGB", (32, 32), (s * 100, i * 50, 0)).save(
                        buf, format="JPEG"
                    )
                    data = buf.getvalue()
                    info = tarfile.TarInfo(f"{key}.jpg")
                    info.size = len(data)
                    tar.addfile(info, io.BytesIO(data))
                    txt = f"caption {s} {i}".encode()
                    info = tarfile.TarInfo(f"{key}.txt")
                    info.size = len(txt)
                    tar.addfile(info, io.BytesIO(txt))
        return tmp_path

    def test_brace_expansion(self):
        shards = expand_shards("shard-{0000..0003}.tar")
        assert shards == [f"shard-{i:04d}.tar" for i in range(4)]

    def test_iterates_pairs(self, tar_shards):
        ds = TarImageTextDataset(str(tar_shards), text_len=16, image_size=16)
        batches = list(ds.batches(3))
        assert len(batches) == 2
        assert batches[0]["images"].shape == (3, 16, 16, 3)
        assert batches[0]["text"].shape == (3, 16)

    def test_shard_split(self, tar_shards):
        ds = TarImageTextDataset(str(tar_shards), text_len=8, image_size=16)
        s0 = list(ds.samples(shard=(0, 2)))
        s1 = list(ds.samples(shard=(1, 2)))
        assert len(s0) == 3 and len(s1) == 3
        assert {c for c, _ in s0}.isdisjoint({c for c, _ in s1})

    def test_shuffle_seed_reshuffles_epochs(self, tar_shards):
        ds = TarImageTextDataset(
            str(tar_shards), text_len=8, image_size=16, shuffle_buffer=4
        )
        base = [c for c, _ in ds.samples()]
        e0 = [c for c, _ in ds.samples(shuffle_seed=0)]
        e0_again = [c for c, _ in ds.samples(shuffle_seed=0)]
        e1 = [c for c, _ in ds.samples(shuffle_seed=1)]
        assert sorted(e0) == sorted(base)  # a permutation, nothing dropped
        assert e0 == e0_again  # deterministic per seed
        assert e0 != e1 or e0 != base  # epochs actually reshuffle

    def test_missing_caption_filtered(self, tmp_path):
        from PIL import Image

        with tarfile.open(tmp_path / "solo.tar", "w") as tar:
            buf = io.BytesIO()
            Image.new("RGB", (8, 8)).save(buf, format="JPEG")
            data = buf.getvalue()
            info = tarfile.TarInfo("orphan.jpg")
            info.size = len(data)
            tar.addfile(info, io.BytesIO(data))
        ds = TarImageTextDataset(str(tmp_path / "solo.tar"))
        assert list(ds.samples()) == []


class TestCrop:
    def test_random_resized_crop_shape_and_range(self):
        rng = np.random.RandomState(0)
        img = np.random.randint(0, 255, (50, 70, 3), np.uint8)
        out = random_resized_crop(img, 32, rng)
        assert out.shape == (32, 32, 3)
        assert 0.0 <= out.min() and out.max() <= 1.0


class TestTokenDataset:
    """Offline token precompute (precompute_tokens.py + TokenDataset) — the
    offline counterpart of the in-forward frozen-VAE encode
    (`dalle_pytorch.py:619-627`)."""

    def test_roundtrip(self, tmp_path):
        import subprocess, sys, os
        from pathlib import Path

        repo = Path(__file__).resolve().parents[1]
        env = {**os.environ, "PYTHONPATH": str(repo),
               "JAX_PLATFORMS": "cpu"}

        # tiny dVAE checkpoint
        import jax, jax.numpy as jnp
        from dalle_pytorch_tpu.models.dvae import DiscreteVAE
        from dalle_pytorch_tpu.training.pipeline import save_vae_checkpoint

        vae = DiscreteVAE(image_size=16, num_layers=2, num_tokens=32,
                          codebook_dim=16, hidden_dim=16)
        params = vae.init(
            {"params": jax.random.PRNGKey(0), "gumbel": jax.random.PRNGKey(1)},
            jnp.zeros((1, 16, 16, 3)),
        )["params"]
        save_vae_checkpoint(str(tmp_path / "vae.npz"), vae, params)

        out = subprocess.run(
            [sys.executable, str(repo / "precompute_tokens.py"),
             "--image_text_folder", "rainbow:20",
             "--vae_path", str(tmp_path / "vae.npz"),
             "--batch_size", "8", "--output", str(tmp_path / "tok.npz")],
            capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path,
        )
        assert out.returncode == 0, out.stdout + out.stderr

        from dalle_pytorch_tpu.data.loader import TokenDataset
        from dalle_pytorch_tpu.data.tokenizer import ByteTokenizer

        ds = TokenDataset(tmp_path / "tok.npz", ByteTokenizer(), text_len=16)
        assert len(ds) == 20  # drop_last=False keeps the ragged tail
        assert ds.num_tokens == 32 and ds.image_size == 16
        batches = list(ds.batches(8, shuffle_seed=0))
        assert len(batches) == 2  # 20 // 8 full batches
        b = batches[0]
        assert b["text"].shape == (8, 16)
        assert b["image_tokens"].shape == (8, 16)  # 4x4 fmap
        assert b["image_tokens"].dtype == np.int32
        # captions roundtrip through the tokenizer
        text = ByteTokenizer().decode(b["text"][0])
        # text_len=16 may truncate the shape word; size words survive
        from dalle_pytorch_tpu.data.rainbow import SIZES

        assert any(text.startswith(w) for w in SIZES)
