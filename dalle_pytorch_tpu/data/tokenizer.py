"""Host-side text tokenizers.

Functional equivalents of the reference's four tokenizers
(`/root/reference/dalle_pytorch/tokenizer.py:55,158,196,232`), all sharing
the contract `tokenize(texts, context_length, truncate_text) ->
int32 [B, ctx]` zero-padded (id 0 is reserved: it becomes the
per-position unique padding token inside DALLE) and `decode(ids)`.

Differences from the reference, by design:
  * tokenization is pure host-side numpy — tokens are fed to jit'ted
    steps as arrays, so no torch dependency;
  * the CLIP BPE vocabulary file is NOT vendored (262k lines; and this
    build environment has no egress) — `SimpleTokenizer` accepts any
    CLIP-format merges file via `bpe_path` and is byte-exact against the
    published CLIP BPE (tests/test_tokenizer_goldens.py);
  * the DEFAULT is the shipped CLIP-scale 32k-merge native C++ BPE
    vocabulary (`default_bpe_32k.model`, `NativeBPETokenizer`) — the
    in-repo replacement for the reference's youtokentome dependency;
  * `ByteTokenizer` is a dependency-free fallback (raw UTF-8 bytes +
    offset) so the full pipeline runs with zero data files.
"""

from __future__ import annotations

import html
from functools import lru_cache
from pathlib import Path
from typing import List, Optional, Sequence, Union

import numpy as np

try:
    import regex as re
except ImportError:  # pragma: no cover
    import re  # type: ignore


# ---------------------------------------------------------------- helpers


@lru_cache()
def _byte_unicode_table() -> dict:
    """Reversible byte -> printable-unicode mapping (GPT-2/CLIP scheme).

    Insertion order matters beyond the mapping itself: the CLIP vocabulary
    lists the printable bytes first (in codepoint order) and the remapped
    non-printables after, and single-symbol token ids are positions in that
    list — so this dict iterates in CLIP vocab order, not byte order
    (verified byte-exact by tests/test_tokenizer_goldens.py).
    """
    printable = (
        list(range(ord("!"), ord("~") + 1))
        + list(range(ord("\xa1"), ord("\xac") + 1))
        + list(range(ord("\xae"), ord("\xff") + 1))
    )
    mapping = {b: chr(b) for b in printable}
    extra = 0
    for b in range(256):
        if b not in mapping:
            mapping[b] = chr(256 + extra)
            extra += 1
    return mapping


def _clean_text(text: str) -> str:
    try:
        import ftfy

        text = ftfy.fix_text(text)
    except ImportError:
        pass
    text = html.unescape(html.unescape(text))
    return " ".join(text.split()).strip()


def _pack(
    token_lists: Sequence[List[int]],
    context_length: int,
    truncate_text: bool,
    texts: Sequence[str],
) -> np.ndarray:
    out = np.zeros((len(token_lists), context_length), dtype=np.int32)
    for i, toks in enumerate(token_lists):
        if len(toks) > context_length:
            if not truncate_text:
                raise RuntimeError(
                    f"Input {texts[i]!r} is too long for context length "
                    f"{context_length}"
                )
            toks = toks[:context_length]
        out[i, : len(toks)] = toks
    return out


class _TokenizerBase:
    vocab_size: int

    def encode(self, text: str) -> List[int]:
        raise NotImplementedError

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        raise NotImplementedError

    def tokenize(
        self,
        texts: Union[str, Sequence[str]],
        context_length: int = 256,
        truncate_text: bool = False,
    ) -> np.ndarray:
        if isinstance(texts, str):
            texts = [texts]
        return _pack([self.encode(t) for t in texts], context_length, truncate_text, texts)

    @staticmethod
    def _to_list(tokens) -> List[int]:
        if hasattr(tokens, "tolist"):
            return [int(t) for t in np.asarray(tokens).reshape(-1)]
        return list(tokens)


# ---------------------------------------------------------- byte fallback


class ByteTokenizer(_TokenizerBase):
    """Dependency-free byte-level tokenizer: ids = utf-8 bytes + 1.

    Not in the reference; exists so the framework runs end-to-end with no
    vocabulary file (id 0 stays reserved for padding).
    """

    def __init__(self):
        self.vocab_size = 257

    def encode(self, text: str) -> List[int]:
        return [b + 1 for b in _clean_text(text).lower().encode("utf-8")]

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        toks = [t for t in self._to_list(tokens) if t > 0 and t not in pad_tokens]
        return bytes(t - 1 for t in toks).decode("utf-8", errors="replace")


# ------------------------------------------------------------- CLIP BPE


class SimpleTokenizer(_TokenizerBase):
    """Byte-level BPE in the OpenAI-CLIP vocabulary format.

    Loads a CLIP `bpe_simple_vocab_16e6.txt`-style merges file (first line
    is a header; merges are space-separated pairs). Vocabulary layout
    matches CLIP: 256 byte symbols, 256 end-of-word symbols, one id per
    merge, then <|startoftext|>/<|endoftext|> (total 49,408 for the
    standard file — reference `tokenizer.py:68`).
    """

    MAX_MERGES = 49152 - 256 - 2

    def __init__(self, bpe_path: Union[str, Path]):
        bpe_path = Path(bpe_path)
        assert bpe_path.exists(), f"BPE merges file {bpe_path} does not exist"
        self.byte_to_unicode = _byte_unicode_table()
        self.unicode_to_byte = {v: k for k, v in self.byte_to_unicode.items()}

        lines = bpe_path.read_text(encoding="utf8").split("\n")
        merges = [tuple(m.split()) for m in lines[1 : self.MAX_MERGES + 1] if m]

        symbols = list(self.byte_to_unicode.values())
        vocab = symbols + [s + "</w>" for s in symbols]
        vocab += ["".join(pair) for pair in merges]
        vocab += ["<|startoftext|>", "<|endoftext|>"]

        self.token_to_id = {tok: i for i, tok in enumerate(vocab)}
        self.id_to_token = {i: tok for tok, i in self.token_to_id.items()}
        self.merge_rank = {pair: i for i, pair in enumerate(merges)}
        self.vocab_size = len(vocab)
        self._cache: dict[str, List[str]] = {}
        self.pattern = re.compile(
            r"<\|startoftext\|>|<\|endoftext\|>|'s|'t|'re|'ve|'m|'ll|'d"
            r"|[\p{L}]+|[\p{N}]|[^\s\p{L}\p{N}]+",
            re.IGNORECASE,
        )
        self.sot = self.token_to_id["<|startoftext|>"]
        self.eot = self.token_to_id["<|endoftext|>"]

    def _bpe(self, token: str) -> List[str]:
        if token in self._cache:
            return self._cache[token]
        parts = list(token[:-1]) + [token[-1] + "</w>"]
        while len(parts) > 1:
            pairs = [(parts[i], parts[i + 1]) for i in range(len(parts) - 1)]
            ranked = min(pairs, key=lambda p: self.merge_rank.get(p, float("inf")))
            if ranked not in self.merge_rank:
                break
            merged: List[str] = []
            i = 0
            while i < len(parts):
                if (
                    i < len(parts) - 1
                    and (parts[i], parts[i + 1]) == ranked
                ):
                    merged.append(parts[i] + parts[i + 1])
                    i += 2
                else:
                    merged.append(parts[i])
                    i += 1
            parts = merged
        self._cache[token] = parts
        return parts

    def encode(self, text: str) -> List[int]:
        ids: List[int] = []
        for word in re.findall(self.pattern, _clean_text(text).lower()):
            if word in ("<|startoftext|>", "<|endoftext|>"):
                # control tokens pass through whole (the pattern matches
                # them as single words; they must not be byte-BPE'd)
                ids.append(self.token_to_id[word])
                continue
            mapped = "".join(self.byte_to_unicode[b] for b in word.encode("utf-8"))
            ids.extend(self.token_to_id[p] for p in self._bpe(mapped))
        return ids

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        skip = set(pad_tokens) | {0, self.sot, self.eot}
        toks = [t for t in self._to_list(tokens) if t not in skip]
        text = "".join(self.id_to_token.get(t, "") for t in toks)
        raw = bytes(self.unicode_to_byte[c] for c in text if c in self.unicode_to_byte)
        return raw.decode("utf-8", errors="replace").replace("</w>", " ").strip()


# --------------------------------------------------- HuggingFace bridges


class HugTokenizer(_TokenizerBase):
    """tokenizers-json bridge (reference `tokenizer.py:158-192`)."""

    def __init__(self, bpe_path: Union[str, Path]):
        from transformers import PreTrainedTokenizerFast

        bpe_path = Path(bpe_path)
        assert bpe_path.exists(), f"BPE json path {bpe_path} does not exist"
        self.tokenizer = PreTrainedTokenizerFast(tokenizer_file=str(bpe_path))
        self.vocab_size = self.tokenizer.vocab_size

    def encode(self, text: str) -> List[int]:
        return self.tokenizer.encode(text, add_special_tokens=False)

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        skip = set(pad_tokens) | {0}
        toks = [t for t in self._to_list(tokens) if t not in skip]
        return self.tokenizer.decode(toks, skip_special_tokens=True)


class ChineseTokenizer(_TokenizerBase):
    """bert-base-chinese wordpiece (reference `tokenizer.py:196-228`).

    Requires the model files locally (no egress in this build env).
    """

    def __init__(self, model_name: str = "bert-base-chinese"):
        from transformers import BertTokenizerFast

        self.tokenizer = BertTokenizerFast.from_pretrained(model_name)
        self.vocab_size = self.tokenizer.vocab_size

    def encode(self, text: str) -> List[int]:
        return self.tokenizer.encode(text, add_special_tokens=False)

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        skip = set(pad_tokens) | {0}
        toks = [t for t in self._to_list(tokens) if t not in skip]
        return self.tokenizer.decode(toks)


class YttmTokenizer(_TokenizerBase):
    """youtokentome-model bridge (reference `tokenizer.py:232-266`).

    youtokentome (C++ BPE) is not in this environment; raise with
    guidance. `NativeBPETokenizer` (native/bpe.cpp) is the in-repo
    replacement for new vocabularies; this bridge exists for users with
    existing yttm model files and an installed youtokentome.
    """

    def __init__(self, bpe_path: Union[str, Path]):
        try:
            import youtokentome as yttm  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "youtokentome is not installed; use SimpleTokenizer/"
                "HugTokenizer, or convert the yttm model to a tokenizers json"
            ) from e
        import youtokentome as yttm

        self.tokenizer = yttm.BPE(model=str(bpe_path))
        self.vocab_size = self.tokenizer.vocab_size()

    def encode(self, text: str) -> List[int]:
        import youtokentome as yttm

        return self.tokenizer.encode([text], output_type=yttm.OutputType.ID)[0]

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        return self.tokenizer.decode(
            [self._to_list(tokens)], ignore_ids=list(set(pad_tokens) | {0})
        )[0]


class NativeBPETokenizer(_TokenizerBase):
    """Framework-native C++ BPE (native/bpe.cpp via ctypes) — the in-repo
    replacement for the reference's youtokentome C++ dependency
    (`tokenizer.py:232-266`). Same tokenize/decode contract; batch encode
    runs threaded in native code.
    """

    def __init__(self, bpe_path: Union[str, Path]):
        from dalle_pytorch_tpu.data.native_bpe import NativeBPE

        self.bpe = NativeBPE.load(bpe_path)
        self.vocab_size = self.bpe.vocab_size

    @classmethod
    def train(cls, corpus: str, model_path: Union[str, Path], vocab_size: int = 8192):
        from dalle_pytorch_tpu.data.native_bpe import NativeBPE

        NativeBPE.train(corpus, vocab_size).save(model_path)
        return cls(model_path)

    def encode(self, text: str) -> List[int]:
        return self.bpe.encode(_clean_text(text))

    def tokenize(
        self,
        texts: Union[str, Sequence[str]],
        context_length: int = 256,
        truncate_text: bool = False,
    ) -> np.ndarray:
        if isinstance(texts, str):
            texts = [texts]
        return self.bpe.encode_batch(
            [_clean_text(t) for t in texts], context_length, truncate=truncate_text
        )

    def decode(self, tokens, pad_tokens: set = frozenset()) -> str:
        ids = [t for t in self._to_list(tokens) if t not in pad_tokens]
        return self.bpe.decode(ids)


def get_tokenizer(
    bpe_path: Optional[str] = None,
    hug: bool = False,
    chinese: bool = False,
    yttm: bool = False,
    native: bool = False,
    byte: bool = False,
) -> _TokenizerBase:
    """Tokenizer selection mirroring the trainer flags
    (`/root/reference/train_dalle.py:131-135`), plus the framework-native
    C++ BPE backend and the dependency-free byte tokenizer (`byte`)."""
    if byte:
        return ByteTokenizer()
    if chinese:
        return ChineseTokenizer()
    if native:
        assert bpe_path, "--bpe_path required for native BPE tokenizer"
        return NativeBPETokenizer(bpe_path)
    if yttm:
        assert bpe_path, "--bpe_path required for yttm tokenizer"
        return YttmTokenizer(bpe_path)
    if hug:
        assert bpe_path, "--bpe_path required for huggingface tokenizer"
        return HugTokenizer(bpe_path)
    if bpe_path:
        return SimpleTokenizer(bpe_path)
    # No flags: the shipped native BPE vocabulary (the analogue of the
    # reference's vendored CLIP vocab, `tokenizer.py:64-68`) — trained by
    # scripts/train_default_vocab.py and committed to the repo. Discovery is
    # by glob so a regenerated default_bpe_<N>k.model is picked up; the
    # largest vocabulary wins. The vocabulary size is a model width
    # (num_text_tokens), so nothing here degrades: a native library that
    # cannot be built raises, naming the compiler command.

    def _vocab_k(p: Path) -> int:
        try:
            return int(p.stem[len("default_bpe_"):].rstrip("k"))
        except ValueError:
            return 0

    models = sorted(
        Path(__file__).parent.glob("default_bpe_*.model"), key=_vocab_k
    )
    if not models:
        raise FileNotFoundError(
            f"no default BPE vocabulary ({Path(__file__).parent}/"
            "default_bpe_*.model): run scripts/train_default_vocab.py, or "
            "choose a tokenizer explicitly (bpe_path=..., or byte=true for "
            "the 257-symbol ByteTokenizer)"
        )
    return NativeBPETokenizer(models[-1])
