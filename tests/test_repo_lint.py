"""Repo hygiene: no debugger artifacts in the shipped package.

The reference codebase shipped live import-time breakpoints — `import ipdb;
st()` at module scope (SURVEY.md §0) — which turn any import into a hung
process. This check used to be a regex scan; it is now a thin shim over
tracelint's TL006 rule (`dalle_pytorch_tpu/analysis/`), which parses the
AST instead of pattern-matching lines: strings and comments mentioning
`breakpoint()` no longer need carve-outs, and `.set_trace()` is covered
too. The suite still fails with the same SURVEY.md §0 message.
"""

from pathlib import Path

from dalle_pytorch_tpu.analysis import lint_paths

PACKAGE = Path(__file__).resolve().parent.parent / "dalle_pytorch_tpu"


def test_no_debugger_artifacts_in_package():
    assert PACKAGE.is_dir(), f"package dir moved? {PACKAGE}"
    result = lint_paths([PACKAGE], select={"TL006"})
    assert result.clean, (
        "debugger artifacts in shipped code (the reference repo's "
        "import-time-breakpoint regression, SURVEY.md §0):\n"
        + "\n".join(f.render() for f in result.findings)
    )


def test_the_decode_caches_layout_has_one_owner():
    """`models/decode_cache.py` alone knows how a decode cache is laid out:
    no other module spells a per-layer key, and only the trunk (which picks
    the EXECUTOR, and says which layout it takes when a cache is made) and
    the trainer's config check compare `executor`. A function that takes
    `executor` to handle a cache has nowhere left to live."""
    owner = PACKAGE / "models" / "decode_cache.py"
    may_compare = {PACKAGE / "models" / "transformer.py", PACKAGE / "training" / "pipeline.py"}
    keyed, compares = [], []
    for path in sorted(PACKAGE.rglob("*.py")):
        text = path.read_text()
        if path != owner and 'layer_{' in text:
            keyed.append(str(path.relative_to(PACKAGE)))
        if path not in may_compare and "executor ==" in text:
            compares.append(str(path.relative_to(PACKAGE)))
    assert not keyed, f"per-layer cache keys spelled outside decode_cache.py: {keyed}"
    assert not compares, f"`executor ==` outside the trunk and the trainer: {compares}"
