"""PEP 562 lazy re-exports for the package `__init__`s.

`serve.py --supervise`, `launch.py`, the fleet router and `chip_smoke.py`
are parents that must stay off JAX (a process that holds the chip starves
the child it spawns). They import stdlib-only submodules of this package —
which runs the package `__init__`s — so those must not pull the model code
(and with it jax/flax) in eagerly. Names resolve on first attribute access
instead; `from dalle_pytorch_tpu.serving import ContinuousEngine` works as
before.
"""

import importlib
import sys


def lazy_exports(package: str, exports: dict):
    """(`__getattr__`, `__dir__`) for a package whose public names live in
    submodules: `exports` maps name -> submodule (relative to `package`)."""

    def __getattr__(name):
        sub = exports.get(name)
        if sub is None:
            raise AttributeError(f"module {package!r} has no attribute {name!r}")
        value = getattr(importlib.import_module(f"{package}.{sub}"), name)
        setattr(sys.modules[package], name, value)  # resolve once
        return value

    def __dir__():
        return sorted(exports)

    return __getattr__, __dir__
