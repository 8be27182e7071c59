"""Plain float32 references: they import nothing of the program.

A configuration file names its reference model under ``"reference"``;
``model(name)`` finds the module ``bench/reference/<name>.py`` by that name.
A reference model defines ``token_nll(weights, tokens, targets, cfg, prec)``
and ``flops_per_token(sizes, seq)`` (see ``bench/flops.py``), and may import
the shared helpers and the other models of this package relatively.  A new
architecture brings its reference as a new file here and names it in its
configuration file; nothing else in the benchmark changes.
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

# modules of this package that are not models
HELPERS = ("adamw", "corpus", "numerics", "train")


def model(name: str):
    """The reference model ``name``, from the benchmark's own directory
    (``harness.BENCH``), kept in ``sys.modules`` as ``bench.reference.<name>``
    so that its relative imports resolve."""
    from bench import harness
    directory = harness.BENCH / "reference"
    path = (directory / f"{name}.py").resolve()
    qual = f"{__name__}.{name}"
    mod = sys.modules.get(qual)
    if mod is None or Path(mod.__file__).resolve() != path:
        if name not in known(directory):
            raise SystemExit(f"no reference model {name!r}; known: "
                             f"{known(directory)}")
        spec = importlib.util.spec_from_file_location(qual, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[qual] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            sys.modules.pop(qual, None)
            raise
    if not callable(getattr(mod, "token_nll", None)):
        raise SystemExit(f"bench/reference/{name}.py defines no token_nll; "
                         f"reference models: {known(directory)}")
    return mod


def known(directory: Path) -> list:
    return sorted(p.stem for p in directory.glob("*.py")
                  if p.stem not in HELPERS and not p.stem.startswith("_"))
