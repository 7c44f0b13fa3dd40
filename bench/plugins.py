"""The benchmark's files of one architecture or one kernel, found by name.

A configuration file's ``"reference"`` key names its architecture: the
plain reference ``bench/reference/<arch>.py`` and the architecture module
``bench/archs/<arch>.py`` (weight layout, program mapping, model FLOPs
per token). A kernel's device operation name (`bench.trace.op_name`)
names its module ``bench/kernels/<op>.py`` (FLOPs and bytes of one call).
So a configuration of another architecture, or a new kernel, is new
files: no shared module names one.
"""
from __future__ import annotations

import importlib
from pathlib import Path

BENCH = Path(__file__).resolve().parent


class CellError(Exception):
    """The cell, or a file it names, is missing or malformed."""


def names(kind: str) -> frozenset[str]:
    """The names of the modules under ``bench/<kind>/``."""
    return frozenset(p.stem for p in (BENCH / kind).glob("*.py")
                     if not p.stem.startswith("_"))


def module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported; a `CellError` naming the file
    where there is none."""
    path = BENCH / kind / f"{name}.py"
    if not name.isidentifier() or not path.is_file():
        raise CellError(f"{kind} module {path} is missing")
    return importlib.import_module(f"bench.{kind}.{name}")


def arch(cfg: dict):
    """The architecture module of a configuration file's dict."""
    return module("archs", cfg["reference"])


def reference(cfg: dict):
    """The plain reference of a configuration file's dict."""
    return module("reference", cfg["reference"])


def kernel(op: str):
    """The module that counts one call of the kernel ``op``."""
    return module("kernels", op)
