"""How the harness finds a file by the name a data file gives it, and
refuses a key that no code reads.

A configuration names its reference (``references/<name>.py``) and a
mix its stages (``stages/<name>.py``): modules found by name, so that a
new one is a new file and no edit.  Any key of a configuration, a mix or
a stage that is neither read by code nor one of the descriptive keys is
an error before anything runs: a knob that nothing reads would run as
if it were absent, and the check would call that ``correct``.
"""
from __future__ import annotations

import importlib
import re

# keys that describe a configuration or a mix for its reader; no code
# reads them
DESCRIPTIVE = {"about", "source", "assumed", "guarantees", "deployment",
               "control", "why"}
_NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def module(kind: str, name: str):
    """``perfbench.<kind>.<name>``; a name that is not a module of that
    directory is refused (``ValueError``)."""
    if not isinstance(name, str) or not _NAME.fullmatch(name):
        raise ValueError(f"{kind}: {name!r} is not a module name")
    full = f"perfbench.{kind}.{name}"
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name not in (full, f"perfbench.{kind}"):
            raise
        raise ValueError(f"no {kind} module named {name!r}") from None


def check_keys(what: str, d: dict, required, optional=()):
    """Refuses (``ValueError``) a dict that lacks a required key or has
    one that is neither read (``required``, ``optional``) nor
    descriptive."""
    missing = sorted(set(required) - set(d))
    if missing:
        raise ValueError(f"{what}: missing key(s) {missing}")
    unknown = sorted(set(d) - set(required) - set(optional) - DESCRIPTIVE)
    if unknown:
        raise ValueError(f"{what}: no code reads the key(s) {unknown}")
