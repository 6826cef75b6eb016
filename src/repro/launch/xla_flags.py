"""Curated XLA/runtime flag presets + XLA_FLAGS merge semantics.

XLA reads ``XLA_FLAGS`` exactly once, when jax initializes its backends,
so every launcher in this repo used to carry its own ad-hoc docstring
string (``XLA_FLAGS=--xla_force_host_platform_device_count=8 python
...``) and ``launch/dryrun.py`` rebuilt the variable by string
concatenation -- silently clobbering whatever the user had exported.
This module is the one place those strings live now:

* :data:`PRESETS` -- small, curated per-backend flag dicts (the
  ``--xla-preset`` CLI flag on ``sweep_serve``/``serve`` names one);
* :func:`merge` / :func:`merge_flag_strings` -- duplicate-deduped merge
  where LATER sources win, so callers always put the user's exported
  ``XLA_FLAGS`` last and the user wins;
* :func:`apply_preset` -- writes the merged result back to the
  environment, guarded so it can only happen BEFORE jax is imported
  (after backend init the variable is dead weight and silently applying
  nothing is exactly the bug this module exists to prevent).

This module must stay importable without jax: the whole point is to run
before ``import jax``.
"""
from __future__ import annotations

import os
import sys
from typing import Dict, Mapping, Optional

# Flag value ``None`` means a bare flag (no ``=value`` part).
FlagDict = Dict[str, Optional[str]]

# Curated per-backend presets.  Deliberately conservative: nothing here
# may change numerics (the repo's gates assert bit-equality across
# launch configurations), only scheduling/runtime behavior.
PRESETS: Dict[str, FlagDict] = {
    # CPU hosts (CI, dev boxes): thread the Eigen matmuls the interpret
    # -mode kernels and the jnp sweep body lower to.
    "cpu": {
        "--xla_cpu_multi_thread_eigen": "true",
    },
    # GPU: hide collective latency behind compute.
    "gpu": {
        "--xla_gpu_enable_latency_hiding_scheduler": "true",
    },
    # Explicit no-op preset so scripts can pass a preset unconditionally.
    "none": {},
}


def parse_flags(s: str) -> FlagDict:
    """``"--a=1 --b"`` -> ``{"--a": "1", "--b": None}`` (order kept)."""
    out: FlagDict = {}
    for tok in (s or "").split():
        if "=" in tok:
            k, v = tok.split("=", 1)
            out[k] = v
        else:
            out[tok] = None
    return out


def format_flags(flags: Mapping[str, Optional[str]]) -> str:
    """Inverse of :func:`parse_flags`."""
    return " ".join(k if v is None else f"{k}={v}"
                    for k, v in flags.items())


def merge(*sources: Mapping[str, Optional[str]]) -> FlagDict:
    """Merge flag dicts; duplicates deduped, LATER sources win.

    A flag overridden by a later source also takes that source's
    position, so the winning source's relative flag ordering survives
    verbatim (XLA itself resolves duplicates last-wins; after this
    merge there are no duplicates left to resolve).
    """
    out: FlagDict = {}
    for src in sources:
        for k, v in src.items():
            out.pop(k, None)        # re-insert at the winner's position
            out[k] = v
    return out


def merge_flag_strings(*strs: str) -> str:
    """String-level :func:`merge`: later strings win, duplicates deduped."""
    return format_flags(merge(*(parse_flags(s) for s in strs)))


def jax_imported() -> bool:
    """True once jax is in ``sys.modules`` -- past that point XLA_FLAGS
    edits no longer reach the backend."""
    return "jax" in sys.modules


def apply_preset(name: Optional[str], *, extra: Optional[FlagDict] = None,
                 env: Optional[Dict[str, str]] = None,
                 force: bool = False) -> str:
    """Merge ``PRESETS[name]`` (then ``extra``, then the user's existing
    ``XLA_FLAGS`` -- the user wins) into ``env['XLA_FLAGS']``.

    Must run before jax is imported: raises ``RuntimeError`` otherwise
    (``force=True`` skips the guard for tests that only inspect the
    produced string).  ``name=None`` applies only ``extra``.  Returns
    the final flag string.
    """
    if name is not None and name not in PRESETS:
        raise ValueError(
            f"unknown XLA preset {name!r}; available: {sorted(PRESETS)}")
    env = os.environ if env is None else env
    if not force and env is os.environ and jax_imported():
        raise RuntimeError(
            "apply_preset() after jax was imported: XLA reads XLA_FLAGS "
            "at backend init, so the preset would silently do nothing. "
            "Apply it before the first jax import (the sweep_serve/serve "
            "CLIs do this for --xla-preset).")
    merged = merge_flag_strings(
        format_flags(PRESETS.get(name or "none", {})),
        format_flags(extra or {}),
        env.get("XLA_FLAGS", ""))
    if merged:
        env["XLA_FLAGS"] = merged
    return merged
