"""Shared benchmark utilities: timing, CSV emission, cached field data."""
from __future__ import annotations

import functools
import json
import os
import time
from typing import Callable, Dict

import numpy as np
import jax
import jax.numpy as jnp

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
os.makedirs(RESULTS_DIR, exist_ok=True)

_ROWS = []


def emit(name: str, us_per_call: float, derived: str):
    row = f"{name},{us_per_call:.1f},{derived}"
    _ROWS.append(row)
    print(row, flush=True)


def rows():
    return list(_ROWS)


def time_fn(fn: Callable, *args, warmup: int = 1, iters: int = 5,
            **kwargs) -> float:
    """Compile-excluded median seconds per call (kernels.tune.time_fn).

    ``jax.block_until_ready`` on the full result pytree both in warmup
    (so compile time never leaks into the measurement) and per iter.
    """
    from repro.kernels import tune as _tune
    return _tune.time_fn(fn, *args, warmup=warmup, iters=iters, **kwargs)


def timeit(fn: Callable, *args, warmup: int = 1, iters: int = 3) -> float:
    """Median wall-time per call in microseconds (post-warmup)."""
    return time_fn(fn, *args, warmup=warmup, iters=iters) * 1e6


@functools.lru_cache(maxsize=32)
def field_slices_cached(name: str, count: int, n: int):
    from repro.data import scientific
    return scientific.field_slices(name, count=count, n=n)


@functools.lru_cache(maxsize=8)
def gaussian_cached(sample_type: int, count: int, n: int):
    from repro.data import gaussian
    return gaussian.sample_batch(sample_type, count=count, n=n)


@functools.lru_cache(maxsize=512)
def cr_cached(comp: str, field: str, count: int, n: int, eps: float,
              idx: int) -> float:
    from repro import compressors as C
    s = field_slices_cached(field, count, n)[idx]
    return C.get(comp).cr(s, eps)


def crs_for(comp: str, field: str, count: int, n: int, eps: float):
    return np.asarray([cr_cached(comp, field, count, n, eps, i)
                       for i in range(count)])


def free_port() -> int:
    """A free localhost TCP port (jax.distributed coordinator)."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_child_module(module: str, args, num_devices: int):
    """Start ``python -m module *args`` detached, with ``num_devices``
    virtual CPU devices (jax locks the device count at first init, so
    multi-device configurations cannot run in the parent).  The child is
    pinned to the CPU: it exists only for virtual devices, and must never
    compete with the parent for an accelerator.  Combine with
    :func:`wait_children`; multi-process fabrics spawn one child per
    process against a :func:`free_port` coordinator."""
    import subprocess
    import sys
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={num_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.path.dirname(os.path.dirname(__file__)),
         env.get("PYTHONPATH", "")])
    return subprocess.Popen(
        [sys.executable, "-m", module, *map(str, args)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def wait_children(procs, timeout: int = 560) -> list:
    """Wait for :func:`spawn_child_module` children; on timeout every
    child is reaped (a hung collective must not leak processes).
    Asserts zero exits and returns the per-child (stdout, stderr)."""
    import subprocess
    try:
        outs = [p.communicate(timeout=timeout) for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        outs = [p.communicate() for p in procs]
        raise AssertionError("children timed out (hung collective?):\n" +
                             "\n".join(o + "\n" + e for o, e in outs))
    assert all(p.returncode == 0 for p in procs), "\n".join(
        f"rc={p.returncode}\n{o}\n{e}"
        for p, (o, e) in zip(procs, outs))
    return outs


def run_child_module(module: str, args, num_devices: int,
                     timeout: int = 560):
    """Run ``python -m module *args`` in one child interpreter (see
    :func:`spawn_child_module`); asserts a zero exit and returns the
    child's (stdout, stderr)."""
    proc = spawn_child_module(module, args, num_devices)
    return wait_children([proc], timeout=timeout)[0]


def save_json(name: str, obj):
    with open(os.path.join(RESULTS_DIR, name + ".json"), "w") as f:
        json.dump(obj, f, indent=1, default=str)
