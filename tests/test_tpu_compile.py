"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode runs on the CPU and accepts blocks the chip's compiler
refuses (lane-misaligned tiles, more scoped VMEM than a kernel gets).
These tests compile each kernel through its ops entry point for a
described v5e chip -- nothing runs -- at the Miranda (384 x 384) and
Hurricane (500 x 500, not 128-aligned) slice widths, and check that the
kernel is in the compiled program as a ``tpu_custom_call``.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler, and it keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import backend
from repro.kernels.gram import ops as gram_ops
from repro.kernels.qent import ops as qent_ops
from repro.kernels.quality import ops as quality_ops

WIDTHS = {"miranda": 384, "hurricane": 500}
K, E = 4, 6


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def lowered_for_tpu(monkeypatch):
    """Kernels lower for the TPU; the persistent compile cache is off
    (a described chip's executables cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(backend, "interpret", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _has_kernel(txt: str, name: str) -> bool:
    return any(name in ln for ln in txt.splitlines()
               if "tpu_custom_call" in ln)


@pytest.mark.parametrize("field", sorted(WIDTHS))
def test_gram_compiles(field, one_chip, lowered_for_tpu):
    n = WIDTHS[field]
    txt = _compiled_text(lambda x: gram_ops.gram_batched(x), one_chip,
                         (K, n, n))
    assert _has_kernel(txt, "gram_batched")


@pytest.mark.parametrize("bins", [4096, 65536])
@pytest.mark.parametrize("field", sorted(WIDTHS))
def test_qent_compiles(field, bins, one_chip, lowered_for_tpu):
    n = WIDTHS[field]
    txt = _compiled_text(
        lambda x, e: qent_ops.quantized_entropy_sweep(x, e, bins),
        one_chip, (K, n * n), (E,))
    assert _has_kernel(txt, "qent_histogram")


@pytest.mark.parametrize("field", sorted(WIDTHS))
def test_quality_compiles(field, one_chip, lowered_for_tpu):
    n = WIDTHS[field]
    txt = _compiled_text(
        lambda x, e: quality_ops.quality_sweep(x, e, use_kernel=True),
        one_chip, (K, n, n), (E,))
    assert _has_kernel(txt, "quality_sse")
