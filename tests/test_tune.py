"""Autotuner table semantics, donation safety, and XLA_FLAGS merging.

The tuned-table contract (ISSUE 8): explicit kwarg > TuneConfig field >
table cell > kernel default; a missing/corrupt/stale table or an unknown
backend falls back to today's defaults bit-exactly; buffer donation on
the sweep hot path changes buffer lifetime, never results.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import tune as KT
from repro.kernels.gram import gram as GK
from repro.kernels.gram import ops as gram_ops
from repro.kernels.qent import qent as QK
from repro.kernels.qent import ops as qent_ops
from repro.launch import xla_flags as XF


@pytest.fixture()
def tuned_dir(tmp_path, monkeypatch):
    """Point the table loader at a scratch dir for the test, then
    restore the checked-in tables."""
    monkeypatch.setenv("REPRO_TUNED_DIR", str(tmp_path))
    KT.invalidate_table_cache()
    yield tmp_path
    KT.invalidate_table_cache()


def _write(path, obj):
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)
    KT.invalidate_table_cache()


# ------------------------------------------------------------- table I/O
def test_table_roundtrip(tuned_dir):
    table = {"schema_version": KT.SCHEMA_VERSION, "backend": "testbe",
             "cells": {KT.gram_key(256, 256): {"bn": 256, "bk": 128}}}
    KT.save_table(table, str(tuned_dir / "testbe.json"))
    got = KT.load_table("testbe")
    assert got == table
    assert KT.gram_blocks(256, 256, KT.TuneConfig(backend="testbe")) \
        == (256, 128)


def test_missing_corrupt_and_stale_tables_fall_back(tuned_dir):
    assert KT.load_table("nosuch") is None

    _write(tuned_dir / "corrupt.json", "{not json")
    with pytest.warns(UserWarning, match="unreadable"):
        assert KT.load_table("corrupt") is None

    _write(tuned_dir / "stale.json",
           {"schema_version": KT.SCHEMA_VERSION + 1, "cells": {}})
    with pytest.warns(UserWarning, match="schema_version"):
        assert KT.load_table("stale") is None

    # every fallback resolves to the kernel defaults
    for be in ("nosuch", "corrupt", "stale"):
        t = KT.TuneConfig(backend=be)
        assert KT.gram_blocks(256, 256, t) == (GK.DEFAULT_BN, GK.DEFAULT_BK)
        assert KT.qent_tile(16384, 4096, t) == QK.DEFAULT_TILE


def test_check_table_gate(tuned_dir):
    with pytest.raises(SystemExit, match="missing or stale"):
        KT.check_table("nosuch")
    KT.save_table({"schema_version": KT.SCHEMA_VERSION, "backend": "be",
                   "cells": {}}, str(tuned_dir / "be.json"))
    assert "OK" in KT.check_table("be")


def test_checked_in_cpu_table_loads():
    """The committed baseline must load at the current schema and carry
    at least one gram and one qent cell."""
    KT.invalidate_table_cache()
    table = KT.load_table("cpu")
    assert table is not None, "kernels/tuned/cpu.json missing or stale"
    keys = table["cells"].keys()
    assert any(k.startswith("gram:") for k in keys)
    assert any(k.startswith("qent:") for k in keys)


# ----------------------------------------------------------- precedence
def test_precedence_kwarg_over_config_over_table(tuned_dir):
    KT.save_table(
        {"schema_version": KT.SCHEMA_VERSION, "backend": "testbe",
         "cells": {KT.gram_key(128, 128): {"bn": 512, "bk": 128},
                   KT.qent_key(8192, 512): {"tile": 4096}}},
        str(tuned_dir / "testbe.json"))

    table_only = KT.TuneConfig(backend="testbe")
    assert KT.gram_blocks(128, 128, table_only) == (512, 128)
    assert KT.qent_tile(8192, 512, table_only) == 4096

    # a set TuneConfig field beats the table (per-field)
    cfg = KT.TuneConfig(backend="testbe", gram_bn=64, qent_tile=1024)
    assert KT.gram_blocks(128, 128, cfg) == (64, 128)
    assert KT.qent_tile(8192, 512, cfg) == 1024

    # an explicit kwarg beats everything
    assert KT.gram_blocks(128, 128, cfg, bn=256, bk=64) == (256, 64)
    assert KT.qent_tile(8192, 512, cfg, tile=512) == 512

    # use_table=False skips the table but keeps set fields
    off = KT.TuneConfig(backend="testbe", use_table=False)
    assert KT.gram_blocks(128, 128, off) == (GK.DEFAULT_BN, GK.DEFAULT_BK)
    assert KT.qent_tile(8192, 512, off) == QK.DEFAULT_TILE

    # a miss on the exact cell key falls through to the defaults
    assert KT.gram_blocks(300, 500, table_only) \
        == (GK.DEFAULT_BN, GK.DEFAULT_BK)


def test_untuned_backend_bitequal_to_defaults():
    """An unknown backend (no table) must produce bit-identical outputs
    to explicitly-passed kernel defaults -- the fallback is exact."""
    rng = np.random.default_rng(3)
    x = np.asarray(rng.standard_normal((2, 128, 128)), np.float32)
    nb = KT.TuneConfig(backend="no-such-backend")
    got = np.asarray(gram_ops.gram_batched(x, tune=nb))
    want = np.asarray(
        gram_ops.gram_batched(x, bn=GK.DEFAULT_BN, bk=GK.DEFAULT_BK))
    assert np.array_equal(got, want)

    flat = np.asarray(rng.standard_normal((2, 8192)), np.float32)
    epss = np.asarray([1e-3, 1e-2], np.float32)
    got = np.asarray(qent_ops.quantized_entropy_sweep(flat, epss, 512,
                                                      tune=nb))
    want = np.asarray(qent_ops.quantized_entropy_sweep(
        flat, epss, 512, tile=QK.DEFAULT_TILE))
    assert np.array_equal(got, want)


def test_cpu_table_bitequal_to_defaults():
    """The committed CPU table's cells never change numerics: the tuned
    configuration's output is bitwise the default's (tuner bit filter)."""
    KT.invalidate_table_cache()
    table = KT.load_table("cpu")
    assert table is not None
    for key, cell in table["cells"].items():
        k = 2
        if key.startswith("gram:"):
            _, m, n = cell["shape"]
            x = np.asarray(np.random.default_rng(0)
                           .standard_normal((k, min(m, 256), min(n, 256))),
                           np.float32)
            got = np.asarray(
                gram_ops.gram_batched(x, bn=cell["bn"], bk=cell["bk"]))
            want = np.asarray(gram_ops.gram_batched(
                x, bn=GK.DEFAULT_BN, bk=GK.DEFAULT_BK))
        else:
            _, n, bins, e = cell["shape"]
            x = np.asarray(np.random.default_rng(1)
                           .standard_normal((k, min(n, 8192))), np.float32)
            epss = np.geomspace(1e-3, 1e-1, e).astype(np.float32)
            got = np.asarray(qent_ops.quantized_entropy_sweep(
                x, epss, bins, tile=min(cell["tile"], 8192)))
            want = np.asarray(qent_ops.quantized_entropy_sweep(
                x, epss, bins, tile=QK.DEFAULT_TILE))
        assert np.array_equal(got, want), key


def test_hw_for_rejects_unknown_kind():
    assert KT.hw_for("tpu-v5e")["peak_flops"] == 197e12
    assert KT.hw_for("tpu-v5-lite") is KT.hw_for("tpu-v5e")
    assert KT.hw_for("cpu")["mem_bw"] > 0
    with pytest.raises(ValueError, match="no hardware peaks"):
        KT.hw_for("quantum")


# ------------------------------------------------------------- donation
def test_sweep_padded_donation_bitequal():
    from repro.dist import sweep as DS
    rng = np.random.default_rng(7)
    x = np.asarray(rng.standard_normal((3, 96, 96)), np.float32)
    epss = [1e-3, 1e-2]
    base = np.asarray(DS.sweep_padded(jnp.asarray(x), epss, k_pad=4,
                                      donate=False))
    donated = np.asarray(DS.sweep_padded(jnp.asarray(x), epss, k_pad=4,
                                         donate=True))
    assert np.array_equal(base, donated)
    # numpy inputs are unaffected by donation (only the device upload
    # is donated); the service's staging buffers rely on this
    donated_np = np.asarray(DS.sweep_padded(x, epss, k_pad=4, donate=True))
    assert np.array_equal(base, donated_np)


def test_donated_jit_variant_bitequal():
    """The donated executable is a distinct jit with identical math.
    (XLA may or may not be able to reuse the donated buffer -- the
    sweep's (k, e, 2) output never aliases the (k, m, n) input -- but
    donation must never change results, only buffer lifetime.)"""
    from repro.core import predictors as PRED
    x = jnp.asarray(np.random.default_rng(8)
                    .standard_normal((2, 96, 96)).astype(np.float32))
    epss = jnp.asarray([1e-3, 1e-2], jnp.float32)
    kw = dict(vf=PRED.variance_fraction_for(PRED.PredictorConfig(), 3),
              bins=PRED.PredictorConfig().qent_bins, use_kernels=True,
              tune=None)
    assert PRED._features_sweep_donated is not PRED._features_sweep_traced
    want = np.asarray(PRED._features_sweep_traced(x, epss, **kw))
    got = np.asarray(PRED._features_sweep_donated(x, epss, **kw))
    assert np.array_equal(got, want)


# ------------------------------------------------------------ xla_flags
def test_parse_format_roundtrip():
    s = "--xla_a=1 --xla_bare --xla_b=x=y"
    assert XF.format_flags(XF.parse_flags(s)) == s
    assert XF.parse_flags(s)["--xla_bare"] is None
    assert XF.parse_flags(s)["--xla_b"] == "x=y"
    assert XF.parse_flags("") == {}


def test_merge_later_wins_and_dedups():
    merged = XF.merge_flag_strings(
        "--xla_a=1 --xla_b=2", "--xla_a=9 --xla_c=3")
    flags = XF.parse_flags(merged)
    assert flags == {"--xla_b": "2", "--xla_a": "9", "--xla_c": "3"}
    assert merged.count("--xla_a") == 1

    # the dryrun shape: default device count loses to the user's export
    assert XF.merge_flag_strings(
        "--xla_force_host_platform_device_count=512",
        "",
        "--xla_force_host_platform_device_count=8",
    ) == "--xla_force_host_platform_device_count=8"


def test_apply_preset_user_wins():
    env = {"XLA_FLAGS": "--xla_cpu_multi_thread_eigen=false --xla_u=1"}
    out = XF.apply_preset("cpu", env=env)
    flags = XF.parse_flags(out)
    assert flags["--xla_cpu_multi_thread_eigen"] == "false"  # user wins
    assert flags["--xla_u"] == "1"
    assert env["XLA_FLAGS"] == out

    env = {}
    out = XF.apply_preset("gpu", extra={"--xla_extra": None}, env=env)
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in out
    assert "--xla_extra" in out


def test_apply_preset_guards():
    with pytest.raises(ValueError, match="unknown XLA preset"):
        XF.apply_preset("warp-drive", env={})
    assert XF.jax_imported()          # the test process imported jax above
    with pytest.raises(RuntimeError, match="after jax was imported"):
        XF.apply_preset("cpu")        # os.environ + jax imported -> refuse
    assert XF.apply_preset("none", env={}) == ""
