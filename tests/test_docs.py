"""Docs-layer guards: the README/docs the CI docs-smoke job executes
must exist, extract cleanly, and point at real code.

The quickstart is *executed* by the docs-smoke CI job (via
``tools/extract_quickstart.py``); here we keep the cheap invariants in
tier-1 so a README edit cannot silently break the extraction or drift
from the codebase.
"""
import importlib.util
import os
import re

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _read(*parts) -> str:
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


def _load_extractor():
    spec = importlib.util.spec_from_file_location(
        "extract_quickstart",
        os.path.join(ROOT, "tools", "extract_quickstart.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_readme_quickstart_extracts_and_compiles():
    readme = _read("README.md")
    snippet = _load_extractor().extract(readme)
    # the snippet CI executes must at least be valid python that drives
    # the public pipeline API
    compile(snippet, "README.md", "exec")
    for needle in ("field_slices", "CRPredictor.train", "model.predict",
                   "make_sweep_mesh", "features_sweep"):
        assert needle in snippet, f"quickstart lost its {needle} step"


def test_readme_covers_required_sections():
    readme = _read("README.md")
    # architecture map must name every package the map claims to cover
    for pkg in ("core", "kernels", "dist", "serve", "launch",
                "compressors", "data"):
        assert os.path.isdir(os.path.join(ROOT, "src", "repro", pkg)), pkg
        assert f"{pkg}/" in readme, f"architecture map lost {pkg}/"
    # install + tier-1 command from pyproject
    assert 'pip install -e ".[test,zstd]"' in readme
    assert "pytest" in readme
    # benchmark table rows must reference results some benchmark module
    # actually writes (results/ itself is a generated, gitignored dir,
    # so existence-on-disk cannot be the check in a fresh checkout)
    writers = ""
    bench_dir = os.path.join(ROOT, "benchmarks")
    for fn in os.listdir(bench_dir):
        if fn.endswith(".py"):
            writers += _read("benchmarks", fn)
    for ref in re.findall(r"`(BENCH_\w+\.json|bench_\w+\.json|"
                          r"fig\d+_\w+\.json)`", readme):
        assert f'"{ref[:-len(".json")]}"' in writers, \
            f"README benchmark table references {ref}, which no " \
            "benchmark writes via common.save_json"


def test_docs_reference_real_code():
    serving = _read("docs", "serving.md")
    for sym in ("max_batch_slices", "max_wait_ms", "cache_bytes",
                "cache_admit_after", "sweep_padded", "scatter_requests",
                "dist_init", "serve()",
                # servable-method platform vocabulary
                "ServableMethod", "kv_gate", "max_live_batches",
                "min_wait_ms", "adapt_window", "batch_buckets",
                "warmup_spec"):
        assert sym in serving, f"serving.md lost {sym}"
    mapping = _read("docs", "paper_mapping.md")
    svc = _read("src", "repro", "serve", "sweep_service.py")
    for sym in ("quantized_entropy", "svd_trunc", "hosvd_trunc_batch",
                "find_error_bound_for_cr", "best_compressor",
                "bench_3d", "EbGridModel", "ServableMethod",
                "default_registry", "kv_gate",
                # streaming advisor rows (UC1/UC2 at dataset scale)
                "stream_features", "launch.advise"):
        assert sym in mapping, f"paper_mapping.md lost {sym}"
    # the knobs the serving doc teaches must exist on ServiceConfig
    from repro.serve.sweep_service import ServiceConfig
    cfg = ServiceConfig()
    for knob in ("max_batch_slices", "max_wait_ms", "cache_bytes",
                 "cache_admit_after", "max_eps_per_launch",
                 "min_wait_ms", "adapt_window", "max_live_batches",
                 "post_workers"):
        assert hasattr(cfg, knob)
    assert "broadcast_one_to_all" in svc  # the fabric serving.md describes


def test_method_platform_modules_expose_documented_api():
    """The symbols serving.md/paper_mapping.md teach for the method
    layer must exist in the new modules."""
    method = _read("src", "repro", "serve", "method.py")
    for sym in ("class ServableMethod", "def pre_process",
                "def post_process", "def warmup_spec", "batch_buckets",
                "class SweepLauncher", "class Int8CRLauncher",
                "class KVGateMethod", "class QualityLauncher",
                "class QualityMethod", "class FindSettingMethod"):
        assert sym in method, f"method.py lost {sym}"
    registry = _read("src", "repro", "serve", "registry.py")
    for sym in ("def default_registry", "def register",
                "def launcher_id"):
        assert sym in registry, f"registry.py lost {sym}"
    from repro.serve.registry import default_registry
    assert default_registry().names() == (
        "featurize", "find_eb", "best_compressor", "kv_gate", "advise",
        "find_setting", "quality")


def test_streaming_doc_references_real_code():
    """docs/streaming.md must keep teaching the symbols the streaming
    layer actually exports, and the README must link it."""
    doc = _read("docs", "streaming.md")
    for sym in ("DatasetSource", "MemmapSource", "NpzSource",
                "GeneratorSource", "StreamingDigest", "StreamConfig",
                "stream_features", "stream_dataset", "budget_bytes",
                "prefetch", "max_in_flight", "process_local",
                "make_dataset.py", "repro.launch.advise",
                "submit_advise", "harmonic", "BENCH_stream"):
        assert sym in doc, f"streaming.md lost {sym}"
    # the doc's vocabulary must exist in code
    from repro.core import stream as ST
    from repro.data import source as SRC
    for mod, names in ((SRC, ("DatasetSource", "MemmapSource", "NpzSource",
                              "GeneratorSource", "StreamingDigest",
                              "open_dataset", "write_dataset")),
                       (ST, ("StreamConfig", "stream_features",
                             "stream_dataset"))):
        for name in names:
            assert hasattr(mod, name), f"{mod.__name__} lost {name}"
    from repro.serve.sweep_service import SweepService
    assert hasattr(SweepService, "submit_advise")
    assert hasattr(SweepService, "advise")
    assert "docs/streaming.md" in _read("README.md")


def test_quality_doc_references_real_code():
    """docs/quality.md must keep teaching the symbols the quality layer
    actually exports, and the README must link it."""
    doc = _read("docs", "quality.md")
    for sym in ("quality_sweep", "features_sweep", "quality=True",
                "find_setting", "QualityTable", "JointSetting",
                "submit_quality", "submit_find_setting", "--psnr-floor",
                "det_log10", "DEFAULT_TILE", "PSNR_CAP",
                "BENCH_quality.json"):
        assert sym in doc, f"quality.md lost {sym}"
    # the doc's vocabulary must exist in code
    from repro.core import predictors as P
    from repro.core import usecases as UC
    from repro.kernels import quality as Q
    for mod, names in ((P, ("quality_sweep", "features_sweep")),
                       (UC, ("find_setting", "QualityTable",
                             "JointSetting")),
                       (Q, ("quality_sweep", "DEFAULT_TILE", "PSNR_CAP",
                            "NRMSE_CAP"))):
        for name in names:
            assert hasattr(mod, name), f"{mod.__name__} lost {name}"
    from repro.serve.sweep_service import SweepService
    for name in ("submit_quality", "submit_find_setting", "quality",
                 "find_setting"):
        assert hasattr(SweepService, name)
    assert "docs/quality.md" in _read("README.md")
    assert "psnr-floor" in _read("src", "repro", "launch", "advise.py")


def test_performance_doc_references_real_code():
    perf = _read("docs", "performance.md")
    for sym in ("repro.kernels.tune", "TuneConfig", "REPRO_TUNED_DIR",
                "--xla-preset", "bench_tune", "BENCH_tune",
                "measured_stream_bw", "BACKEND_HW", "invalidate_table_cache",
                "apply_preset", "merge_flag_strings", "donate_argnums"):
        assert sym in perf, f"performance.md lost {sym}"
    # the knobs/presets the doc teaches must exist
    from repro.kernels import tune as KT
    from repro.launch import xla_flags as XF
    for name in ("cpu", "gpu", "none"):
        assert name in XF.PRESETS
    assert hasattr(KT.TuneConfig(), "use_table")
    # the committed baseline the doc (and the default load path) relies on
    assert os.path.exists(os.path.join(
        ROOT, "src", "repro", "kernels", "tuned", "cpu.json"))
    # README links the doc
    assert "docs/performance.md" in _read("README.md")


def test_paper_mapping_paths_exist():
    mapping = _read("docs", "paper_mapping.md")
    for path in re.findall(r"`((?:core|kernels|dist|serve|launch|data|"
                           r"compressors)/[\w./]+\.py)`", mapping):
        assert os.path.exists(
            os.path.join(ROOT, "src", "repro", path)), \
            f"paper_mapping.md references missing {path}"
