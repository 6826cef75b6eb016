"""Compressor-suite tests: error bounds (the contract), CR sanity, and
scheme behaviour."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import compressors as C
from repro import obs
from repro.compressors.base import error_bound_slack
from repro.compressors.sz import SZ2, quantize_bounded
from repro.data import gaussian, scientific


FIELDS = ["miranda-vx", "scale-u", "hurricane-u", "cesm-cloud"]
# an edge that is a multiple of neither ZFP's 4 nor SZ's 6
EDGE_50 = "hurricane-u-50x50"


@pytest.fixture(scope="module")
def slices():
    out = {f: scientific.field_slices(f, count=1, n=96)[0] for f in FIELDS}
    out[EDGE_50] = scientific.field_slices("hurricane-u", count=1, n=50)[0]
    return out


@pytest.mark.parametrize("name", C.STUDY_2D)
@pytest.mark.parametrize("field", FIELDS + [EDGE_50])
def test_error_bound_held(name, field, slices):
    """The compiled decode of the compiled encode holds the bound."""
    x = slices[field]
    rng = float(jnp.max(x) - jnp.min(x))
    for eps_rel in (1e-2, 1e-3, 1e-4):
        eps = eps_rel * rng
        err = C.get(name).roundtrip_error(x, eps)
        assert err <= eps + error_bound_slack(x), (name, field, eps_rel, err / eps)


def _leaves(tree):
    """(structure, leaves as arrays) of a (codes, aux) or a recon."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, [np.asarray(leaf) for leaf in leaves]


@pytest.mark.parametrize("name", C.STUDY_2D)
def test_compiled_equals_op_by_op(name, slices):
    """Each compressor's compiled encode and decode give the bits of the
    same functions run op by op: codes, aux (tree, shapes, dtypes) and
    the reconstruction."""
    x = slices[EDGE_50]
    comp = C.get(name)
    rng = float(jnp.max(x) - jnp.min(x))
    for eps_rel in (1e-2, 1e-4):
        eps = eps_rel * rng
        codes, aux = comp.encode(x, eps)
        got = _leaves((codes, aux, comp.decode(codes, aux, eps)))
        with jax.disable_jit():
            codes0, aux0 = comp.encode(x, eps)
            want = _leaves((codes0, aux0, comp.decode(codes0, aux0, eps)))
        assert got[0] == want[0], (name, eps_rel)
        for a, b in zip(got[1], want[1]):
            assert a.dtype == b.dtype and np.array_equal(a, b), (name, eps_rel)


@pytest.mark.parametrize("name", C.STUDY_2D)
def test_one_trace_per_shape_whatever_eps(name):
    """Five error bounds, as Python, float64 and float32 scalars, on one
    shape: the encoder is traced (and compiled) once."""
    x = scientific.field_slices("scale-u", count=1, n=64)[0][:29, :35]
    rng = float(jnp.max(x) - jnp.min(x))
    with obs.span("test.mark") as mark:
        pass
    for eps in (1e-4 * rng, np.float64(3e-4 * rng), np.float32(1e-3 * rng),
                3e-3 * rng, 1e-2 * rng):
        C.get(name).encode(x, eps)
    traces = [r for r in obs.records() if r.index > mark.index
              and r.name == "repro.compress.trace"]
    assert [(r.attrs["compressor"], r.attrs["shape"]) for r in traces] == [
        (name, (29, 35))]


@pytest.mark.parametrize("name", C.STUDY_2D)
def test_cr_monotone_in_eps(name, slices):
    """Looser bounds must compress at least as well (within coder noise)."""
    x = slices["miranda-vx"]
    rng = float(jnp.max(x) - jnp.min(x))
    crs = [C.get(name).cr(x, e * rng) for e in (1e-4, 1e-3, 1e-2)]
    assert crs[0] <= crs[1] * 1.05 and crs[1] <= crs[2] * 1.05, crs


def test_smooth_field_compresses_better():
    k = jax.random.PRNGKey(0)
    smooth = gaussian.grf_sample(k, 128, 32.0)
    rough = gaussian.grf_sample(k, 128, 2.0)
    for name in ("sz2", "zfp", "mgard"):
        c = C.get(name)
        assert c.cr(smooth, 1e-3) > c.cr(rough, 1e-3), name


def test_quantize_bounded_property():
    k = jax.random.PRNGKey(1)
    vals = jax.random.normal(k, (4096,)) * 100.0
    for eps in (1e-3, 1e-1, 3.0):
        q = quantize_bounded(vals, eps)
        recon = q.astype(jnp.float32) * (2.0 * eps)
        slack = float(jnp.max(jnp.abs(vals))) * 2.0 ** -23
        assert float(jnp.max(jnp.abs(vals - recon))) <= eps + slack


def test_sz2_dynamic_selection():
    """Planar data routes blocks to regression; locally-correlated but
    non-planar data routes to Lorenzo (on white noise the plane fit
    legitimately wins -- residual sigma vs Lorenzo's 2 sigma)."""
    sz2 = C.get("sz2")
    ii = jnp.arange(96, dtype=jnp.float32)
    planar = ii[:, None] * 0.7 + ii[None, :] * 0.3
    planar = planar + 0.001 * jax.random.normal(jax.random.PRNGKey(2), planar.shape)
    frac_planar = sz2.regression_fraction(planar, 1e-3)
    wavy = gaussian.grf_sample(jax.random.PRNGKey(3), 96, 4.0)
    frac_wavy = sz2.regression_fraction(wavy, 1e-3)
    assert frac_planar > 0.9, frac_planar
    assert frac_wavy < 0.5, frac_wavy


def test_tthresh_rmse_bound():
    vol = scientific.volume("qmcpack", shape=(24, 48, 48))
    t = C.get("tthresh")
    rng = float(jnp.max(vol) - jnp.min(vol))
    eps = 1e-2 * rng
    rmse = t.roundtrip_error(vol, eps)  # TTHRESH's contract is RMSE
    assert rmse <= eps * 1.05, (rmse, eps)
    assert t.cr(vol, eps) > 1.5


def test_lorenzo_3d_roundtrip():
    vol = scientific.volume("miranda-vx", shape=(16, 32, 32))
    c = C.get("sz3-lorenzo")
    eps = 1e-3 * float(jnp.max(vol) - jnp.min(vol))
    assert c.roundtrip_error(vol, eps) <= eps + error_bound_slack(vol)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=2 ** 31 - 1),
       st.sampled_from([1e-3, 1e-2]))
def test_zfp_bound_property(seed, eps_rel):
    x = gaussian.grf_sample(jax.random.PRNGKey(seed), 64, 8.0)
    rng = float(jnp.max(x) - jnp.min(x))
    eps = eps_rel * rng
    err = C.get("zfp").roundtrip_error(x, eps)
    assert err <= eps + error_bound_slack(x)


def test_size_accounting_positive():
    x = scientific.field_slices("nyx-vx", count=1, n=64)[0]
    for name in C.STUDY_2D:
        c = C.get(name)
        codes, aux = c.encode(x, 1e-3)
        assert c.size_bytes(codes, aux, 1e-3) > 0
