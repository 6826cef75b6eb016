"""Closed loop of one caller over resident field stacks.

Each call is ``features_sweep(stack, ebs, quality=True)`` on one
field's whole stack at its error-bound grid, the library path of the
paper's use cases; the caller waits for each result before the next
call and cycles over the configuration's fields.

The fields are made from the mix's ``data_seed``, the same for every
run, and the run's seed orders each stack's slices: the time of the
batched ``eigvalsh`` depends on the data, so fields made from the run's
seed would change the work from seed to seed.

Mix keys: ``data_seed`` (the fields' seed), ``check_rows`` (rows of the timed results compared with the
reference, drawn from the seed), ``sweep_module`` (the executable's
name in the device trace, for ``sweep_device_ms``).
"""
from __future__ import annotations

import time

import numpy as np

PROBE_CALLS = 3


def setup(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.core import predictors as P
    D = ctx.lib("data")
    c = ctx.cell.config
    stacks, lo, hi = D.make_fields(c["fields"], ctx.cell.mix["data_seed"],
                                   generator=c["generator"],
                                   count=c["slices"], n=c["edge"])
    stacks = D.shuffle_slices(stacks, ctx.seed)
    ebs = [D.eb_grid(c["eps"], lo[i], hi[i], c["n_ebs"], c["eb_top"])
           for i in range(len(stacks))]
    st = {"stacks": stacks, "ebs": ebs, "ebs_dev": [jnp.asarray(e) for e in ebs],
          "cfg": P.PredictorConfig(), "outs": []}
    for i in range(2):                     # the one shape, compiled once
        jax.block_until_ready(_call(st, i % len(stacks)))
    if ctx.trace:
        _make_probes(st, P)
        _run_probes(st)
    return st


def _call(st: dict, f: int):
    from repro.core import predictors as P
    return P.features_sweep(st["stacks"][f], st["ebs_dev"][f], st["cfg"],
                            quality=True)


def _make_probes(st: dict, P) -> None:
    """The program's two sweep stages, each jitted alone under a stable
    name, for the roofline readers."""
    import jax
    cfg = st["cfg"]

    def bench_trunc_probe(x):
        return P.svd_trunc_batch(x, cfg.variance_fraction_2d,
                                 use_kernel=cfg.use_kernels, tune=cfg.tune)

    def bench_qent_probe(x, ebs):
        return P.quantized_entropy_sweep(x, ebs, cfg.qent_bins,
                                         use_kernel=cfg.use_kernels,
                                         tune=cfg.tune)

    st["probes"] = (jax.jit(bench_trunc_probe), jax.jit(bench_qent_probe))


def _run_probes(st: dict) -> None:
    import jax
    trunc, qent = st["probes"]
    x, ebs = st["stacks"][0], st["ebs_dev"][0]
    for _ in range(PROBE_CALLS):
        jax.block_until_ready(trunc(x))
        jax.block_until_ready(qent(x, ebs))


def window(st: dict, seconds: float, ctx) -> dict:
    import jax
    n_fields = len(st["stacks"])
    k, e = st["stacks"][0].shape[0], len(st["ebs"][0])
    outs = st["outs"]
    t0 = time.perf_counter()
    deadline = t0 + seconds
    t = t0
    while t < deadline:
        f = len(outs) % n_fields
        with jax.profiler.TraceAnnotation("bench.launch"):
            out = _call(st, f)
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(out)
        outs.append((f, out))
        t = time.perf_counter()
    return {"attempted": len(outs), "failed": 0,
            "metrics": {"sweep_rate": len(outs) * k * e / (t - t0)},
            "counters": {"launches": len(outs)}}


def probes(st: dict, ctx) -> None:
    import jax
    with jax.profiler.TraceAnnotation("bench.probe"):
        _run_probes(st)


def release(st: dict, ctx) -> list:
    """The sampled rows of the timed results, drawn from the seed, with
    their slices and error bounds, on the host; the device arrays are
    dropped."""
    outs, stacks = st.pop("outs"), st.pop("stacks")
    st.pop("ebs_dev")
    st.pop("probes", None)
    rng = np.random.default_rng(ctx.seed % (1 << 64))
    k = stacks[0].shape[0]
    kept = []
    for _ in range(int(ctx.cell.mix["check_rows"])):
        j, r = int(rng.integers(len(outs))), int(rng.integers(k))
        f, (feats, qual) = outs[j]
        kept.append((np.asarray(stacks[f][r]), st["ebs"][f],
                     np.concatenate([np.asarray(feats[r]),
                                     np.asarray(qual[r])], -1)))
    return kept


def check(kept: list, ctx) -> dict:
    """Worst deviation of the sampled rows from the reference."""
    return ctx.lib("ref/oracle").compare(
        kept, float(ctx.cell.config["variance_fraction"]))


def control(kept: list, ctx) -> dict:
    """The same comparison with the control in the program's place."""
    return ctx.lib("ref/oracle").compare(
        kept, float(ctx.cell.config["variance_fraction"]), control=True)


def fault(kept: list, ctx) -> dict:
    """The same comparison where every answer is another one's: each
    sampled row's output handed to its neighbour."""
    rows = [r[:2] + kept[(i + 1) % len(kept)][2:] for i, r in
            enumerate(kept)]
    return check(rows, ctx)
