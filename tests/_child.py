"""Shared child-interpreter helpers for multi-device/multi-process tests.

The main pytest process must keep the default single CPU device (jax
locks the device count at first init), so every sharded scenario runs in
a child interpreter with XLA_FLAGS set before importing jax.  Children
exist only to get virtual CPU devices, so they pin ``JAX_PLATFORMS=cpu``:
on a machine with an accelerator they never compete for it.
``run_procs`` extends this to the multi-process fabric: N children join a
``jax.distributed`` coordinator on a free localhost port and run the SAME
body SPMD (``PID``/``NPROCS`` are injected).

Chaos-test extensions: ``kill={pid: after_s}`` SIGKILLs chosen children
on a timer, ``proc_env={pid: {...}}`` injects per-process environment
(e.g. ``REPRO_FAULT_INJECT`` specs for ``repro.dist.faultinject``),
``expect_fail={pid, ...}`` allows chosen children to exit nonzero, and
``external_coordinator=True`` hosts the ``jax.distributed`` coordination
service in its OWN child (so killing any worker -- the leader included
-- leaves the survivors' KV store up).

Port-race hardening: ``free_port()`` closes its probe socket before the
children bind, so a colliding bind is possible.  ``dist_init`` pre-probes
the port and raises a catchable error; the child preamble converts it to
exit code 47, and ``run_procs`` relaunches the whole cohort on a fresh
port (bounded by ``attempts``) instead of failing the test.
"""
import os
import socket
import subprocess
import sys
import textwrap
import threading

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_PORT_RACE_RC = 47


def _env(devices: int, extra: dict = None) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if extra:
        env.update({k: str(v) for k, v in extra.items()})
    return env


def run_child(body: str, devices: int = 8) -> str:
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                          env=_env(devices), capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    return proc.stdout


def free_port() -> int:
    """A free localhost TCP port for a jax.distributed coordinator."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _preamble(pid: int, num_procs: int, addr: str,
              external_coordinator: bool) -> str:
    if external_coordinator:
        return textwrap.dedent(f"""
            import sys
            PID, NPROCS = {pid}, {num_procs}
            from repro.launch import mesh as _M
            _M.dist_init("{addr}", num_processes=NPROCS, process_id=PID,
                         external_coordinator=True, init_timeout_s=60)
        """)
    return textwrap.dedent(f"""
        import sys
        PID, NPROCS = {pid}, {num_procs}
        from repro.launch import mesh as _M
        try:
            _M.dist_init("{addr}", num_processes=NPROCS, process_id=PID,
                         init_timeout_s=60)
        except RuntimeError as _e:
            if "already in use" in str(_e):
                print(_e, file=sys.stderr)
                sys.exit({_PORT_RACE_RC})
            raise
    """)


_COORD_BODY = """
    import sys, time
    from repro.launch import mesh as _M
    try:
        _svc = _M.serve_coordinator("{addr}", {n}, block=False)
    except RuntimeError as _e:
        print("COORD_FAIL", flush=True)
        print(_e, file=sys.stderr)
        sys.exit({rc})
    print("COORD_UP", flush=True)
    while True:
        time.sleep(3600)
"""


def run_procs(body: str, num_procs: int = 2, devices: int = 4,
              timeout: int = 560, kill: dict = None, env: dict = None,
              proc_env: dict = None, expect_fail=(),
              external_coordinator: bool = False, attempts: int = 3) -> list:
    """Run ``body`` SPMD in ``num_procs`` jax.distributed child processes.

    Each child gets ``devices`` virtual CPU devices and a preamble that
    joins the coordinator (``repro.launch.mesh.dist_init`` with gloo CPU
    collectives) before the body runs; the body sees ``PID`` (process
    index) and ``NPROCS``.  Asserts every child exits 0 -- except pids
    named in ``kill`` (SIGKILLed ``kill[pid]`` seconds after spawn) or
    ``expect_fail`` (any exit status accepted) -- and returns the
    per-process stdouts in process order.  ``env`` adds common extra
    environment; ``proc_env[pid]`` adds per-process extras on top.
    ``external_coordinator=True`` hosts the coordination service in a
    dedicated extra child that no worker death can take down.
    """
    code = textwrap.dedent(body)
    expect_fail = set(expect_fail) | set(kill or ())
    last_report = "(no attempt ran)"
    for _ in range(max(1, attempts)):
        port = free_port()
        addr = f"127.0.0.1:{port}"
        coord, procs, timers = None, [], []
        try:
            if external_coordinator:
                coord = subprocess.Popen(
                    [sys.executable, "-c", textwrap.dedent(
                        _COORD_BODY.format(addr=addr, n=num_procs,
                                           rc=_PORT_RACE_RC))],
                    env=_env(devices), stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True)
                if coord.stdout.readline().strip() != "COORD_UP":
                    last_report = "coordinator lost the port race"
                    continue                      # fresh port, new cohort
            for pid in range(num_procs):
                procs.append(subprocess.Popen(
                    [sys.executable, "-c",
                     _preamble(pid, num_procs, addr,
                               external_coordinator) + code],
                    env=_env(devices, {**(env or {}),
                                       **((proc_env or {}).get(pid, {}))}),
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True))
            for pid, after_s in (kill or {}).items():
                t = threading.Timer(float(after_s), procs[pid].kill)
                t.daemon = True
                t.start()
                timers.append(t)
            try:
                outs = [p.communicate(timeout=timeout) for p in procs]
            except subprocess.TimeoutExpired:
                for p in procs:           # a hung collective: reap them all
                    p.kill()
                outs = [p.communicate() for p in procs]
                raise AssertionError(
                    "multi-process children timed out (hung collective?):\n"
                    + "\n".join(f"--- proc {i} ---\n{o}\n{e}"
                                for i, (o, e) in enumerate(outs)))
        finally:
            for t in timers:
                t.cancel()
            if coord is not None:
                coord.kill()
                coord.communicate()
        if any(p.returncode == _PORT_RACE_RC for p in procs):
            last_report = "\n".join(
                f"--- proc {i} (rc={p.returncode}) ---\n{o}\n{e}"
                for i, (p, (o, e)) in enumerate(zip(procs, outs)))
            continue                              # fresh port, new cohort
        report = "\n".join(
            f"--- proc {i} (rc={p.returncode}) ---\n{o}\n{e}"
            for i, (p, (o, e)) in enumerate(zip(procs, outs)))
        assert all(p.returncode == 0 or i in expect_fail
                   for i, p in enumerate(procs)), report
        return [o for o, _ in outs]
    raise AssertionError(
        f"coordinator port kept colliding across {attempts} cohort "
        f"launches:\n{last_report}")
