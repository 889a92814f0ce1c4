"""Multi-host (DCN) backend drill: two OS processes join a
jax.distributed group (Gloo over loopback — the CPU stand-in for DCN),
build ONE global model=2 mesh, and serve two greedy requests through the
lockstep MultihostEngineDriver. The primary's tokens must match a
single-process run of the identical engine/mesh/partitioning exactly.

Hermetic: no TPU, no network beyond 127.0.0.1.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

WORKER = Path(__file__).parent / "multihost_worker.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


#: Exact signature XLA emits when a computation spans processes on a CPU
#: backend built without cross-process collectives (no Gloo support).
_NO_CPU_MULTIPROC_SIG = \
    "Multiprocess computations aren't implemented on the CPU backend"

_PROBE = """
import sys
import jax
import jax.numpy as jnp
jax.distributed.initialize(sys.argv[1], num_processes=2,
                           process_id=int(sys.argv[2]))
out = jax.pmap(lambda x: jax.lax.psum(x, "i"), axis_name="i")(
    jnp.ones((jax.local_device_count(),)))
print("PROBE_OK", float(out[0]))
"""

_cpu_multiprocess_memo = None


def _cpu_multiprocess_skip_reason() -> str:
    """'' when this jax build can run cross-process computations on the
    CPU backend; otherwise the skip reason. Probed ONCE per session: two
    subprocesses join a 2-process jax.distributed group over loopback and
    run one psum — far cheaper than letting the full-stack drills burn
    minutes before hitting the same XLA error. Only the exact capability
    signature skips; any other probe failure lets the real tests run and
    surface the real error."""
    global _cpu_multiprocess_memo
    if _cpu_multiprocess_memo is not None:
        return _cpu_multiprocess_memo
    addr = f"127.0.0.1:{_free_port()}"
    env = _env(local_devices=1)
    try:
        procs = [subprocess.Popen(
            [sys.executable, "-c", _PROBE, addr, str(pid)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env) for pid in (0, 1)]
        outs, sig = [], False
        for p in procs:
            try:
                out, _ = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
            outs.append(out or "")
            sig = sig or _NO_CPU_MULTIPROC_SIG in outs[-1]
        if sig:
            _cpu_multiprocess_memo = (
                "jax CPU backend in this container cannot run "
                "multiprocess computations (no cross-process collectives: "
                f'"{_NO_CPU_MULTIPROC_SIG}")')
        else:
            _cpu_multiprocess_memo = ""
    except OSError:
        _cpu_multiprocess_memo = ""   # can't probe: let the tests decide
    return _cpu_multiprocess_memo


def _require_cpu_multiprocess() -> None:
    reason = _cpu_multiprocess_skip_reason()
    if reason:
        pytest.skip(reason)


def _env(local_devices: int) -> dict:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={local_devices}",
        "PYTHONPATH": str(Path(__file__).parent.parent),
    })
    return env


def _parse_result(stdout: str) -> dict:
    for line in stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no RESULT line in: {stdout[-2000:]}")


class TestMultihostAgentE2E:
    def test_full_stack_with_follower_host(self):
        """coord server + master + a 2-host engine instance (tp=2 over
        the global mesh): the primary host registers/serves HTTP, the
        follower mirrors events in lockstep. A completion must round-trip
        through the whole stack."""
        _require_cpu_multiprocess()
        import time
        import urllib.request

        import tempfile

        coord_port, http_port, rpc_port = (_free_port(), _free_port(),
                                           _free_port())
        mh_port = _free_port()
        procs = []
        logs = []
        logdir = tempfile.mkdtemp(prefix="mh_e2e_")
        env1 = _env(local_devices=1)

        def spawn(cmd, env):
            # Log to files, not PIPE: four chatty children over ~4 min
            # would fill an undrained pipe buffer and deadlock.
            log = open(f"{logdir}/{len(procs)}.log", "w")
            logs.append(log)
            p = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                                 text=True, env=env)
            procs.append(p)
            return p

        try:
            spawn([sys.executable, "-m",
                   "xllm_service_tpu.coordination.server",
                   "--port", str(coord_port)], env1)
            spawn([sys.executable, "-m", "xllm_service_tpu.master",
                   "--coordination-addr", f"127.0.0.1:{coord_port}",
                   "--host", "127.0.0.1", "--http-port", str(http_port),
                   "--rpc-port", str(rpc_port)], env1)
            mh = {"XLLM_MH_COORDINATOR": f"127.0.0.1:{mh_port}",
                  "XLLM_MH_NUM_HOSTS": "2"}
            agent_cmd = [sys.executable, "-m",
                         "xllm_service_tpu.engine.agent",
                         "--coordination-addr", f"127.0.0.1:{coord_port}",
                         "--model-id", "tiny-model",
                         "--model-config", "tiny", "--tp", "2",
                         "--max-seq-len", "128", "--num-pages", "64",
                         "--max-batch-size", "2"]
            spawn(agent_cmd, {**env1, **mh, "XLLM_MH_HOST_ID": "1"})
            spawn(agent_cmd, {**env1, **mh, "XLLM_MH_HOST_ID": "0"})

            body = json.dumps({"model": "tiny-model",
                               "prompt": [5, 7, 9, 11],
                               "max_tokens": 6}).encode()
            deadline = time.monotonic() + 240
            last_err = None
            while time.monotonic() < deadline:
                try:
                    resp = urllib.request.urlopen(urllib.request.Request(
                        f"http://127.0.0.1:{http_port}/v1/completions",
                        data=body,
                        headers={"Content-Type": "application/json"}),
                        timeout=30)
                    out = json.loads(resp.read())
                    assert out["choices"][0]["finish_reason"] == "length"
                    assert out["usage"]["completion_tokens"] == 6
                    return
                except Exception as e:  # noqa: BLE001 — stack warming up
                    last_err = e
                    time.sleep(3)
            tails = []
            for i in range(len(procs)):
                try:
                    with open(f"{logdir}/{i}.log") as f:
                        tails.append(f"--- proc {i}: {f.read()[-800:]}")
                except OSError:
                    pass
            raise AssertionError(
                f"stack never served: {last_err}\n" + "\n".join(tails))
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for p in procs:
                p.wait(timeout=30)
            for log in logs:
                log.close()


class TestMultihostLockstep:
    def test_two_process_serving_matches_single_process(self):
        _require_cpu_multiprocess()
        # Baseline: one process, both mesh devices local.
        base = subprocess.run(
            [sys.executable, str(WORKER), "0", "1", "0"],
            capture_output=True, text=True, timeout=420,
            env=_env(local_devices=2))
        assert base.returncode == 0, base.stderr[-2000:]
        baseline = _parse_result(base.stdout)
        assert set(baseline) == {"a", "b"} and all(baseline.values())

        # Two processes, one mesh device each; same global mesh.
        port = str(_free_port())
        follower = subprocess.Popen(
            [sys.executable, str(WORKER), "1", "2", port],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=_env(local_devices=1))
        try:
            primary = subprocess.run(
                [sys.executable, str(WORKER), "0", "2", port],
                capture_output=True, text=True, timeout=420,
                env=_env(local_devices=1))
            f_out, f_err = follower.communicate(timeout=60)
        finally:
            if follower.poll() is None:
                follower.kill()
        assert primary.returncode == 0, primary.stderr[-2000:]
        assert follower.returncode == 0, f_err[-2000:]
        assert _parse_result(primary.stdout) == baseline
