"""Full-stack drill with REAL OS processes (the SURVEY §4 "multi-node
without a cluster" recipe, automated): native C++ coordination server +
master process + engine process, driven over HTTP — then a
failure/recovery cycle. This is the CI form of the manual verify recipe
(.claude/skills/verify)."""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest
import requests

REPO = Path(__file__).resolve().parent.parent

ENV = {**os.environ,
       "JAX_PLATFORMS": "cpu",
       "PYTHONPATH": str(REPO)}


def _wait_http(url: str, timeout: float = 30.0):
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            r = requests.get(url, timeout=2)
            return r
        except requests.RequestException as e:
            last = e
            time.sleep(0.2)
    raise TimeoutError(f"{url} never came up: {last}")


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    procs: list[subprocess.Popen] = []
    logdir = tmp_path_factory.mktemp("logs")

    def spawn(name, cmd):
        log = open(logdir / f"{name}.log", "w")
        p = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                             env=ENV, cwd=str(REPO))
        procs.append(p)
        return p

    # Native coordination server on a fixed free-ish port.
    build = subprocess.run(["make", "-C", str(REPO / "csrc")],
                           capture_output=True, text=True)
    if build.returncode != 0:
        pytest.skip(f"native build failed: {build.stderr[-300:]}")
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    coord_port = s.getsockname()[1]
    s.close()
    s2 = socket.socket()
    s2.bind(("127.0.0.1", 0))
    http_port = s2.getsockname()[1]
    s2.close()
    s3 = socket.socket()
    s3.bind(("127.0.0.1", 0))
    rpc_port = s3.getsockname()[1]
    s3.close()

    spawn("coord", [str(REPO / "csrc" / "coordination_server"),
                    "--port", str(coord_port)])
    time.sleep(0.5)
    spawn("master", [sys.executable, "-m", "xllm_service_tpu.master",
                     "--coordination-addr", f"127.0.0.1:{coord_port}",
                     "--host", "127.0.0.1",
                     "--http-port", str(http_port),
                     "--rpc-port", str(rpc_port)])
    engine = spawn("engine", [sys.executable,
                              str(REPO / "examples" / "run_fake_engine.py"),
                              "--coordination-addr",
                              f"127.0.0.1:{coord_port}"])
    base = f"http://127.0.0.1:{http_port}"
    _wait_http(base + "/hello")
    # Readiness flips once the engine registers.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        r = requests.post(base + "/v1/completions", json={
            "model": "fake-model", "prompt": "hi", "max_tokens": 8},
            timeout=10)
        if r.status_code == 200:
            break
        time.sleep(0.3)
    else:
        pytest.fail("cluster never became ready")
    yield base, engine, spawn, coord_port
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


class TestMultiprocessCluster:
    def test_completion_and_stream(self, cluster):
        base, _, _, _ = cluster
        r = requests.post(base + "/v1/completions", json={
            "model": "fake-model", "prompt": "hi", "max_tokens": 16},
            timeout=30)
        assert r.status_code == 200
        assert r.json()["choices"][0]["text"]

        r = requests.post(base + "/v1/chat/completions", json={
            "model": "fake-model", "stream": True,
            "messages": [{"role": "user", "content": "hi"}]},
            stream=True, timeout=30)
        events = [ln for ln in r.iter_lines() if ln.startswith(b"data: ")]
        assert events[-1] == b"data: [DONE]"
        texts = [json.loads(e[6:]) for e in events[:-1]]
        assert any(
            t["choices"][0]["delta"].get("content") for t in texts)

    def test_engine_failure_and_recovery(self, cluster):
        base, engine, spawn, coord_port = cluster
        engine.send_signal(signal.SIGKILL)
        # Lease lapses + probe fails -> SUSPECT -> 503 within ~10s.
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            r = requests.post(base + "/v1/completions", json={
                "model": "fake-model", "prompt": "hi", "max_tokens": 4},
                timeout=10)
            if r.status_code == 503:
                break
            time.sleep(0.3)
        else:
            pytest.fail("dead engine never surfaced as 503")

        # A replacement engine restores service.
        spawn("engine2", [sys.executable,
                          str(REPO / "examples" / "run_fake_engine.py"),
                          "--coordination-addr",
                          f"127.0.0.1:{coord_port}"])
        deadline = time.monotonic() + 45
        while time.monotonic() < deadline:
            r = requests.post(base + "/v1/completions", json={
                "model": "fake-model", "prompt": "hi", "max_tokens": 4},
                timeout=10)
            if r.status_code == 200:
                return
            time.sleep(0.3)
        pytest.fail("replacement engine never restored service")
