"""What run.py and sweep.py share: resolving a cell's files by name, the
three-process cluster (coordination server, master, chip-holding agent),
the open-loop client, and the reduction from request records to the
end-to-end metrics. Never imports JAX: the processes it starts need the chip.
"""

from __future__ import annotations

import asyncio
import base64
import dataclasses
import importlib.util
import json
import os
import queue
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

from chipbench import loadgen, stats

HARNESS = Path(__file__).resolve().parent
ROOT = HARNESS.parent
MODEL_ID = "chipbench"
BOOT_TIMEOUT_S = 1100        # a first run compiles; the contract allows 1200
REQUEST_TIMEOUT_S = 120
DRAIN_S = 40                 # after the window: time for due requests to end
TRACE_S = 4.0                # traced part of a --trace 1 window
ALPHABET = ("ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz"
            "0123456789-_")
TOKEN_CHARS = 3              # 64**3 = 262144 ids
AGENT_SCRIPT = HARNESS / "agent_main.py"   # a test may put a broken one here


class Failure(RuntimeError):
    """The run cannot give a result (no chip, a child died, a file is
    missing): exit non-zero, print no result line."""


LOG_FILE: Path | None = None   # run.py points this at the cell's output dir


def say(**kw) -> None:
    """One JSON line on stdout, and in the run's own file: what comes back
    from a machine is the end of its output only."""
    line = json.dumps(kw)
    print(line, flush=True)
    if LOG_FILE is not None:
        with open(LOG_FILE, "a") as f:
            f.write(line + "\n")


# ------------------------------------------------------------------ files
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_dir: Path
    hf: dict
    engine: dict
    mix: dict
    rate: float
    limits: dict
    check_requests: int
    check_logprobs: int
    family: "Family"
    decode_paths: dict     # op of `decode_multi` -> the prefix its path must have


def _find(search: list, *parts: str) -> Path:
    for d in search:
        p = Path(d, *parts)
        if p.exists():
            return p
    raise Failure(f"no {'/'.join(parts)} under any of "
                  f"{[str(s) for s in search]}")


def load_bench(bench_file: Path) -> tuple[dict, list]:
    bench = json.loads(Path(bench_file).read_text())
    search = [ROOT / p for p in bench["paths"]]
    return bench, search


def resolve_cell(bench: dict, search: list, name: str) -> Cell:
    """Everything a cell is, found by the names in BENCHMARK.json."""
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise Failure(f"workload {name!r} is not in BENCHMARK.json "
                      f"({[w['name'] for w in bench['workloads']]})")
    cfg = next((c for c in bench["configs"] if c["name"] == wl["config"]),
               None)
    if cfg is None:
        raise Failure(f"cell {name}: config {wl['config']!r} not listed")
    config_file = ROOT / cfg["file"]
    if not config_file.exists():
        raise Failure(f"{config_file} is missing")
    hf = json.loads(config_file.read_text())
    from chipbench.engine_setup import read_engine_json

    engine = read_engine_json(config_file.parent)
    mix = loadgen.read_mix(_find(search, "traffic", wl["traffic"] + ".json"))
    cf = json.loads(_find(search, "cells", name + ".json").read_text())
    if (cf["config"], cf["traffic"]) != (wl["config"], wl["traffic"]):
        raise Failure(f"cells/{name}.json names {cf['config']}/"
                      f"{cf['traffic']}, BENCHMARK.json {wl['config']}/"
                      f"{wl['traffic']}")
    if loadgen.longest_total(mix) > engine["max_seq_len"]:
        raise Failure(f"cell {name}: the mix's longest request "
                      f"({loadgen.longest_total(mix)} tokens) exceeds "
                      f"max_seq_len {engine['max_seq_len']}")
    return Cell(name, wl["chips"], config_file.parent, hf, engine, mix,
                float(cf["rate_per_s"]), cf["limits"],
                int(cf.get("check_requests", 6)),
                int(cf.get("check_logprobs", 0)),
                family_of(search, hf), decode_paths_of(hf, config_file))


def prepare(bench_file: str, workload: str, tag: str = ""):
    """What run.py and sweep.py do before anything starts: name the compile
    cache, read BENCHMARK.json, resolve the cell, make its directories.
    Returns (bench, search, cell, outdir, workdir)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # The program's own rule names this directory when the environment
        # names none; saying it here makes the reference's programs and
        # the weights' share it.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
            ROOT / ".jax_compile_cache")
    path = Path(bench_file)
    bench, search = load_bench(path if path.is_absolute() else ROOT / path)
    cell = resolve_cell(bench, search, workload)
    outdir = ROOT / "chiprun_out" / "chipbench" / (cell.name + tag)
    workdir = ROOT / ".chipbench_work" / (cell.name + tag)
    outdir.mkdir(parents=True, exist_ok=True)
    workdir.mkdir(parents=True, exist_ok=True)
    return bench, search, cell, outdir, workdir


def metrics_for(bench: dict, kind: str, cell: str) -> list[dict]:
    """The metrics of one kind that `cell` reports: those that list it
    under `workloads`, and of those that list nothing every end-to-end
    metric, and every per-layer metric whose `moves` the cell reports."""
    def listed(m):
        return "workloads" not in m or cell in m["workloads"]

    judged = {m["name"] for m in bench["end_to_end"] if listed(m)}
    return [m for m in bench[kind] if listed(m) and (
        kind == "end_to_end" or "workloads" in m or m["moves"] in judged)]


def load_file(path: Path):
    """The module in the file `path`, which is on no import path (a reader,
    a part of a family): run once a process and kept, so that everything
    that asks for a family's weights gets the same module."""
    path = Path(path).resolve()
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in str(path.with_suffix("")))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_reader(search: list, metric: str):
    return load_file(_find(search, "layers", metric + ".py")).read


# ---------------------------------------------------------------- families
DECODE_PATHS = {"paged_attention": "pallas"}   # where config.json names none
FAMILY_PARTS = {"weights": "weights", "reference": "reference",
                "bytes": "bytes_model"}       # part -> the default's module


class Family:
    """Everything of the harness that depends on a model's shape: its
    seeded `weights`, its plain `reference` and its `bytes` counts (the
    contract: README, "A family"). A configuration names its family in
    config.json's `chipbench` group; `families/<name>/<part>.py` is then
    found under BENCHMARK.json's `paths` as readers, mixes and cells are.
    Without a name it is the default: the dense-GQA llama tree of
    weights.py, reference.py and bytes_model.py beside this file.

    A part is loaded when first asked for: weights and reference import
    JAX, which the launcher may not before its children are gone."""

    def __init__(self, search: list, name: str = ""):
        self.search, self.name = [Path(s) for s in search], name

    def __getattr__(self, part: str):
        if part not in FAMILY_PARTS:
            raise AttributeError(part)
        if self.name:
            mod = load_file(_find(self.search, "families", self.name,
                                  part + ".py"))
        else:
            mod = importlib.import_module("chipbench." + FAMILY_PARTS[part])
        setattr(self, part, mod)
        return mod


def family_of(search: list, hf: dict) -> Family:
    """The family that config.json `hf` names, or the default."""
    return Family(search, (hf.get("chipbench") or {}).get("family", ""))


def decode_paths_of(hf: dict, config_file: Path) -> dict:
    """op of `decode_multi` -> the prefix its path must have, as config.json
    `hf` states it, or the default. A configuration states which kernels
    its decode program must run; it cannot state that none must: an empty
    object, or a prefix that every path has, is refused."""
    paths = (hf.get("chipbench") or {}).get("decode_paths", DECODE_PATHS)
    if not (isinstance(paths, dict) and paths and all(
            isinstance(v, str) and v for v in paths.values())):
        raise Failure(f"{config_file}: chipbench.decode_paths {paths!r} "
                      "must name at least one op of decode_multi, each "
                      "with the non-empty prefix its path must have")
    return dict(paths)


# -------------------------------------------------------------- tokenizer
def token_text(i: int) -> str:
    return (ALPHABET[(i >> 12) & 63] + ALPHABET[(i >> 6) & 63]
            + ALPHABET[i & 63])


_INDEX = {c: k for k, c in enumerate(ALPHABET)}


def text_tokens(text: str) -> list[int]:
    ix = _INDEX
    if len(text) % TOKEN_CHARS:
        raise ValueError(f"text of {len(text)} chars is no whole tokens")
    return [(ix[text[i]] << 12) | (ix[text[i + 1]] << 6) | ix[text[i + 2]]
            for i in range(0, len(text), TOKEN_CHARS)]


def write_tokenizer(dirpath: Path, vocab: int) -> Path:
    """A tiktoken-format vocabulary in which token i reads as three
    characters that spell i, so that the served text gives the served ids
    back: the API streams text, and the comparison needs tokens."""
    dirpath.mkdir(parents=True, exist_ok=True)
    with open(dirpath / "vocab.tiktoken", "w") as f:
        for i in range(vocab):
            f.write(base64.b64encode(token_text(i).encode()).decode()
                    + f" {i}\n")
    return dirpath


# ---------------------------------------------------------------- cluster
class Cluster:
    """coordination server + master + agent_main, three OS processes."""

    def __init__(self, cell: Cell, seed: int, outdir: Path, workdir: Path,
                 platform: str):
        from xllm_service_tpu.utils import pick_free_port   # needs no JAX

        self.cell, self.seed, self.platform = cell, seed, platform
        self.outdir, self.workdir = outdir, workdir
        self.procs: list[tuple[str, subprocess.Popen]] = []
        self.coord_port, self.http_port = pick_free_port(), pick_free_port()
        self.rpc_port, self.agent_port = pick_free_port(), pick_free_port()
        self.base = f"http://127.0.0.1:{self.http_port}"
        self.agent_base = f"http://127.0.0.1:{self.agent_port}"
        self.device: dict = {}
        self.started: dict = {}
        self._agent: subprocess.Popen | None = None
        self._replies: queue.Queue = queue.Queue()
        self._cmd_lock = threading.Lock()

    def env(self) -> dict:
        env = dict(os.environ, PYTHONUNBUFFERED="1")
        env["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT)] + [p for p in env.get("PYTHONPATH", "").split(
                os.pathsep) if p])
        if self.platform == "cpu":
            env.update(JAX_PLATFORMS="cpu", XLLM_PALLAS_INTERPRET="1")
        return env

    def log_path(self, proc: str) -> Path:
        return self.outdir / f"{proc}.log"

    def _spawn(self, proc: str, argv: list, **kw) -> subprocess.Popen:
        # One process per chip: a parent that has touched JAX holds the
        # chip, and the agent could not take it.
        if "jax" in sys.modules and self.platform != "cpu":
            raise Failure("the launcher imported jax before its children")
        log = open(self.log_path(proc), "w")
        p = subprocess.Popen([sys.executable, *argv], stderr=log,
                             cwd=str(ROOT), env=self.env(), **kw)
        log.close()
        self.procs.append((proc, p))
        return p

    def start(self) -> None:
        self.outdir.mkdir(parents=True, exist_ok=True)
        tok = write_tokenizer(self.workdir / "tokenizer",
                              self.cell.hf["vocab_size"])
        coord = f"127.0.0.1:{self.coord_port}"
        out = open(self.log_path("coord.out"), "w")
        self._spawn("coord", ["-m", "xllm_service_tpu.coordination.server",
                              "--port", str(self.coord_port)], stdout=out)
        out.close()
        time.sleep(0.5)
        out = open(self.log_path("master.out"), "w")
        self._spawn("master", ["-m", "xllm_service_tpu.master",
                               "--coordination-addr", coord,
                               "--host", "127.0.0.1",
                               "--http-port", str(self.http_port),
                               "--rpc-port", str(self.rpc_port)], stdout=out)
        out.close()
        self._agent = self._spawn(
            "agent", [str(AGENT_SCRIPT),
                      "--config-dir", str(self.cell.config_dir),
                      "--seed", str(self.seed),
                      "--coordination-addr", coord,
                      "--port", str(self.agent_port),
                      "--model-id", MODEL_ID,
                      "--tokenizer-path", str(tok),
                      "--platform", self.platform,
                      *(a for s in self.cell.family.search
                        for a in ("--search", str(s)))],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        threading.Thread(target=self._read_agent, daemon=True).start()

    def _read_agent(self) -> None:
        with open(self.log_path("agent.out"), "w") as log:
            for line in self._agent.stdout:
                log.write(line)
                log.flush()
                if line.startswith("CHIPBENCH "):
                    self._replies.put(json.loads(line[10:]))
        self._replies.put({"event": "eof"})

    def check_alive(self) -> None:
        for proc, p in self.procs:
            if p.poll() is not None:
                raise Failure(f"{proc} exited rc={p.returncode}\n"
                              + tail(self.log_path(proc)))

    def _next(self, want: str, deadline: float) -> dict:
        while True:
            try:
                msg = self._replies.get(timeout=0.5)
            except queue.Empty:
                self.check_alive()
                if time.monotonic() > deadline:
                    raise Failure(f"agent said no {want!r} in time\n"
                                  + tail(self.log_path("agent")))
                continue
            if msg["event"] == want:
                return msg
            if msg["event"] in ("fatal", "eof", "error"):
                raise Failure(f"agent: {msg}\n" + tail(self.log_path("agent")))

    def command(self, cmd: str, want: str, timeout: float = 120,
                **kw) -> dict:
        with self._cmd_lock:
            self._agent.stdin.write(json.dumps({"cmd": cmd, **kw}) + "\n")
            self._agent.stdin.flush()
            return self._next(want, time.monotonic() + timeout)

    def wait_ready(self) -> None:
        """Until the agent has said which devices it holds (they must be
        the platform asked for), has built its engine, and the master
        answers a completion through it."""
        import requests

        deadline = time.monotonic() + BOOT_TIMEOUT_S
        self.device = {k: v for k, v in
                       self._next("devices", deadline).items() if k != "event"}
        if (self.device["platform"] != self.platform
                or self.device["count"] < self.cell.chips):
            raise Failure(f"the agent holds {self.device}; cell "
                          f"{self.cell.name} needs {self.cell.chips} "
                          f"{self.platform} chip(s)")
        self.started = self._next("started", deadline)
        while True:
            self.check_alive()
            if time.monotonic() > deadline:
                raise Failure(f"not ready in {BOOT_TIMEOUT_S}s\n"
                              + tail(self.log_path("agent")))
            try:
                r = requests.post(self.base + "/v1/completions", json={
                    "model": MODEL_ID, "prompt": [300, 301, 302],
                    "max_tokens": 2, "temperature": 0, "ignore_eos": True},
                    timeout=600)
                if r.status_code == 200:
                    return
            except requests.RequestException:
                pass
            time.sleep(0.5)

    def get_json(self, url: str) -> dict:
        import requests

        r = requests.get(url, timeout=30)
        r.raise_for_status()
        return r.json()

    def stop(self) -> None:
        """Stop every child and wait for it: the chip must be free."""
        if self._agent is not None and self._agent.poll() is None:
            try:
                self._agent.stdin.write('{"cmd": "exit"}\n')
                self._agent.stdin.flush()
                self._agent.wait(timeout=30)
            except (OSError, subprocess.TimeoutExpired):
                pass
        for _, p in reversed(self.procs):
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for _, p in reversed(self.procs):
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=20)
        self.procs.clear()


def tail(path: Path, n: int = 3000) -> str:
    try:
        return f"--- tail of {path} ---\n" + path.read_text(
            errors="replace")[-n:]
    except OSError as e:
        return f"--- {path}: {e} ---"


# ----------------------------------------------------------------- client
@dataclasses.dataclass
class Record:
    req: loadgen.Request
    due: float                    # monotonic
    sent: float = 0.0
    chunks: list = dataclasses.field(default_factory=list)   # (t, n_tokens)
    text: str = ""
    lps: list = dataclasses.field(default_factory=list)   # per token {text: logprob}
    done: float = 0.0             # monotonic time of [DONE]; 0 = never
    error: str = ""

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.chunks)

    @property
    def ok(self) -> bool:
        return (not self.error and self.done > 0
                and self.tokens == self.req.max_tokens)


async def _one(session, base: str, rec: Record) -> None:
    import aiohttp

    delay = rec.due - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)
    rec.sent = time.monotonic()
    body = {"model": MODEL_ID, "prompt": rec.req.prompt,
            "max_tokens": rec.req.max_tokens, "temperature": 0,
            "ignore_eos": True, "stream": True}
    if rec.req.logprobs:
        body["logprobs"] = rec.req.logprobs
    try:
        async with session.post(
                base + "/v1/completions", json=body,
                timeout=aiohttp.ClientTimeout(total=REQUEST_TIMEOUT_S)) as r:
            if r.status != 200:
                rec.error = f"status {r.status}: {(await r.text())[:200]}"
                return
            async for raw in r.content:
                if not raw.startswith(b"data:"):
                    continue
                data = raw[5:].strip()
                now = time.monotonic()
                if data == b"[DONE]":
                    rec.done = now
                    break
                ev = json.loads(data)
                if "error" in ev:
                    rec.error = str(ev["error"])[:200]
                    return
                text = "".join(c.get("text") or "" for c in
                               ev.get("choices", ()))
                for c in ev.get("choices", ()):
                    rec.lps += (c.get("logprobs") or {}).get(
                        "top_logprobs", ())
                if text:
                    rec.chunks.append((now, len(text) // TOKEN_CHARS))
                    rec.text += text
    except (aiohttp.ClientError, asyncio.TimeoutError, OSError) as e:
        rec.error = f"{type(e).__name__}: {e}"[:200]


async def drive(base: str, requests_: list, t0: float, timed: list,
                hard_stop: float) -> list[Record]:
    """Send every request at t0 + its due offset, whatever became of the
    earlier ones. `timed`: (offset, coroutine function) pairs to run at
    offsets from t0. Returns when all requests ended or `hard_stop`
    (monotonic) came."""
    import aiohttp

    recs = [Record(r, t0 + r.due) for r in requests_]

    async def at(offset, fn):
        await asyncio.sleep(max(0.0, t0 + offset - time.monotonic()))
        await fn()

    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=conn) as session:
        tasks = [asyncio.create_task(_one(session, base, rec))
                 for rec in recs]
        extra = [asyncio.create_task(at(o, fn)) for o, fn in timed]
        _, pending = await asyncio.wait(
            tasks, timeout=max(0.0, hard_stop - time.monotonic()))
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        await asyncio.gather(*extra)
    for rec in recs:
        if not rec.ok and not rec.error:
            rec.error = (f"unfinished: {rec.tokens}/{rec.req.max_tokens} "
                         "tokens when the run gave up")
    return recs


def send_serially(base: str, reqs: list) -> list[Record]:
    """Set-up traffic (warm-up, prefix fills): one after the other."""
    out = []
    for r in reqs:
        t = time.monotonic()
        out += asyncio.run(drive(base, [dataclasses.replace(r, due=0.0)],
                                 t, [], t + 900))
        if not out[-1].ok:
            raise Failure(f"set-up request {r.rid} failed: {out[-1].error}")
    return out


def dump_records(recs: list[Record], t0: float, path: Path) -> None:
    """The raw timings of a run (seconds from the window's start), so that
    any statistic can be worked out again from what was measured."""
    rows = [{"rid": r.req.rid, "phase": r.req.phase, "due": r.due - t0,
             "sent": r.sent - t0, "done": r.done - t0 if r.done else None,
             "prompt_tokens": len(r.req.prompt),
             "max_tokens": r.req.max_tokens, "error": r.error,
             "chunks": [[t - t0, n] for t, n in r.chunks]} for r in recs]
    path.write_text(json.dumps(rows))


def load_records(path: Path) -> list[Record]:
    """dump_records' inverse, with the window's start at 0."""
    out = []
    for d in json.loads(Path(path).read_text()):
        req = loadgen.Request(d["rid"], d["due"], [0] * d["prompt_tokens"],
                              d["max_tokens"], phase=d["phase"])
        out.append(Record(req, d["due"], d["sent"],
                          [tuple(c) for c in d["chunks"]], "",
                          d["done"] or 0.0, d["error"]))
    return out


# ---------------------------------------------------------------- warm-up
def bucket_for(buckets, n: int) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise Failure(f"no prefill bucket holds {n} tokens ({buckets})")


def warmup_requests(cell: Cell) -> list[loadgen.Request]:
    """One request per prefill bucket the cell's traffic can meet (every
    bucket between its shortest and its longest prefill), and one
    per decode horizon (powers of two up to the configured one): a request
    that has N tokens left to decode alone runs the horizon that is the
    next power of two of N. They ask for log-probabilities where the cell's
    compared requests will. Fixed token ids: warm-up is the same work for
    every seed."""
    eng = cell.engine
    buckets = sorted({
        b for lo, hi in loadgen.prefill_ranges(cell.mix)
        for b in eng["prefill_buckets"]
        if bucket_for(eng["prefill_buckets"], lo) <= b
        <= bucket_for(eng["prefill_buckets"], hi)})
    horizons, h = [], eng["decode_horizon"]
    while h >= 1:
        horizons.append(h)
        h //= 2
    vocab = cell.hf["vocab_size"]
    out = []
    n = max(len(buckets), len(horizons))
    for i in range(n):
        b = buckets[i % len(buckets)]
        h = horizons[i % len(horizons)]
        plen = min(b - 16, eng["max_seq_len"] - 16)
        prompt = [256 + (7919 * (i + 1) + 31 * k) % (vocab - 256)
                  for k in range(plen)]
        out.append(loadgen.Request(f"warm{i}", 0.0, prompt, h + 1,
                                   phase="warm",
                                   logprobs=cell.check_logprobs))
    return out


# ----------------------------------------------------------------- metrics
def end_to_end(recs: list[Record], t0: float, seconds: float) -> dict:
    """The end-to-end numbers, from the client's records of the
    window's requests. A request that failed counts as the worst value of
    each latency: the time from its due moment to when the run gave up."""
    window = [r for r in recs if r.req.phase == "window"]
    give_up = max([t0 + seconds] + [c[0] for r in recs for c in r.chunks]
                  + [r.done for r in recs])
    ttft, tpot, failed = [], [], 0
    for r in window:
        worst = (give_up - r.due) * 1000.0
        if not r.ok:
            failed += 1
            ttft.append(worst)
            tpot.append(worst)
            continue
        ttft.append((r.chunks[0][0] - r.due) * 1000.0)
        pace = stats.tpot_ms(r.chunks[0][0], r.chunks[-1][0], r.tokens)
        if pace is not None:
            tpot.append(pace)
    gaps = stats.pooled_gaps_ms([[t for t, _ in r.chunks] for r in window])
    in_window = sum(n for r in recs for t, n in r.chunks
                    if t0 <= t < t0 + seconds)
    late = [(r.sent - r.due) * 1000.0 for r in window if r.sent]
    return {
        "attempted": len(window), "failed": failed,
        "ttft_ms.mean": sum(ttft) / len(ttft),
        "tpot_ms.p90": stats.percentile(tpot, 90),
        "gap_ms.p95": stats.percentile(gaps, 95) if gaps else None,
        "out_tok_per_s": in_window / seconds,
        # beside them, on the earlier lines only (too few samples in a
        # window for a bound of 10% to hold them: PERF.md section 2)
        "ttft_ms.p50": stats.percentile(ttft, 50),
        "ttft_ms.p90": stats.percentile(ttft, 90),
        "tpot_ms.p50": stats.percentile(tpot, 50),
        "gap_ms.p99": stats.percentile(gaps, 99) if gaps else None,
        "gap_samples": len(gaps),
        "send_late_ms.p99": stats.percentile(late, 99) if late else None,
        "errors": sorted({r.error for r in window if r.error})[:5],
    }


def backlog(recs: list[Record], t: float) -> int:
    """Requests due by `t` (monotonic) and not finished by then."""
    return sum(1 for r in recs if r.due <= t and not (r.ok and r.done <= t))


def backlog_mean(recs: list[Record], a: float, b: float) -> float:
    """Mean backlog over [a, b) (monotonic), read every quarter second."""
    n = max(1, int((b - a) / 0.25))
    return sum(backlog(recs, a + (b - a) * i / n) for i in range(n)) / n


def late_ttft_p50(recs: list[Record], t0: float, seconds: float) -> float:
    """Median time to first token of the window's requests due in its second
    half (a failed one counts as the rest of the window and the drain): it
    stays at the service time under the knee and grows with the queue above
    it."""
    late = [r for r in recs if r.req.phase == "window"
            and r.due >= t0 + seconds / 2]
    if not late:
        raise ValueError("no request due in the window's second half")
    return stats.percentile(
        [(r.chunks[0][0] - r.due) * 1000.0 if r.ok else
         (t0 + seconds + DRAIN_S - r.due) * 1000.0 for r in late], 50)
