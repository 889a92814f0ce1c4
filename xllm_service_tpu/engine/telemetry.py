"""One telemetry record for the engine's step loop.

Everything the loop *decides* between two device calls is counted here,
where the work happens: what admission matched in the prefix cache, how many
sequences and context tokens a decode call served, how many pages the live
sequences hold against the tokens they have written, and which phase of a
loop iteration the host spent its time in. One `EngineTelemetry` per
`InferenceEngine`; the engine pump is the only writer (`confined:engine-pump`
in devtools/ownership.py), so the write path takes no lock. Readers on other
threads (the agent's `/stats`, `/metrics` and heartbeat) copy a dict or a
deque in one C call under the GIL and compute every view on read.

Counters are cumulative and flat (`family/key` for the ones kept by bucket,
horizon, reason or phase); `summarize` nests them for output:

  admission   admissions, prompt_tokens, prefix_hit_tokens (prompt tokens
              whose KV was not recomputed: the block-aligned match after the
              trim that keeps one suffix token, cold-tier onloads included),
              prefix_onload_tokens (the part of them restored from DRAM/SSD),
              prefill_calls/<bucket|chunk>, prefill_chunks and
              prefill_chunk_tokens (standalone `prefill_chunk` calls and
              the tokens they held), prefill_chunked_admissions (prompts
              that were prefilled in chunks: chunks / these + 1 install is
              the programs an admission took),
              prefill_padded_tokens (bucket - suffix),
              prefix_skipped_stateful (admissions whose prompt the prefix
              cache was not asked about, and whose blocks it was not given:
              the model's family keeps a recurrent state per slot, and a
              cached page of keys is no use without the state at its
              boundary), state_bytes_reserved (bytes of those per-slot
              state buffers, set once at start: a gauge, 0 for most
              families),
              admissions_blocked/<no_slot|no_pages> (loop iterations that
              left a waiting request unadmitted), preemptions, cancelled,
              finished, prefill_behind_steps (decode steps dispatched and
              not yet fetched that the chip still had to run, by the
              pump's estimate of the running call's end, when an
              admission's install program was dispatched, summed over
              admissions: what the chip runs before it reaches the
              prefill; 0 where the pump fetches first, a step or so where
              it looks ahead late, a whole call where it looks ahead at
              once)
  seam        look_ahead_late/<hit|late|skipped>: what became of the seam
              behind a decode call long enough to be looked ahead of late
              (engine.look_ahead_plan). hit: the pump waited for the
              moment before the call's predicted end, and the call's
              result was not ready when the next program went onto the
              queue behind it: the chip went straight on. late: it was
              ready (then, or before the moment came): the seam stayed.
              skipped: the pump fetched first (no steady estimate, a
              waiting request the call's tokens might make room for, a
              chunked prefill in flight, every budget ending in the call)
  decode      decode_calls/<horizon|spec>, decode_steps (sum of horizons),
              live_slot_steps (live x horizon), context_token_steps (sum of
              the live sequences' `context_len` at dispatch x horizon: the
              host's count lags the step in flight and does not grow inside
              a call, so this is a LOWER bound of the tokens the attention
              kernel read), sarathi_rides, walk_chunks / walk_run_chunks
              (the chunks of 16 table entries the decode kernel's page
              walk fetches for the live rows at dispatch x horizon, and
              those of them it fetches as one run of adjacent pool pages:
              `ops/page_walk.walk_run_counts`, the kernel's own rule)
  moe         of a family that routes tokens to experts, counted on the
              device by the router itself and brought home in the decode
              call's own result (`moe_landed`): moe_steps (steps of the
              calls that brought counts), moe_tokens_routed (rows that
              held a running request, summed over those steps: a slot that
              stops inside a call leaves at that step),
              moe_experts_touched (experts that got at least one row,
              summed over steps and expert layers: each is read from HBM
              at least once)
  pages       sampled once per decode call, weighted by its horizon so that
              x / decode_steps is a mean per step: pages_reserved_steps
              (pages held by live sequences, a shared prefix page once per
              sequence holding it, as `context_len` counts its tokens).
              Tokens written is context_token_steps: used/reserved =
              context_token_steps / (pages_reserved_steps x page_size).
              (Pages in use and cached are gauges of `/stats` already:
              `kv_usage_perc`, `cached_blocks`.)
  host_s/<p>  seconds of the pump thread by phase (PHASES), exclusive: a
              phase entered inside another stops the outer one's clock, so
              the six sum to the thread's wall time

Every counter is read by something: a per-layer metric of the benchmark
(chipbench/layers/), the agent's `ttft_spans` / heartbeat tables / span
attributes, or an operator's decision that docs/observability.md names
beside it. One that is none of these does not belong here.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Iterable, NamedTuple, Optional

from jax.profiler import TraceAnnotation

from ..devtools import ownership as _ownership

#: Host phases of one loop iteration; each is also a `TraceAnnotation`
#: named `engine.<phase>` (inert without a profiler session).
PHASES = ("admit", "prefill_dispatch", "decode_dispatch", "fetch_wait",
          "emit", "idle")
WINDOW_S = 30.0     # what `recent` covers
SNAPSHOT_S = 1.0    # how often the pump keeps a copy of the counters
RING = 512          # samples kept per ring

_SCALARS = (
    "admissions", "prompt_tokens", "prefix_hit_tokens",
    "prefix_onload_tokens", "prefill_padded_tokens", "preemptions",
    "cancelled", "finished", "prefill_behind_steps", "decode_steps",
    "live_slot_steps", "context_token_steps", "sarathi_rides",
    "pages_reserved_steps", "walk_chunks", "walk_run_chunks",
    "prefix_skipped_stateful", "state_bytes_reserved",
    "prefill_chunks", "prefill_chunk_tokens", "prefill_chunked_admissions",
    "moe_steps", "moe_tokens_routed", "moe_experts_touched")


class AdmissionSample(NamedTuple):
    t: float                    # telemetry clock at the first token
    prompt_len: int
    matched: int                # prefix_hit_tokens of this admission
    bucket: int                 # the install program's suffix bucket
    queue_ms: Optional[float]   # submit -> admission (None: never submitted)
    prefill_ms: float           # admission -> first token fetched


class DecodeSample(NamedTuple):
    t: float                    # telemetry clock when the call was fetched
    horizon: int
    live: int                   # its sequences still live when fetched
    context_tokens: int         # sum of their context_len then
    ms_per_tok: float           # dispatch -> fetched, per step


class _Phase:
    """`with telemetry.phase(name)`: the phase's clock and its trace
    annotation, as one context manager."""

    __slots__ = ("_tel", "_name", "_prev", "_ann")

    def __init__(self, tel: "EngineTelemetry", name: str):
        self._tel, self._name = tel, name

    def __enter__(self) -> None:
        self._ann = TraceAnnotation("engine." + self._name)
        self._ann.__enter__()
        self._prev = self._tel.switch(self._name)

    def __exit__(self, *exc) -> None:
        self._tel.switch(self._prev)
        self._ann.__exit__(*exc)


@_ownership.verify_state
class EngineTelemetry:
    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.counters: dict[str, float] = dict.fromkeys(_SCALARS, 0)
        self.counters.update((f"host_s/{p}", 0.0) for p in PHASES)
        self.admissions: deque[AdmissionSample] = deque(maxlen=RING)
        self.decodes: deque[DecodeSample] = deque(maxlen=RING)
        # (clock, copy of counters), one a SNAPSHOT_S: `recent` is the
        # difference between now and the oldest copy inside the window.
        self._snapshots: deque[tuple[float, dict[str, float]]] = deque(
            maxlen=int(WINDOW_S / SNAPSHOT_S) + 2)
        self._phase = "idle"
        self._t_phase = clock()
        self._t_snapshot = self._t_phase

    # ------------------------------------------------------- the pump's side
    def count_by(self, family: str, key: Any, n: float = 1) -> None:
        k = f"{family}/{key}"
        self.counters[k] = self.counters.get(k, 0) + n

    def phase(self, name: str) -> _Phase:
        return _Phase(self, name)

    def switch(self, name: str) -> str:
        """Charge the time since the last switch to the running phase and
        start `name`; returns the phase that was running."""
        now = self.clock()
        prev = self._phase
        self.counters["host_s/" + prev] += now - self._t_phase
        self._phase, self._t_phase = name, now
        return prev

    def turnaround_s(self) -> float:
        """Seconds the pump has spent so far in the phases that stand
        between a landed decode result and the next decode dispatch (admit,
        decode_dispatch), the running phase brought up to date. The engine
        differences it around a dispatch: its look-ahead rule's input."""
        self.switch(self._phase)
        c = self.counters
        return c["host_s/admit"] + c["host_s/decode_dispatch"]

    def tick(self) -> None:
        """Once per loop iteration: keeps a copy of the counters every
        SNAPSHOT_S (the running phase's time brought up to date first)."""
        if self.clock() - self._t_snapshot >= SNAPSHOT_S:
            self.switch(self._phase)
            self._t_snapshot = self._t_phase
            self._snapshots.append((self._t_phase, dict(self.counters)))

    def admitted(self, prompt_len: int, matched: int, bucket: int,
                 queue_ms: Optional[float],
                 prefill_ms: float) -> AdmissionSample:
        c = self.counters
        c["admissions"] += 1
        c["prompt_tokens"] += prompt_len
        c["prefix_hit_tokens"] += matched
        sample = AdmissionSample(
            self.clock(), prompt_len, matched, bucket, queue_ms, prefill_ms)
        self.admissions.append(sample)
        return sample

    def decode_dispatched(self, key: Any, steps: int, live: int,
                          context_tokens: int, pages_reserved: int,
                          walk_chunks: int, walk_run_chunks: int) -> None:
        c = self.counters
        self.count_by("decode_calls", key)
        c["decode_steps"] += steps
        c["live_slot_steps"] += live * steps
        c["context_token_steps"] += context_tokens * steps
        c["pages_reserved_steps"] += pages_reserved * steps
        c["walk_chunks"] += walk_chunks * steps
        c["walk_run_chunks"] += walk_run_chunks * steps

    def chunk_dispatched(self, tokens: int) -> TraceAnnotation:
        """Counts one standalone `prefill_chunk` call of `tokens` tokens
        and returns its span, `engine.prefill_chunk`, for the dispatch to
        run under (inside the `prefill_dispatch` phase; inert without a
        profiler session)."""
        self.count_by("prefill_calls", "chunk")
        self.counters["prefill_chunks"] += 1
        self.counters["prefill_chunk_tokens"] += tokens
        return TraceAnnotation("engine.prefill_chunk")

    def mark_decode_landed(self, live: int, horizon: int) -> None:
        """One `TraceAnnotation` entered and left at once, just after a
        `decode_multi` call's result was fetched:
        `engine.decode_live.<live>.<horizon>`, the sequences the call was
        dispatched for and its steps. Inert without a profiler session.
        A reader of the trace pairs it with the device execution that
        ended just before it and so prices that very call (bytes a live
        slot, say) from the trace alone, where `live_slot_steps` covers
        another window than the traced seconds."""
        with TraceAnnotation(f"engine.decode_live.{live}.{horizon}"):
            pass

    def moe_landed(self, steps: int, routed: int, touched: int) -> None:
        """The router's counts of one landed decode call, and their marker
        on the trace's host plane, `engine.moe.landed.<touched>.<routed>.
        <steps>`, left as `mark_decode_landed` leaves its own: a reader of
        the trace prices that very call's expert products."""
        c = self.counters
        c["moe_steps"] += steps
        c["moe_tokens_routed"] += routed
        c["moe_experts_touched"] += touched
        with TraceAnnotation(f"engine.moe.landed.{touched}.{routed}.{steps}"):
            pass

    def decode_fetched(self, horizon: int, live: int, context_tokens: int,
                       ms_per_tok: float) -> None:
        self.decodes.append(DecodeSample(
            self.clock(), horizon, live, context_tokens, ms_per_tok))

    # ----------------------------------------------------- any thread's side
    def window(self, now: float) -> tuple[dict, dict, float]:
        """(totals, their change over the last `seconds`, seconds): the
        change is taken against the oldest snapshot inside WINDOW_S, so it
        never reaches further back than that; where the pump kept none
        inside it (a step longer than the window), against the newest."""
        total = dict(self.counters)
        snaps = list(self._snapshots)
        inside = [s for s in snaps if s[0] >= now - WINDOW_S]
        t0, base = inside[0] if inside else snaps[-1] if snaps else (now, total)
        return total, {k: v - base.get(k, 0) for k, v in total.items()}, now - t0


def _nest(flat: dict[str, float]) -> dict[str, Any]:
    out: dict[str, Any] = {}
    for k, v in flat.items():
        family, _, key = k.partition("/")
        if key:
            out.setdefault(family, {})[key] = v
        else:
            out[k] = v
    return out


def _spread(values: list) -> dict[str, float]:
    xs = sorted(values)
    if not xs:
        return {"n": 0}
    return {"n": len(xs), "p50": xs[len(xs) // 2],
            "p90": xs[min(len(xs) - 1, len(xs) * 9 // 10)]}


def summarize(telemetries: Iterable[EngineTelemetry],
              now: Optional[float] = None) -> dict[str, Any]:
    """`/stats`.`engine_trace`: counters summed over an agent's engines as
    `total` (since boot) and `recent` (their change over the last
    `recent.seconds` <= WINDOW_S, with p50/p90 of `queue_ms` and
    `prefill_ms` over the admissions that young)."""
    total: dict[str, float] = {}
    recent: dict[str, float] = {}
    admissions: list[AdmissionSample] = []
    seconds = 0.0
    for tel in telemetries:
        t = tel.clock() if now is None else now
        tot, delta, span = tel.window(t)
        seconds = max(seconds, span)
        for acc, part in ((total, tot), (recent, delta)):
            for k, v in part.items():
                acc[k] = acc.get(k, 0) + v
        admissions += [s for s in list(tel.admissions) if s.t >= t - WINDOW_S]
    return {
        "total": _nest(total),
        "recent": {
            "seconds": seconds, **_nest(recent),
            "queue_ms": _spread([s.queue_ms for s in admissions
                                 if s.queue_ms is not None]),
            "prefill_ms": _spread([s.prefill_ms for s in admissions])},
    }
