"""What a test under tests/chipbench says of BENCHMARK.json's entries, in
one place (chipbench/README.md, "Adding things"): a test that a PR brings
holds the entries that PR added, each as written but for its `workloads`
list, its place AFTER the entries that stood before it and the cells it was
written for; never the last entry, the number of entries or every cell,
which the next PR moves and cannot repair (`tests/chipbench` lies under
BENCHMARK.json's `paths`). What a later PR may do to an entry that stands is
one rule, `edits_of_what_stood` in test_chipbench_files.py; an entry that a
`benchmark` PR took away on purpose is that rule's to name, so the helpers
here take such an entry for gone and hold the rest."""

from chipbench import harness

# The parts of the first token's time that `ttft_ms.mean` moves, read by the
# standing entries that name no cell: due wherever that metric is judged.
FIRST_TOKEN_PARTS = ("master.schedule_ms", "agent.first_delta_ms",
                     "engine.queue_ms", "engine.prefill_ms")
FIRST_TOKEN_WRAPPERS = ("client.ttft_mean_ms", "engine.queue_ms",
                        "engine.prefill_ms")


def per_layer(bench: dict, name: str) -> dict | None:
    """The per-layer entry `name`; None where the benchmark holds none."""
    found = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(found) <= 1, name
    return found[0] if found else None


def but_its_list(entry: dict) -> dict:
    """The entry as written but for its `workloads` list (a later PR may
    have given a listless entry the list of the cells that were due it)."""
    return {k: v for k, v in entry.items() if k != "workloads"}


def due(bench: dict, name: str, cell: str) -> bool:
    return name in [m["name"] for m in harness.metrics_for(
        bench, "per_layer", cell)]


def stands_after(bench: dict, names: list, stood: list) -> bool:
    """The entries of `stood` that the benchmark still holds come first
    among these, in that order, and those of `names` that it still holds
    follow, in theirs: additions were appended, nothing was moved."""
    order = [m["name"] for m in bench["per_layer"]
             if m["name"] in set(stood) | set(names)]
    return order == ([n for n in stood if n in order]
                     + [n for n in names if n in order])


def first_token_is_read(bench: dict, cell: str, mix: str) -> bool:
    """The first token's time is read in `cell` in one of two ways: judged
    (`ttft_ms.mean` lists the cell, the standing readers of its parts are
    due there through it and the cell has no wrapper entry of its own), or,
    where its runs spread too widely for that, per layer through the cell's
    wrapper entries `<reader>.<mix>` (PERF.md section 2)."""
    judged = "ttft_ms.mean" in [m["name"] for m in harness.metrics_for(
        bench, "end_to_end", cell)]
    parts = [due(bench, n, cell) for n in FIRST_TOKEN_PARTS]
    wrapped = [due(bench, f"{n}.{mix}", cell) for n in FIRST_TOKEN_WRAPPERS]
    return all(parts) and not any(wrapped) if judged else all(wrapped)
