"""Every cell of BENCHMARK.json resolves its configuration, mix and
metric readers by name, and the file keeps to the benchmark's rules."""
import json
import os
import re

import pytest

import harness
from tiny import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = harness.resolve(cell, ROOT)
    assert c.config["tp"] in (1, c.chips) and c.chips in (1, 4)
    assert c.mix["kind"] in ("open_loop", "closed")
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end:
        assert callable(harness.load_reader(m["name"], ROOT))
    for m in c.per_layer:
        assert callable(c.readers[m["name"]])
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_names_units_and_keys():
    top = {"command", "paths", "run_seconds", "configs", "workloads",
           "end_to_end", "per_layer"}
    assert set(BENCH) == top
    assert BENCH["paths"] == ["bench"]
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m) - {"workloads"} <= {
            "name", "unit", "better", "bound", "source", "layer", "moves"}
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_configs_files_and_reductions():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        harness.check_cut(body, harness.load_family(body))
        assert body["kv_dtype"] == "bf16"
        # the pool is the slots' worth, and the radix cache leaves an
        # idle engine room to admit any request
        assert body["pool_tokens"] == body["max_batch"] * body["capacity"]
        assert body["prefix_cache_tokens"] <= (
            body["pool_tokens"] - body["capacity"])


def test_cells_fit_the_check():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(CELLS)
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    # the whole check with the full 24 cells fits 43200 seconds
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
