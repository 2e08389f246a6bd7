"""BENCHMARK.json keeps to the benchmark's contract, and every
configuration, cell, traffic mix and per-layer metric it names is found
by that name under bench/."""
import json
import pathlib
import re

import pytest

from bench import generator, harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_configs_are_found_and_unreduced():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"])
        spec = harness.load_config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert spec["name"] == c["name"] and spec["reduced"] == c["reduced"]
        harness.model_config(spec)          # the program's sizes agree


def test_cells_are_found_by_name():
    names = {c["name"] for c in BENCH["configs"]}
    seen = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and LINE.match(w["why"])
        assert w["chips"] == 1 and w["config"] in names
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        cell = harness.load_cell(w["name"])
        assert (cell["config"], cell["traffic"]) == (w["config"],
                                                     w["traffic"])
        mix = generator.load_mix(w["traffic"])
        generator.load_prompt_set(mix["prompts"]["set"])
        assert mix["arrivals"] == "closed" and cell["clients"] >= 1
        # no cell pins a kernel implementation or launch policy
        assert not {"attn_impl", "step_impl", "packed", "policy"} & set(cell)
    assert {c["name"] for c in BENCH["configs"]} == {
        w["config"] for w in BENCH["workloads"]}


def test_metrics():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert e2e == {"images_per_s", "latency_p50_s", "latency_p90_s",
                   "setup_s"}
    for m in BENCH["end_to_end"]:
        assert set(m) == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["source"] == "host_clock"
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e and LINE.match(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        assert callable(harness.load_module("metrics", m["name"]).read)
        layers.setdefault(m["layer"], []).append(m["name"])
    assert len(BENCH["per_layer"]) == 8


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_reads_nothing_from_an_empty_window(name):
    from bench.trace_reduce import Reduced
    from repro.serving.telemetry import Tracer
    ctx = {"counts": {"nfe": 0.0, "completed": 0.0, "pack_rows": 0.0,
                      "pack_pad_rows": 0.0},
           "trace": Reduced((0.0, 0.0), 0, 0.0, {}, {}, {}, []),
           "tracer": Tracer(), "window": (0.0, 0.0), "compiles": [],
           "device_kind": "cpu", "row_eval_flops": 1.0}
    value = harness.load_module("metrics", name).read(ctx)
    assert value is None or (name == "device.compiles_in_window"
                             and value == 0)
