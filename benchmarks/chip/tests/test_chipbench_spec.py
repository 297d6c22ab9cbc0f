"""BENCHMARK.json resolves, by name, to the benchmark's files, and keeps to
the shape its later changes rely on."""
import json
import re

import pytest

from yardstick import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = spec.resolve(w["name"], BENCH)
    assert cell.chips in (1, 4)
    assert cell.config["name"] == w["config"]
    assert callable(cell.driver().run)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(c):
    assert c["file"] == f"benchmarks/chip/configs/{c['name']}.json"
    with open(spec.ROOT / c["file"]) as f:
        body = json.load(f)
    assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_resolves(m):
    assert callable(spec.metric_reader(m["name"]))
    assert m["moves"] in E2E
    assert m["unit"] and m["better"] in ("lower", "higher")
    for cell in m["workloads"]:
        e2e = E2E[m["moves"]]
        assert "workloads" not in e2e or cell in e2e["workloads"]


def test_names_and_bounds():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
