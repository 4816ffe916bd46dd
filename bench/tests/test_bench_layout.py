"""``BENCHMARK.json`` against the files the harness finds by name: every
cell's configuration and mix, and a reader for each of its metrics."""
import json
import os

import pytest

import deploy
import traffic
from tinycell import BENCH, load_run

ROOT = os.path.dirname(BENCH)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    SPEC = json.load(f)
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_are_found_by_name(cell):
    run = load_run()
    entry, cfg, mix = run.load_cell(cell)
    assert cfg["name"] == entry["config"]
    assert cfg["bank_shards"] in (1, entry["chips"])
    traffic.check_mix(mix)
    e2e = run.metric_entries(cell, trace=False)
    layer = run.metric_entries(cell, trace=True)
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert layer
    for m in e2e + layer:
        assert os.path.exists(run.reader_path(m["name"])), m["name"]


def test_a_mix_reader_falls_back_to_its_base_name():
    run = load_run()
    metrics = os.path.join(BENCH, "metrics")
    assert run.reader_path("step_ms.server") == \
        os.path.join(metrics, "step_ms.py")
    assert run.reader_path("latency_p99_ms") == \
        os.path.join(metrics, "latency_p99_ms.py")


def test_every_configuration_is_used_and_cites_its_source():
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        cfg = deploy.load_json("configs", c["name"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert "2502.10089" in c["source"] and "2502.10089" in cfg["source"]
