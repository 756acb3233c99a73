"""BENCHMARK.json against the files it names: the harness is driven by data,
so every name must lead to a file of its own."""

import importlib
import json
import os
import re

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
#: metric files that no entry names and that stay all the same: tier-1's
#: ``tests/unit/test_serving_ahead.py`` opens this one, and the PR that
#: retired its two entries (PR 59, a ``benchmark`` PR) may not edit a file
#: under ``tests/``. The next PR that may drops that reading; the next
#: ``benchmark`` PR after it deletes the file and this exception.
HELD_FOR_TIER1 = {"ahead_step_share.json"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_name_leads_to_a_file(manifest):
    confs = {c["name"]: c for c in manifest["configs"]}
    for c in confs.values():
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert os.path.isfile(os.path.join(BENCH, "reference", "maps", c["name"] + ".json"))
    for w in manifest["workloads"]:
        assert w["config"] in confs and w["chips"] in (1, 4)
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    used = set()
    for m in manifest["per_layer"]:
        # "<group>.<file>": one reading under several end-to-end metrics
        base = m["name"].rpartition(".")[2]
        used.add(base + ".json")
        with open(os.path.join(BENCH, "layer_metrics", base + ".json")) as f:
            spec = json.load(f)
        assert hasattr(importlib.import_module("readers." + spec["reader"]), "read")
    assert not used & HELD_FOR_TIER1
    assert used | HELD_FOR_TIER1 \
        == set(os.listdir(os.path.join(BENCH, "layer_metrics")))


def test_names_units_and_bounds(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(m["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        # a per-layer metric is reported only where the metric it moves is
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(moved.get("workloads", cells))
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(len(manifest["workloads"]) // 4, 1)


def test_every_cell_reports_what_the_contract_asks(manifest):
    for w in manifest["workloads"]:
        mine = lambda ms: [m for m in ms if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine(manifest["end_to_end"])) >= 2
        assert len(mine(manifest["per_layer"])) >= 1
