"""``BENCHMARK.json`` and ``pending/`` hold together: every name in
them resolves to a file, every list names cells, every per-layer
metric moves an end-to-end metric its cells report.  One case per
cell, per metric and per pending file, so the count rises with the
manifest.  No JAX, no chip, seconds.

    python3 -m pytest benchmark/tests/test_manifest.py -q
"""

import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import run as harness  # noqa: E402

MANIFEST = harness.load_json(harness.ROOT, "BENCHMARK.json")
PENDING = sorted(glob.glob(os.path.join(harness.HERE, "pending", "*.json")))
CELLS = [w["name"] for w in MANIFEST["workloads"]]
GROUPS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}
METRICS = [(g, m["name"]) for g in GROUPS for m in MANIFEST[g]]


def reports(manifest, name, group):
    """The names of ``group`` that ``run.py`` puts in the cell's line."""
    cell = harness.Cell(manifest, name, 1, "toy")
    return {m["name"] for m in cell.metrics(manifest, group)}


def check_cell(manifest, name):
    cell = harness.Cell(manifest, name, 1, "toy")
    assert cell.chips in (1, 4)
    assert set(cell.config["sizes"]) >= {"full", "toy"}
    ref = cell.reference
    for needed in ("make_inputs", "answer", "control_answer", "compare",
                   "LIMITS", "min_bytes"):
        assert hasattr(ref, needed), needed
    assert hasattr(harness.load("drivers", cell.traffic["driver"]),
                   "Driver")
    end_to_end = reports(manifest, name, "end_to_end")
    assert "setup_s" in end_to_end and len(end_to_end) >= 2
    assert reports(manifest, name, "per_layer")


def check_metric(manifest, group, name):
    entry = [m for m in manifest[group] if m["name"] == name]
    assert len(entry) == 1, "one entry of that name"
    entry = entry[0]
    assert callable(harness.load(GROUPS[group], name).read)
    cells = [w["name"] for w in manifest["workloads"]]
    listed = entry.get("workloads", cells)
    assert listed and set(listed) <= set(cells), listed
    if group == "end_to_end":
        assert 0 < entry["bound"] <= 0.25
        return
    assert entry["moves"] in {m["name"] for m in manifest["end_to_end"]}
    for cell in listed:
        assert entry["moves"] in reports(manifest, cell, "end_to_end"), (
            "%s lists %s, which does not report %s"
            % (name, cell, entry["moves"]))


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads(name):
    check_cell(MANIFEST, name)


@pytest.mark.parametrize("group,name", METRICS)
def test_metric_resolves_and_moves_what_its_cells_report(group, name):
    check_metric(MANIFEST, group, name)


@pytest.mark.parametrize("path", PENDING, ids=os.path.basename)
def test_pending_file_names_files_that_exist(path):
    """A pending file, copied into the manifest as ``with_pending``
    does, passes what the manifest itself passes."""
    entries = harness.load_json(path)
    assert entries["what"]
    for cell in [w["name"] for w in entries["workloads"]]:
        assert cell not in CELLS, "in BENCHMARK.json and pending/ at once"
        assert os.path.basename(path) == cell + ".json"
        merged = harness.with_pending(MANIFEST, cell)
        for c in entries.get("configs", []):
            assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        check_cell(merged, cell)
        for group in GROUPS:
            for m in entries.get(group, []):
                check_metric(merged, group, m["name"])


def test_every_configuration_keeps_a_cell():
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == {c["name"] for c in MANIFEST["configs"]}
