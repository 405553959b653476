"""The rules of form that the driver holds BENCHMARK.json to before any run
and that ``test_manifest.py`` does not state: a unit's length and letters,
each entry's keys, one line of at most 200 characters for every free text,
the catalog's numbers in a configuration's file.  (PR 31 was refused once for
a unit of 17 characters that nothing here had read.)"""
import json
import os
import re

import pytest

from perfbench import manifest

BENCH = manifest.load_benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_a_unit_is_at_most_16_plain_characters(metric):
    assert re.fullmatch(r"[A-Za-z0-9_/%.-]{1,16}", metric["unit"])
    assert metric["better"] in ("lower", "higher")


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_a_per_layer_metric_has_just_the_contracts_keys(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert _one_line(metric["layer"])
    cells = {w["name"]: w for w in BENCH["workloads"]}
    reports = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}
    for cell in metric.get("workloads", []):
        assert cell in cells
        moved = reports[metric["moves"]]
        assert moved is None or cell in moved


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_a_cell_has_just_the_contracts_keys(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert re.fullmatch(NAME, cell["traffic"]) and cell["chips"] in (1, 4)
    assert _one_line(cell["why"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_has_just_the_contracts_keys(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert _one_line(config["source"]) and _one_line(config["why"])
    assert len(config["reduced"]) <= 16
    assert all(re.fullmatch(NAME, key) for key in config["reduced"])
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert re.fullmatch(r"[A-Za-z0-9_./-]+", config["file"])


@pytest.mark.skipif(not os.path.exists(CATALOG), reason="no catalog here")
@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_catalog_models_file_holds_the_catalogs_numbers(config):
    with open(CATALOG) as fh:
        entries = [json.loads(line) for line in fh if line.strip()]
    entry = [e for e in entries if e.get("source_url") == config["source"]]
    if not entry:
        pytest.skip("not a model of the catalog")
    with open(os.path.join(manifest.ROOT, config["file"])) as fh:
        ours = json.load(fh)
    for key, value in entry[0]["config"].items():
        if key in config["reduced"] or isinstance(value, (str, list)):
            continue
        assert key in ours and ours[key] == value, key


def test_four_chip_cells_and_the_checks_time_fit():
    cells = BENCH["workloads"]
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(1, len(cells) // 4)
    runs = 2 + 14 * len(cells)
    assert runs * (BENCH["run_seconds"] + 60) + 2 * 90 * len(cells) + 1200 \
        <= 43200
