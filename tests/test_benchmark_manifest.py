"""``BENCHMARK.json`` is held to the rules its strings are refused by before
any run (PR 56 was refused on the first with its program unmeasured): every
``why`` and ``source`` is 1 to 200 characters of printable ASCII on one
line; every name matches the pattern; every configuration's file exists and
repeats its ``source`` and ``reduced``; at most a quarter of the cells,
rounded down (and one always), ask for four chips."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

ENTRIES = [(section, e) for section in ("configs", "workloads", "end_to_end",
                                        "per_layer")
           for e in MANIFEST[section]]


def _printable(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and all(32 <= ord(ch) <= 126 for ch in text))


@pytest.mark.parametrize("section, entry", ENTRIES,
                         ids=[f"{s}.{e['name']}" for s, e in ENTRIES])
def test_an_entrys_strings_are_what_the_driver_takes(section, entry):
    assert NAME.fullmatch(entry["name"])
    for key in ("why", "source", "layer"):
        if key in entry and not (section != "configs" and key == "source"):
            assert _printable(entry[key]), (key, entry[key])
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.fullmatch(entry[key])
    for key in entry.get("reduced", []) + entry.get("workloads", []):
        assert NAME.fullmatch(key)
    if "unit" in entry:
        assert UNIT.fullmatch(entry["unit"])
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_names_are_unique_and_the_file_is_small():
    for section in ("configs", "workloads"):
        names = [e["name"] for e in MANIFEST[section]]
        assert len(names) == len(set(names))
    metrics = [e["name"] for s in ("end_to_end", "per_layer")
               for e in MANIFEST[s]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert all(_printable(word) for word in MANIFEST["command"])


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=[c["name"] for c in MANIFEST["configs"]])
def test_a_configurations_file_repeats_its_source_and_cuts(config):
    path = os.path.join(ROOT, config["file"])
    assert config["file"].startswith(tuple(
        p + "/" for p in MANIFEST["paths"])) and os.path.exists(path)
    with open(path) as f:
        held = json.load(f)
    assert held["source"] == config["source"]
    assert sorted(held["reduced"]) == sorted(config["reduced"])
    assert len(config["reduced"]) <= 16
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert config["name"] in used  # a configuration no cell runs is never measured


def test_cells_name_what_exists_and_few_ask_for_four_chips():
    cells = MANIFEST["workloads"]
    configs = {c["name"] for c in MANIFEST["configs"]}
    names = {w["name"] for w in cells}
    for w in cells:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        with open(os.path.join(ROOT, "benchmarks", "cells",
                               w["name"] + ".json")) as f:
            cell = json.load(f)
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)
    for section in ("end_to_end", "per_layer"):
        for m in MANIFEST[section]:
            assert set(m.get("workloads", [])) <= names
    moved = {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(m["moves"] in moved for m in MANIFEST["per_layer"])
