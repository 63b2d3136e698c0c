"""The documents point at things that exist.

README.md and the Makefile are read, not run: a `make` target that was
deleted, a module a target still starts, a record file the README still
sends its reader to.  No cluster, no jax.  BASELINE.md is not read here:
it names the REFERENCE's files (`microbenchmark.json`), which are not ours.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(name):
    with open(os.path.join(ROOT, name)) as f:
        return f.read()


def _make_targets():
    return set(re.findall(r"^([A-Za-z][\w-]*):", _read("Makefile"), re.M))


def _readme_make_targets():
    return sorted(set(re.findall(r"`make ([\w-]+)", _read("README.md"))))


def _makefile_modules():
    return sorted(set(re.findall(r"python -m (ray_tpu[\w.]*)",
                                 _read("Makefile"))))


def _readme_root_files():
    return sorted(set(re.findall(r"`([^`/\s]+\.(?:md|jsonl|json))`",
                                 _read("README.md"))))


def test_the_patterns_find_something():
    # a pattern that stopped matching would pass every case below
    assert "sanitize" in _readme_make_targets()
    assert "ray_tpu._private.staticcheck" in _makefile_modules()
    assert "BENCHMARK.json" in _readme_root_files()


@pytest.mark.parametrize("target", _readme_make_targets())
def test_readme_make_target_exists(target):
    assert target in _make_targets()


@pytest.mark.parametrize("module", _makefile_modules())
def test_makefile_module_exists(module):
    path = os.path.join(ROOT, *module.split("."))
    assert os.path.isfile(path + ".py") \
        or os.path.isfile(os.path.join(path, "__main__.py")), module


@pytest.mark.parametrize("name", _readme_root_files())
def test_readme_root_file_exists(name):
    assert os.path.isfile(os.path.join(ROOT, name)), name
