"""The manifests that every manifest-level test of this directory is run
against: ``BENCHMARK.json`` as committed, and rehearsals of the additions a
later PR makes.

A later PR may add files and append entries at the END of the manifest's
lists; it may edit no file under ``benchmark/`` or ``tests/benchmark/``. So
an assertion here that finds an entry by its place (``workloads[-1]``,
``per_layer[-3:]``, the last name of a metric's ``workloads`` list) holds
until the next cell and then fails in a PR that may not repair it (PRs 27 and
32 each left one; PRs 26 and 31 met them). The rehearsals are what catches the
next one in the PR that writes it: a copy of the real manifest in a temporary
directory with

- ``a_cell_appended``: one more cell over an existing configuration and a
  traffic file copied under a new name (so the pair is new), its name
  appended to every ``workloads`` list that ``mp.train`` is on;
- ``a_config_and_a_metric_appended``: one more configuration (a copy of the
  flagship's file under a new name), a cell that uses it, and one more
  per-layer metric that only the new cell lists.

**Find entries by name, never by place; new entries go last.** A test takes
the fixture ``manifest_path`` (or ``manifest``, the loaded object) and
resolves files against ``root_of(manifest_path)``, never against the repo's
root.
"""

from __future__ import annotations

import copy
import json
import os
import shutil

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
REAL = os.path.join(ROOT, "BENCHMARK.json")
CASES = ("as_committed", "a_cell_appended", "a_config_and_a_metric_appended")
# a metric file the benchmark keeps for a later cell and the manifest does
# not list yet: the rehearsal's new per-layer metric
SPARE_METRIC = "device_idle_pct.predict"


def root_of(manifest_path: str) -> str:
    return os.path.dirname(os.path.abspath(manifest_path))


def load(manifest_path: str) -> dict:
    with open(manifest_path) as f:
        return json.load(f)


def by_name(entries: list) -> dict:
    return {e["name"]: e for e in entries}


def appended(real: dict, case: str) -> tuple[dict, dict]:
    """-> (the manifest with ``case``'s entries appended, {relative path of
    a file to add: relative path of the file it is a copy of})."""
    m = copy.deepcopy(real)
    base = m["paths"][0]
    flagship = by_name(m["configs"])["mp-flagship"]
    files = {}
    if case == "a_cell_appended":
        cell = {"name": "mp.train-again", "config": "mp-flagship",
                "traffic": "train-again", "chips": 1,
                "why": "rehearsal: a cell appended by a later PR"}
        files[f"{base}/traffic/train-again.json"] = f"{base}/traffic/train.json"
    else:
        file = f"{base}/configs/mp-flagship-again.json"
        files[file] = flagship["file"]
        m["configs"].append({**flagship, "name": "mp-flagship-again",
                             "file": file})
        cell = {"name": "mp-again.train", "config": "mp-flagship-again",
                "traffic": "train", "chips": 1,
                "why": "rehearsal: a configuration appended by a later PR"}
        with open(os.path.join(ROOT, base, "layer_metrics",
                               SPARE_METRIC + ".json")) as f:
            layer = json.load(f)["layer"]
        m["per_layer"].append({
            "name": SPARE_METRIC, "unit": "%", "better": "lower",
            "source": "device_trace", "layer": layer, "moves": "train_rate",
            "workloads": [cell["name"]]})
    m["workloads"].append(cell)
    for metric in m["end_to_end"] + m["per_layer"][:len(real["per_layer"])]:
        if "mp.train" in metric.get("workloads", []):
            metric["workloads"].append(cell["name"])
    return m, files


def write_case(case: str, tmp: str) -> str:
    """The case's manifest under ``tmp`` with the data files a ``run.Cell``
    resolves against a manifest's directory -> its path."""
    real = load(REAL)
    m, files = appended(real, case)
    base = real["paths"][0]
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(ROOT, base, sub),
                        os.path.join(tmp, base, sub))
    for new, old in files.items():
        shutil.copyfile(os.path.join(ROOT, old), os.path.join(tmp, new))
    path = os.path.join(tmp, "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(m, f)
    return path


@pytest.fixture(scope="module", params=CASES)
def manifest_path(request, tmp_path_factory) -> str:
    if request.param == "as_committed":
        return REAL
    return write_case(request.param,
                      str(tmp_path_factory.mktemp(request.param)))


@pytest.fixture(scope="module")
def manifest(manifest_path) -> dict:
    return load(manifest_path)
