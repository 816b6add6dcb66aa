"""BENCHMARK.json and the files it names: every configuration, mix and
metric is found by name, and the table of peaks refuses a device it
does not hold."""

import json
import os
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load()


def test_every_named_file_is_found(bench):
    for c in bench["configs"]:
        cfg = spec.config(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert cfg["name"] == c["name"]
        assert set(c["reduced"]) == set(cfg["reduced"])
    for w in bench["workloads"]:
        assert spec.workload(bench, w["name"]) is w
        mix = spec.traffic(w["traffic"])
        assert mix["streams"] >= 1 and mix["queries"]
        assert spec.config(w["config"])["chips"] == w["chips"]
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]).read)


def test_names_and_cells(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert set(m["workloads"]) <= cells
    assert {m["name"] for m in bench["end_to_end"]} >= {"setup_s", "queries_per_s"}


def test_unknown_names_are_errors(bench):
    with pytest.raises(spec.SpecError):
        spec.workload(bench, "no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.config("no-such-config")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no_such_metric")


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("cpu")


def test_limits_are_numbers():
    lim = spec.limits()
    assert lim["unanswered"] == 0 and lim["wrong_exact"] == 0
    assert 0 < lim["float_rel_err"] < 1e-3
    with open(os.path.join(spec.BENCH, "limits.json")) as f:
        assert "readings" in json.load(f)
