"""BENCHMARK.json and the files it names agree: every configuration,
traffic mix and per-layer metric is a file of its own, found by name."""
import re

import pytest

from bench import cells

BENCH = cells.load_json(cells.BENCHMARK_FILE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_files():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for c in BENCH["configs"]:
        cfg = cells.load_json(cells.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert all(k in cfg for k in c["reduced"])
    for m in BENCH["per_layer"]:
        assert callable(cells.reader(m["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_resolves_and_reports_enough(cell):
    c = cells.find_cell(cell)
    loop = cells.loop_class(c.traffic["kind"])
    assert all(callable(getattr(loop, step))
               for step in ("setup", "window", "check", "plant"))
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    moved = {m["moves"] for m in c.per_layer}
    assert moved <= e2e


def test_end_to_end_bounds_and_sources():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("kind", ["no_such_kind", "../loops", "one-shot"])
def test_a_kind_without_its_file_is_refused(kind):
    with pytest.raises(KeyError, match="no traffic loop"):
        cells.loop_class(kind)
