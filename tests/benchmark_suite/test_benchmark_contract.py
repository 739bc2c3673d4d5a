"""BENCHMARK.json against the contract's name, unit and size rules, and
against the files it names: every cell's configuration, traffic mix, runner
and metrics resolve."""

import json
import os
import re

import pytest

from benchmarks import common

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def _load(kind, name):
    with open(os.path.join(REPO, "benchmarks", kind, f"{name}.json")) as f:
        return json.load(f)


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["command"]) <= 32 and all(map(_line, BENCH["command"]))
    assert BENCH["paths"] == ["benchmarks", "tests/benchmark_suite"]
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 4)
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and len(CELLS) == len(set(CELLS))
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _line(cfg["source"]) and _line(cfg["why"])
    assert cfg["file"].startswith("benchmarks/") and len(cfg["reduced"]) <= 16
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    body = _load("configs", cfg["name"])
    assert body["source"] == cfg["source"] and body["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|_size|head_dim|per_tok)$", key)
    # every published width, exactly
    assert (body["hidden_size"], body["intermediate_size"], body["head_dim"],
            body["num_attention_heads"], body["num_key_value_heads"]) == (
        4096, 14336, 128, 32, 8)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_and_reports(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert _line(cell["why"]) and cell["chips"] in (1, 4)
    assert cell["config"] in {c["name"] for c in BENCH["configs"]}
    mix = _load("traffic", cell["traffic"])
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "runners", f"{mix['runner']}.py"))
    assert mix["runner"] in _load("configs", cell["config"])
    listed = lambda m: "workloads" not in m or cell["name"] in m["workloads"]  # noqa: E731
    e2e = [m["name"] for m in BENCH["end_to_end"] if listed(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(listed(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry_and_reader(metric):
    e2e = metric in BENCH["end_to_end"]
    want = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert want <= set(metric) <= want | {"workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", ()):
        assert cell in CELLS
    if e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert _line(metric["layer"])
        moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
        for cell in metric.get("workloads", CELLS):
            assert "workloads" not in moved or cell in moved["workloads"]
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"
    spec = common.metric_spec(REPO, metric["name"])
    assert os.path.exists(os.path.join(
        REPO, "benchmarks", "readers", f"{spec['reader']}.py"))
    # unit, layer, ``moves`` and cells are the manifest's alone: the
    # metric's file holds its reader, the reader's arguments and a line on
    # what is read, so a quantity split by what it moves needs one file
    assert {"reader", "args"} <= set(spec) <= {"what", "reader", "args"}


def test_every_metric_file_is_named_by_the_manifest():
    have = {f[:-5] for f in os.listdir(os.path.join(REPO, "benchmarks", "metrics"))}
    named = {m["name"] for m in METRICS}
    assert have <= named | {n.rsplit(".", 1)[0] for n in named}


@pytest.mark.parametrize("name,file", [
    ("device_idle.chat", "device_idle"), ("device_idle.longdoc", "device_idle"),
    ("itl_p95_ms", "itl_p95_ms"), ("slice_rate.train", "slice_rate"),
    ("device_idle.v2.chat", "device_idle.v2"), ("nothing.chat", None)])
def test_a_split_metric_finds_the_file_of_its_stem(tmp_path, name, file):
    """``<quantity>.<what it moves>`` is read by ``metrics/<quantity>.json``
    unless a file of its full name is there; a later PR's new split of a
    quantity that is here needs an entry and no file."""
    import shutil

    shutil.copytree(os.path.join(REPO, "benchmarks", "metrics"),
                    tmp_path / "benchmarks" / "metrics")
    (tmp_path / "benchmarks" / "metrics" / "device_idle.v2.json").write_text(
        json.dumps({"reader": "trace_idle", "args": {"v": 2}}))
    if file is None:
        with pytest.raises(SystemExit):
            common.metric_spec(str(tmp_path), name)
        return
    with open(tmp_path / "benchmarks" / "metrics" / f"{file}.json") as f:
        assert common.metric_spec(str(tmp_path), name) == json.load(f)


def test_suite_directory_cannot_shadow_the_package():
    here = os.path.dirname(os.path.abspath(__file__))
    assert not os.path.exists(os.path.join(here, "__init__.py"))
    others = set(os.listdir(os.path.dirname(here)))
    assert not {f for f in os.listdir(here) if f.startswith("test_")} & others


@pytest.mark.parametrize("name,ok", [
    ("mistral-7b.chat", True), ("decode_roofline.chat", True), ("_x", True),
    ("a" * 64, True), ("a" * 65, False), ("tokens per s", False),
    ("a/b", False), ("a,b", False), (".hidden", False), ("\u03bcs", False)])
def test_the_name_rule(name, ok):
    assert bool(NAME.match(name)) is ok


@pytest.mark.parametrize("unit,ok", [
    ("ms", True), ("tokens/s", True), ("%", True), ("s", True),
    ("tokens per second", False), ("", False), ("\u03bcs", False),
    ("x" * 17, False)])
def test_the_unit_rule(unit, ok):
    assert bool(UNIT.match(unit)) is ok


def test_files_under_paths_are_named_from_name_characters():
    for path in BENCH["paths"]:
        for base, _, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", rel), rel


def test_a_directory_with_the_benchmark_alone_prints_no_result(tmp_path):
    """BENCHMARK.json and the files under ``paths`` without the program:
    the command exits non-zero and prints no result line."""
    import shutil
    import subprocess
    import sys

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    done = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout
