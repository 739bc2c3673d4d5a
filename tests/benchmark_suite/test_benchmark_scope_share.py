"""``readers/scope_share.py`` on an ``.xplane.pb`` written by hand (PR 39):
self times, the innermost name, per-program paths, the exact sum, nothing
without a trace, ``unnamed`` for a program without the vocabulary; and the
entries of ``BENCHMARK.json`` that read it."""

import json
import os
import re

import pytest

from benchmarks import common

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
STEMS = ("prefill_attn_share", "prefill_ffn_share", "prefill_mixer_share",
         "step_attn_share", "step_ffn_share", "step_mixer_share",
         "scope_unnamed_share")
STEP, PREFILL = r"^jit_step(\(|$)", r"^jit_prefill(\(|$)"
SHARED = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
BODY = "jit(step)/while/body/closed_call/"
NS = 1000  # the wire format counts picoseconds


@pytest.fixture(scope="module")
def reader():
    return common.plugin(REPO, "readers", "scope_share")


def _varint(n):
    out = b""
    while True:
        out += bytes([(n & 0x7F) | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, payload):
    if isinstance(payload, int):
        return _varint(number << 3) + _varint(payload)
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _metadata(key, name, path=None):
    body = _field(1, key) + _field(2, name.encode())
    body += _field(5, _field(1, 35) + _field(3, 12345))  # program_id: skipped
    if path is not None:
        body += _field(5, _field(1, 26) + _field(5, path.encode()))
    return _field(4, _field(1, key) + _field(2, body))


def _line(name, events):
    """events: (metadata id, start ns, length ns); each carries a stat, as
    the profiler's do, which the reader skips."""
    body = _field(2, name.encode())
    for key, start, length in events:
        body += _field(4, _field(1, key) + _field(2, start * NS)
                       + _field(3, length * NS)
                       + _field(4, _field(1, 2) + _field(3, length * NS)))
    return _field(3, body)


def _write(tmp_path, named: bool):
    """One device plane: a step run (a ``while`` with three body operations
    and two gaps, an operation before it and two with no path after it, one
    of them a grouped product as the TPU's compiler renames it), a
    prefill run whose first operation has the step's ``fusion.1`` text under
    another scope, and a second step run after the window closes."""
    def path(text):
        return text if named else re.sub(
            r"(tok|blk|moe)_[a-z_]+/", "", text)

    device = (
        _field(1, 7) + _field(2, b"/device:TPU:0")
        + _metadata(1, "jit_step(111)") + _metadata(2, "jit_prefill(222)")
        + _metadata(10, "%while.7 = (s32[], f32[8]{0}) while(%t)",
                    "jit(step)/while")
        + _metadata(11, SHARED, path(BODY + "blk_qkv/dot_general"))
        + _metadata(12, "%fusion.2 = f32[8]{0} fusion(%a)",
                    path(BODY + "blk_attn/blk_kv_write/scatter"))
        + _metadata(13, "%fusion.3 = f32[8]{0} fusion(%b)",
                    path(BODY + "blk_ffn/moe_gmm/dot_general:"))
        + _metadata(14, "%copy.1 = f32[8]{0} copy(%c)")
        + _metadata(16, "%ragged-dot-none.2 = f32[8]{0} custom-call(%f)")
        + _metadata(15, "%gather.1 = f32[8]{0} gather(%e)",
                    path("jit(step)/tok_embed/gather"))
        + _metadata(21, SHARED, path("jit(prefill)/tok_head/dot_general"))
        + _metadata(22, "%fusion.9 = f32[8]{0} fusion(%d)",
                    path("jit(prefill)/blk_attn/exp"))
        + _line("XLA Modules", [(1, 1000, 1000), (2, 3000, 500),
                                (1, 9000, 100)])
        + _line("XLA Ops", [
            (15, 1000, 100), (10, 1100, 800), (11, 1100, 200),
            (12, 1300, 100), (13, 1500, 300), (14, 1900, 50), (16, 1950, 50),
            (21, 3000, 400), (22, 3400, 100), (11, 9000, 100)])
        + _line("Async XLA Ops", [(14, 1000, 5000)]))
    host = (_field(1, 8) + _field(2, b"/host:CPU")
            + _metadata(1, SHARED, "jit(nothing)/of/the/device"))
    trace_dir = tmp_path / ("named" if named else "bare")
    folder = trace_dir / "plugins" / "profile" / "2026_10_01"
    folder.mkdir(parents=True)
    (folder / "vm.xplane.pb").write_bytes(_field(1, device) + _field(1, host))
    window = {"planes": [{"name": "/host:CPU", "lines": [{
        "name": "python3", "events": [["bench.window", 500, 4500]]}]}]}
    return {"trace": window, "trace_dir": str(trace_dir)}


def test_self_times_innermost_name_and_the_exact_sum(reader, tmp_path, capsys):
    result = _write(tmp_path, named=True)
    found = reader.split(os.path.join(
        result["trace_dir"], "plugins", "profile", "2026_10_01",
        "vm.xplane.pb"), 500, 5000)
    runs, scopes, unnamed, by_name = found["jit_step"]
    # the run after the window is not counted; the while is charged its two
    # gaps (100 + 100 ns), not its body; the operation without a path too,
    # but for the grouped product, which is charged where all of them stand
    assert runs == 1
    assert scopes == {"tok_embed": 100 * NS, "blk_qkv": 200 * NS,
                      "blk_kv_write": 100 * NS, "moe_gmm": 350 * NS,
                      "unnamed": 250 * NS}
    assert sum(scopes.values()) == 1000 * NS  # the run's busy device time
    assert {k.split(" ")[0]: v for k, v in unnamed.items()} == {
        "%while.7": 200 * NS, "%copy.1": 50 * NS}
    # what of moe_gmm was found by instruction name and not by path
    assert by_name == {"moe_gmm": 50 * NS}
    assert found["jit_prefill"][3] == {}
    # the same HLO text in the other program stands under that program's path
    assert found["jit_prefill"][:2] == [1, {"tok_head": 400 * NS,
                                            "blk_attn": 100 * NS}]
    share = reader.read(result, {"module": STEP, "scopes": [
        "blk_attn", "blk_kv_write", "mla_prefill", "mla_decode"]})
    assert share == pytest.approx(100 * 100 / 1000)
    assert reader.read(result, {"module": PREFILL, "scopes": [
        "blk_attn"]}) == pytest.approx(20.0)
    both = reader.read(result, {"module": r"^jit_(prefill|step)(\(|$)",
                                "scopes": ["unnamed"]})
    assert both == pytest.approx(100 * 250 / 1500)
    shares = [reader.read(result, {"module": STEP, "scopes": [name]})
              for name in reader.VOCABULARY + (reader.UNNAMED,)]
    assert sum(shares) == pytest.approx(100.0)
    out = capsys.readouterr().out
    assert out.count("[bench] device by scope") == 2  # once a module
    assert "device by scope jit_step: runs 1, 0.0000 s; " in out
    assert ("; by instruction name: moe_gmm 0.0000; "
            "longest unnamed: while.7 0.0000, copy.1 0.0000") in out
    assert "jit_prefill: runs 1" in out and "by instruction name: none" in out
    for name in reader.VOCABULARY:
        assert f" {name} " in out


def test_the_text_keyed_reader_merges_what_this_one_keeps_apart(tmp_path):
    """Why the events are read by metadata id: ``scopes_by_operation`` keys
    by the HLO text and hands one operation both programs' paths."""
    result = _write(tmp_path, named=True)
    merged = common.plugin(REPO, "readers", "hybrid_roofline") \
        .scopes_by_operation(os.path.join(
            result["trace_dir"], "plugins", "profile", "2026_10_01",
            "vm.xplane.pb"))
    assert merged[SHARED] == {BODY + "blk_qkv/dot_general",
                              "jit(prefill)/tok_head/dot_general"}


def test_nothing_without_a_trace_and_unnamed_without_the_vocabulary(
        reader, tmp_path, capsys):
    args = {"module": STEP, "scopes": ["unnamed"]}
    assert reader.read({"trace": None}, args) is None
    assert reader.read({"trace": {"planes": []}, "trace_dir": None}, args) \
        is None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert reader.read({"trace": {"planes": []}, "trace_dir": str(empty)},
                       args) is None
    assert "device by scope" not in capsys.readouterr().out
    # the parent's program, or one from a compile cache filled before PR 39:
    # the same operations and no name, so the check on the coverage reads
    # all of it but the grouped product, and a share reads 0, not silence
    bare = _write(tmp_path, named=False)
    assert reader.read(bare, args) == pytest.approx(95.0)
    assert reader.read(bare, {"module": PREFILL, "scopes": ["unnamed"]}) \
        == pytest.approx(100.0)
    assert reader.read(bare, {"module": PREFILL, "scopes": ["blk_attn"]}) \
        == 0.0
    assert "unnamed 0.0000 (95.0 %)" in capsys.readouterr().out
    # no run of such a module inside the window
    named = _write(tmp_path, named=True)
    assert reader.read(named, {"module": r"^jit_verify(\(|$)",
                               "scopes": ["unnamed"]}) is None


def test_overlapping_events_are_charged_once(reader):
    assert reader.self_times([(0, 10, "a"), (5, 15, "b")]) == {"a": 5, "b": 10}
    assert reader.self_times([(0, 10, "a"), (0, 10, "b"), (2, 3, "c")]) == {
        "b": 9, "c": 1}
    assert reader.self_times([(0, 4, "a"), (6, 8, "a")]) == {"a": 6}
    assert reader.self_times([]) == {}


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/while/body/closed_call/blk_attn/blk_kv_write/scatter",
     "blk_kv_write"),
    ("jit(step)/blk_ffn/moe_gmm/jit(silu)/mul:", "moe_gmm"),
    ("jit(step)/blk_attn/mla_decode", "mla_decode"),
    ("jit(train_step)/transpose(jvp(blk_attn))/dot_general", "blk_attn"),
    ("jit(step)/blk_loop/while/body/closed_call/blk_qkv/dot_general",
     "blk_qkv"),
    ("jit(step)/blk_loop/while/body/dynamic_slice:", "blk_loop"),
    ("jit(step)/while/body/add", "unnamed"), ("", "unnamed"),
    ("jit(step)/not_blk_attn/add", "unnamed")])
def test_innermost_name_of_a_path(reader, path, scope):
    assert reader.classify(path) == scope


def test_a_renamed_grouped_product_is_charged_where_all_of_them_stand(reader):
    line = "%ragged-dot-none.1 = bf16[256,768]{1,0} custom-call(s32[1]{0} %g)"
    assert reader.classify("", line) == "moe_gmm"
    assert reader.classify("", "%ragged-dot-metadata = (s32[1025]{0})") \
        == "moe_gmm"
    assert reader.classify("", "%copy.64 = bf16[1,4096,4096]{1,2,0}") \
        == "unnamed"
    # a path wins over the table
    assert reader.classify("jit(step)/tok_head/add", line) == "tok_head"
    assert reader.classify("jit(step)/while/body/add", line) == "unnamed"


@pytest.mark.parametrize("stem", STEMS)
def test_entry_resolves_its_file_and_lists_cells_that_exist(reader, stem):
    entries = [m for m in BENCH["per_layer"]
               if m["name"] == stem or m["name"].startswith(stem + ".")]
    assert entries
    cells = {w["name"] for w in BENCH["workloads"]}
    for entry in entries:
        assert entry["workloads"] and set(entry["workloads"]) <= cells
        assert (entry["unit"], entry["source"], entry["layer"],
                entry["moves"], entry["better"]) == (
            "%", "device_trace", "model step", "itl_p95_ms", "lower")
        spec = common.metric_spec(REPO, entry["name"])
        assert spec["reader"] == "scope_share"
        assert set(spec["args"]["scopes"]) <= set(
            reader.VOCABULARY + (reader.UNNAMED,))
        rx = re.compile(spec["args"]["module"])
        matched = {m for m in ("jit_step(1)", "jit_prefill(2)", "jit_step",
                               "jit_prefill") if rx.search(m)}
        assert matched and not rx.search("jit_step_fn(3)")
        if "mixer" in stem:  # the cells whose models have a recurrent mixer
            assert all("agentbatch" in c for c in entry["workloads"])
