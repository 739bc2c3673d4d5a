"""The lower-precision control through the harness's own reader of limits
(``check_limits.py``), at tiny size on the CPU: the sound run of each kind of
cell passes its limits, the float8 control fails them."""

import json

import pytest

import benchmark_tiny as tiny
from benchmarks import check_limits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell,config,runner,seconds", [
    ("tiny-dense.train", "tiny-dense", "train", 0.5),
    ("tiny-dense.chat", "tiny-dense", "serve", 1.0),
    ("tiny-moe.batch", "tiny-moe", "serve", 1.0)])
def test_the_float8_control_fails_where_the_sound_run_passes(
        root, capsys, cell, config, runner, seconds):
    check_limits.main(["--workload", cell, "--seeds", "4",
                       "--seconds", str(seconds)], platform="cpu", root=root)
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("LIMITS ")]
    line = json.loads(out[-1][len("LIMITS "):])
    limits = tiny.CONFIGS[config][runner]["limits"]
    assert line["correct"] is True
    assert all(line["sound"][k] <= limits[k] for k in limits)
    assert any(line["control"][k] > 3 * limits[k] for k in limits)
