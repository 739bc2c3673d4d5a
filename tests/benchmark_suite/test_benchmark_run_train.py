"""The training cell's whole pass at tiny size on the CPU: control plane
children, the fed step, the reference following the first three steps; and
a broken step coming out as not correct (the control has a file of its own,
so that two workers share the time)."""

import re

import pytest

import benchmark_tiny as tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def test_train_cell_matches_the_reference(root, capsys):
    line, text = tiny.run_cell(root, "tiny-dense.train", 2**31 + 3, 1.0, 0,
                               capsys, earlier=True)
    # the rate is every step of the window over all of its time
    closed = re.search(r"window closed steps=(\d+) window_s=([0-9.]+) .*"
                       r"run_ahead_steps=(\d+)", text)
    steps, window_s, lag = (float(x) for x in closed.groups())
    assert lag == 3 and steps == line["attempted"]
    assert line["metrics"]["train_tokens_per_s"]["value"] == pytest.approx(
        steps * 2 * 128 / window_s, rel=2e-3)
    assert 1.0 <= window_s < 2.0
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["device"]["platform"] == "cpu" and line["attempted"] >= 1


def test_a_step_that_leaves_the_parameters_unchanged_is_not_correct(
        root, capsys, monkeypatch):
    from oim_tpu.train import trainer

    monkeypatch.setattr(trainer.optax, "apply_updates", lambda p, u: p)
    line = tiny.run_cell(root, "tiny-dense.train", 9, 0.5, 0, capsys)
    assert line["correct"] is False
