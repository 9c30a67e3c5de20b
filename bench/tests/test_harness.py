"""The harness on the CPU at a small size: a whole run without the look for
a chip, the faults the comparison must catch, and the data-driven layout."""
from __future__ import annotations

import json
import os
import re
import time

import numpy as np
import pytest

from bench import run
from bench.families import dlrm as fam
from bench import gen
from conftest import DATA, ROOT, small_config, small_mix

BENCH = run.load_benchmark()
CELLS = {w["name"]: w for w in BENCH["workloads"]}
CONFIG_OF = {c["name"]: c for c in BENCH["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _run(cell, *, trace=False, seconds=0.4, seed=2**31 + 11):
    w = CELLS[cell]
    cfg = small_config(w["config"])
    return run.run_cell(cfg, small_mix(w["traffic"]),
                        run.load_limits(cell), seed=seed, seconds=seconds,
                        trace=trace, t_start=time.monotonic(),
                        require_chip=False,
                        metrics=run.cell_metrics(BENCH, cell, trace))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_whole_run_is_correct_and_reports_its_metrics(cell):
    out = _run(cell)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in run.cell_metrics(BENCH, cell, False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "compared"
    for c in out["compared"].values():
        assert c["value"] <= c["limit"]


def _tampered(monkeypatch, how):
    real = fam.System.__call__

    def call(self, batch):
        scores = real(self, batch)
        return how(scores)
    monkeypatch.setattr(fam.System, "__call__", call)


@pytest.mark.parametrize("cell", ["paper-serve", "paper-bulk"])
def test_an_answer_altered_where_it_is_produced_fails(cell, monkeypatch):
    """One score of every batch moved by 0.05 inside the timed step."""
    _tampered(monkeypatch, lambda s: s.at[0].add(0.05))
    out = _run(cell)
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"]["score_gap"]["value"] > \
        out["compared"]["score_gap"]["limit"]


#: a committed cell of each mode, whose end-to-end metrics a per-field run
#: of that mode reports
CELL_OF_MODE = {"open": "paper-serve", "closed": "paper-bulk"}
#: the CPU computes in fp32 as the reference does: the scores differ by
#: summation order alone
PER_FIELD_LIMITS = {"score_gap": 1e-4}


def _run_per_field(mode):
    """A whole run of ``data/multi-hot-small.json`` (MLPerf DLRM-DCNv2's 26
    fields and per-field bag lengths, at a CPU's size) under the data mix of
    ``mode``: nothing of the harness but its files."""
    with open(os.path.join(DATA, "multi-hot-small.json")) as f:
        cfg = json.load(f)
    return run.run_cell(cfg, gen.load_mix(f"multi-hot-{mode}", DATA),
                        PER_FIELD_LIMITS, seed=2**31 + 23, seconds=0.4,
                        trace=False, t_start=time.monotonic(),
                        require_chip=False,
                        metrics=run.cell_metrics(BENCH, CELL_OF_MODE[mode],
                                                 False))


@pytest.mark.parametrize("mode", sorted(CELL_OF_MODE))
def test_a_per_field_configuration_runs_whole_and_correct(mode):
    out = _run_per_field(mode)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    want = {m["name"] for m in run.cell_metrics(BENCH, CELL_OF_MODE[mode],
                                                False)}
    assert set(out["metrics"]) == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["compared"]["score_gap"]["value"] <= \
        PER_FIELD_LIMITS["score_gap"]


@pytest.mark.parametrize("mode", sorted(CELL_OF_MODE))
def test_a_per_field_answer_altered_where_it_is_produced_fails(
        mode, monkeypatch):
    _tampered(monkeypatch, lambda s: s.at[0].add(0.05))
    out = _run_per_field(mode)
    assert out["correct"] is False and out["failed"] > 0
    assert out["compared"]["score_gap"]["value"] > \
        PER_FIELD_LIMITS["score_gap"]


def test_an_answer_that_never_comes_fails(monkeypatch):
    """A batch whose first score is lost (nan) counts as unscored."""
    _tampered(monkeypatch, lambda s: s.at[0].set(np.nan))
    out = _run("paper-serve")
    assert out["correct"] is False
    assert out["compared"]["unscored"]["value"] > 0


def test_trace_run_reports_per_layer_metrics_it_can_read():
    """On the CPU there is no device plane: the device readers stay silent
    and the host-clock ones report."""
    out = _run("paper-serve", trace=True)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"gen_late_ms", "assemble_ms"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_new_metric_reader_is_found_by_name(tmp_path):
    (tmp_path / "queue_len.py").write_text(
        "def read(run):\n    return 3.0\n")
    assert run.reader("queue_len.serve", str(tmp_path)).read(None) == 3.0
    with pytest.raises(FileNotFoundError):
        run.reader("nope", str(tmp_path))


def test_every_metric_has_a_reader_and_every_cell_its_files():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert hasattr(run.reader(m["name"]), "read")
    for name, w in CELLS.items():
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           f"{w['traffic']}.json"))
        assert "score_gap" in run.load_limits(name)
        assert os.path.exists(os.path.join(ROOT,
                                           CONFIG_OF[w["config"]]["file"]))


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [c["name"] for c in BENCH["configs"]] + list(CELLS) \
        + list(e2e) + [m["name"] for m in BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"] for x in
                                  run.cell_metrics(BENCH, cell, False)}
    for cell in CELLS:
        e = {m["name"] for m in run.cell_metrics(BENCH, cell, False)}
        assert "setup_s" in e and len(e) >= 2
        assert run.cell_metrics(BENCH, cell, True)
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
