"""A whole run of the fleet cell at K=8 on the CPU, with the chip check
skipped: sound, it is correct; with an answer altered where the fused
window produces it, ``correct`` comes out false."""
import numpy as np
import pytest

from chipbench import run
from chipbench.tests import tiny

CELL, SEED, SECONDS = "fleet-k512-shed", 3000000001, 4.0


@pytest.fixture
def bench(tmp_path):
    return tiny.bench(tmp_path)


def test_sound_run_is_correct(bench):
    out = run.run_cell(bench, CELL, SEED, SECONDS, False, require_chip=False)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0


def _alter(monkeypatch, change):
    from repro.core import fleet
    fused = fleet.fused_fleet_window

    def altered(*a, **k):
        res = fused(*a, **k)
        change(res)
        return res
    monkeypatch.setattr(fleet, "fused_fleet_window", altered)


def test_latency_altered_where_produced_is_caught(bench, monkeypatch):
    def later(res):
        res["latencies"] = np.array(res["latencies"], copy=True)
        res["latencies"][:, 0] += 1e-6
    _alter(monkeypatch, later)
    out = run.run_cell(bench, CELL, SEED, SECONDS, False, require_chip=False)
    assert out["correct"] is False
    assert out["checks"]["latency_gap_s"]["value"] > \
        out["checks"]["latency_gap_s"]["limit"]


def test_shed_count_altered_where_produced_is_caught(bench, monkeypatch):
    def drop(res):
        res["n_rej"] = np.array(res["n_rej"], copy=True)
        res["n_rej"][0] += 1
    _alter(monkeypatch, drop)
    out = run.run_cell(bench, CELL, SEED, SECONDS, False, require_chip=False)
    assert out["correct"] is False


def test_plan_altered_where_produced_is_caught(bench, monkeypatch):
    def other_entry(res):
        res["sel"] = np.array(res["sel"], copy=True)
        res["sel"][0] = res["sel"][0] + 1 if res["sel"][0] % 5 < 4 \
            else res["sel"][0] - 1
    _alter(monkeypatch, other_entry)
    out = run.run_cell(bench, CELL, SEED, SECONDS, False, require_chip=False)
    assert out["correct"] is False
    assert out["checks"]["plan_errors"]["value"] > 0
