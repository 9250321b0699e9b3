"""The per-layer metrics read from the program's own spans (``repro.obs``),
each on hand-built records and a hand-built trace; and silent, without
raising, for a program that has no such spans."""
import sys

import pytest

from chipbench import run
from chipbench.tracefile import Reduced

NEW = ("fleet.prepare_ms", "fleet.fused_call_ms", "fleet.report_ms",
       "fleet.event_fill_pct", "host.gc_pct.fleet", "runtime.wait_pct",
       "runtime.forward_lag_ms", "runtime.planned_step_ms", "train.host_ms",
       "host.gc_pct.train")

MS = 1_000_000                           # ns per ms


def _span(name, start_ms, end_ms, **attrs):
    from repro.obs import Span
    s = Span(name, attrs)
    s.start_ns, s.end_ns = start_ms * MS, end_ms * MS
    return s


def _records():
    """Two fleet windows, then a pair window; times in ms."""
    rec = []
    for i, (a, pt, fu, rp, ev, tp) in enumerate(
            ((0, 20, 30, 40, 100, 256), (100, 30, 50, 60, 300, 512))):
        win = _span("fleet.window", a, a + pt + fu + rp, i=i)
        rec += [win, _span("fleet.prepare", a, a + pt),
                _span("fleet.fused", a + pt, a + pt + fu, events=ev,
                      slots=2 * tp, t_pad=tp, k_pad=2),
                _span("fleet.report", a + pt + fu, a + pt + fu + rp)]
    rec.append(_span("host.gc", 50, 53, generation=2))
    rec.append(_span("runtime.run", 300, 2300, planned_step_s=0.6947))
    for k, (w, lag) in enumerate(((0.25, 0.0004), (0.0, 0.0010))):
        rec.append(_span("runtime.wait", 400 + k, 401 + k, wait_s=w))
        rec.append(_span("runtime.serve", 500 + k, 550 + k, k=k, lag_s=lag))
    rec += [_span("train.step", 600, 1296), _span("train.step", 1300, 1998)]
    return rec


def _layer_run():
    trace = Reduced(window_s=2.0, busy_s=1.5,
                    modules={"jit_train_step": [0.693, 0.695]}, ops=[],
                    gaps=[])
    return run.LayerRun(trace, {"programs": {"train": "jit_train_step"},
                                "windows": 2}, {})


def _read(name):
    return run.Benchmark.load().reader(name)(_layer_run())


def test_new_metrics_are_declared_with_their_cells():
    spec = {m["name"]: m for m in run.Benchmark.load().spec["per_layer"]}
    for name in NEW:
        m = spec[name]
        cell = "fleet-k512-shed" if ".fleet" in name or \
            name.startswith("fleet.") else "pair-train"
        assert m["workloads"] == [cell]
        assert m["source"] == "program_span"


def test_readers_on_hand_built_records(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "_RECORDS", _records())
    want = {
        "fleet.prepare_ms": 25.0,                  # (20 + 30) / 2
        "fleet.fused_call_ms": 40.0,               # (30 + 50) / 2
        "fleet.report_ms": 50.0,                   # (40 + 60) / 2
        "fleet.event_fill_pct": 100.0 * 400 / 1536,
        "host.gc_pct.fleet": 100.0 * 0.003 / 2.0,
        "host.gc_pct.train": 100.0 * 0.003 / 2.0,
        "runtime.wait_pct": 100.0 * 0.25 / 2.0,
        "runtime.forward_lag_ms": 0.7,             # (0.4 + 1.0) / 2
        "runtime.planned_step_ms": 694.7,
        "train.host_ms": 697.0 - 694.0,            # span mean - device mean
    }
    for name in NEW:
        assert _read(name) == pytest.approx(want[name]), name


def test_no_collection_reads_zero_once_spans_were_recorded(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "_RECORDS",
                        [s for s in _records() if s.name != "host.gc"])
    assert _read("host.gc_pct.fleet") == 0.0


def test_readers_are_silent_without_spans(monkeypatch):
    from repro import obs
    monkeypatch.setattr(obs, "_RECORDS", [])
    for name in NEW:
        assert _read(name) is None, name


def test_readers_are_silent_for_a_program_without_obs(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in NEW:
        assert _read(name) is None, name
