"""A whole run of the pair cell at a tiny size on the CPU, with the chip
check skipped: sound, it is correct; with the timed path broken underneath,
``correct`` comes out false, once for each fault the cell can have."""
import jax
import pytest

from chipbench import run
from chipbench.tests import tiny

CELL, SEED, SECONDS = "pair-train", 3000000001, 3.0


@pytest.fixture
def bench(tmp_path):
    return tiny.bench(tmp_path)


def _go(bench):
    return run.run_cell(bench, CELL, SEED, SECONDS, False,
                        require_chip=False)


def test_sound_run_is_correct(bench):
    out = _go(bench)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def _broken_step(monkeypatch, breaks):
    from repro.runtime import train_loop
    make = train_loop.make_train_step

    def make_broken(cfg, opt_cfg):
        return breaks(make(cfg, opt_cfg))
    monkeypatch.setattr(train_loop, "make_train_step", make_broken)


def test_step_returning_its_state_unchanged_is_caught(bench, monkeypatch):
    def unchanged(step):
        def f(params, opt_state, batch):
            new_p, new_o, metrics = step(params, opt_state, batch)
            return params, new_o, metrics
        return f
    _broken_step(monkeypatch, unchanged)
    out = _go(bench)
    assert out["correct"] is False
    assert out["checks"]["change_gap"]["value"] > \
        out["checks"]["change_gap"]["limit"]


def test_half_the_batch_left_out_is_caught(bench, monkeypatch):
    def half(step):
        def f(params, opt_state, batch):
            keep = batch["tokens"].shape[0] // 2
            return step(params, opt_state,
                        jax.tree.map(lambda x: x[:keep], batch))
        return f
    _broken_step(monkeypatch, half)
    assert _go(bench)["correct"] is False


def test_served_token_altered_where_produced_is_caught(bench, monkeypatch):
    from repro.runtime import serving
    infer = serving.BatchInferenceServer.infer

    def altered(self, batch=None):
        out = infer(self, batch)
        # the last position serves its worst token
        return out.at[:, -1].set(-out[:, -1])
    monkeypatch.setattr(serving.BatchInferenceServer, "infer", altered)
    out = _go(bench)
    assert out["correct"] is False
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]
