"""The trace reduction against a hand count on a small recorded trace."""
import pytest

from chipbench import tracefile

# one chip; times in seconds. The window runs from 1.0 to 2.0.
CHIP = {
    "ops": [("fusion.1", 0.9, 1.1),      # clipped to 1.0-1.1
            ("dot.2", 1.05, 1.2),        # overlaps the first
            ("fusion.1", 1.5, 1.6),
            ("copy.3", 1.95, 2.3)],      # clipped to 1.95-2.0
    "modules": [("jit_train_step(12)", 0.9, 1.2),
                ("jit_train_step(12)", 1.5, 1.6),
                ("jit__lambda(7)", 1.95, 2.3),
                ("jit__lambda_2(9)", 1.7, 1.8)],   # another program
}
HOST = [("window", 1.0, 2.0),
        ("wait_for_batch", 1.2, 1.45),
        ("serve_forward", 1.45, 1.6),
        ("train_step", 1.6, 2.0)]


def test_busy_idle_programs_and_gaps_by_hand():
    r = tracefile.reduce_events([CHIP], HOST)
    assert r.window_s == pytest.approx(1.0)
    # union: [1.0, 1.2] + [1.5, 1.6] + [1.95, 2.0] = 0.2 + 0.1 + 0.05
    assert r.busy_s == pytest.approx(0.35)
    assert r.idle_share == pytest.approx(0.65)
    # train step: 1.0-1.2 (clipped) and 1.5-1.6; the forward 1.95-2.0
    assert sorted(r.module_seconds("jit_train_step")) == \
        pytest.approx([0.1, 0.2])
    assert r.module_seconds("jit__lambda") == pytest.approx([0.05])
    assert r.module_seconds("jit__lambda_2") == pytest.approx([0.1])
    ops = dict(r.ops)
    assert ops["fusion.1"] == pytest.approx(0.2)
    assert ops["dot.2"] == pytest.approx(0.15)
    assert ops["copy.3"] == pytest.approx(0.05)
    # gaps: 1.2-1.5 (wait 0.25 of it, serve 0.05) and 1.6-1.95 (train)
    assert [g[0] for g in r.gaps] == ["train_step", "wait_for_batch"]
    assert [g[1] for g in r.gaps] == pytest.approx([0.35, 0.3])


def test_gap_with_no_host_span_and_two_chips_average():
    other = {"ops": [("fusion.9", 1.0, 2.0)], "modules": []}
    r = tracefile.reduce_events([CHIP, other], [("window", 1.0, 2.0)])
    assert r.busy_s == pytest.approx((0.35 + 1.0) / 2)
    assert {g[0] for g in r.gaps} == {"no_span"}


def test_needs_exactly_one_window_and_a_device():
    with pytest.raises(ValueError):
        tracefile.reduce_events([CHIP], HOST[1:])
    with pytest.raises(ValueError):
        tracefile.reduce_events([], HOST)


def test_ops_named_without_their_instruction_text():
    chip = {"ops": [("%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
                     1.0, 1.25),
                    ("%fusion.7 = f32[4]{0} fusion(f32[4]{0} %q), kind=kLoop",
                     1.5, 1.75)],
            "modules": []}
    r = tracefile.reduce_events([chip], [("window", 1.0, 2.0)])
    assert r.ops == [("fusion.7", pytest.approx(0.5))]


@pytest.mark.parametrize("metric", ["infer.forward_ms", "infer.mfu"])
def test_server_readers_need_one_launch_per_forward(metric):
    from chipbench import run
    r = tracefile.reduce_events([CHIP], HOST)
    read = run.Benchmark({}).reader(metric)
    ctx = {"programs": {"infer": "jit__lambda"}, "flops": {"infer": 1e12}}
    peak = {"bf16_flops": 1e14}
    one = read(run.LayerRun(r, dict(ctx, infer_calls=1), peak))
    assert one == pytest.approx(50.0 if metric.endswith("_ms") else 20.0)
    assert read(run.LayerRun(r, dict(ctx, infer_calls=2), peak)) is None
