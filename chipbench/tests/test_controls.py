"""Each cell's control, at a size a CPU test run holds, fails at least one
of the cell's limits: the pair's references in float8 in the system's
place, the fleet's replay in float32."""
import pytest

from chipbench.tests import tiny


@pytest.mark.parametrize("cell", ["pair-train", "fleet-k512-shed"])
def test_control_fails_a_limit(tmp_path, cell):
    bench = tiny.bench(tmp_path)
    w = bench.cell(cell)
    config = bench.config(w["config"])
    out = bench.kind(config).control(config, bench.mix(w["traffic"]),
                                     3000000002, 4.0, log=lambda *a: None)
    ctl = next(v for k, v in out.items() if k.startswith("control"))
    limits = config["limits"]
    assert any(ctl[k] > limits[k] for k in ctl if k in limits), ctl
