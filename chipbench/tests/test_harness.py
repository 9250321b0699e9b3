"""The harness on the CPU: no result without a chip, unknown cells refused,
and a configuration, a mix and a metric found as new files by name."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import run

ROOT = Path(__file__).resolve().parents[2]


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=300)


def test_exits_non_zero_without_a_tpu_and_prints_no_result():
    p = _run("--workload", "fleet-k512-shed", "--seed", "3000000001",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == run.NO_RESULT
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_refuses_an_unknown_cell():
    p = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1")
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "unknown workload" in p.stderr


TOY_KIND = '''
from chipbench.checks import Readings


class Runner:
    def __init__(self, config, mix, seed, seconds, log=print):
        self.c, self.mix, self.seed = config, mix, seed

    def setup(self):
        self.work = self.c["size"] * self.mix["scale"]

    def window(self):
        self.done = self.work

    def end_to_end(self):
        return {"toy_rate": float(self.done)}, 1, 0

    def layer_context(self):
        return {"done": self.done}

    def free(self):
        pass

    def check(self):
        return Readings({"gap": 0.0}, self.c["limits"])
'''


def test_finds_new_files_by_name_without_an_edit(tmp_path):
    for d in ("configs", "traffic", "metrics", "kinds"):
        (tmp_path / d).mkdir()
    (tmp_path / "kinds" / "toy.py").write_text(TOY_KIND)
    (tmp_path / "configs" / "toy-config.json").write_text(json.dumps(
        {"kind": "toy", "size": 6, "limits": {"gap": 0.0}}))
    (tmp_path / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"scale": 7}))
    (tmp_path / "metrics" / "toy.done.py").write_text(
        "def read(run):\n    return float(run.ctx['done'])\n")
    spec = {"workloads": [{"name": "toy-cell", "config": "toy-config",
                           "traffic": "toy-mix", "chips": 1}],
            "end_to_end": [{"name": "toy_rate", "unit": "1/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "toy.done", "unit": "1",
                           "workloads": ["toy-cell"]}]}
    bench = run.Benchmark(spec, tmp_path)
    out = run.run_cell(bench, "toy-cell", 5, 1.0, False, require_chip=False)
    assert out["correct"] is True
    assert out["metrics"]["toy_rate"]["value"] == 42.0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
    reader = bench.reader("toy.done")
    assert [m["name"] for m in bench.per_layer("toy-cell")] == ["toy.done"]
    assert reader(run.LayerRun(None, {"done": 42}, {})) == 42.0
    with pytest.raises(KeyError):
        bench.cell("other-cell")


def test_finds_a_new_architecture_by_name_without_an_edit(tmp_path,
                                                          monkeypatch):
    import chipbench.reference
    import chipbench.systems
    from chipbench.tests import tiny
    for pkg, text in ((chipbench.reference, "def forward_flops(c, b, s):\n"
                       "    return 7.0\n"),
                      (chipbench.systems, "def model_config(c):\n"
                       "    return ('toy', c['width'])\n")):
        d = tmp_path / pkg.__name__.rsplit(".", 1)[1]
        d.mkdir()
        (d / "toy_arch.py").write_text(text)
        monkeypatch.setattr(pkg, "__path__", [*pkg.__path__, str(d)])
    bench = tiny.bench(tmp_path / "bench")
    pair = bench.kind({"kind": "pair"})
    assert pair.reference("toy_arch").forward_flops({}, 1, 1) == 7.0
    assert pair.system_config("toy_arch", {"width": 3}) == ("toy", 3)
