"""Run one cell of the benchmark once, on the chip this process holds.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``chipbench/configs/<config>.json`` (whose ``kind``
names the runner in ``chipbench/kinds/``), its traffic mix in
``chipbench/traffic/<mix>.json`` and each per-layer metric in
``chipbench/metrics/<metric>.py``. A new cell, configuration, mix or metric
is a new file and a new entry; this file does not change.

The run fails, and prints no result, without an accelerator or with fewer
chips than the cell asks for. It builds and warms the cell (set-up), then
measures for ``--seconds``; with ``--trace 1`` it records a profiler trace
of the window and reports the per-layer metrics, otherwise the end-to-end
ones. After the window, with the system's state freed, it compares what the
timed path produced with the plain references. The numbers compared go to
standard error as the last lines, each beside its limit, and the last line
of standard output is one JSON object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

NO_RESULT = 3            # exit code of a run that could not measure
HOST_SPANS = ("plan", "train_step", "serve_forward", "wait_for_batch",
              "serve_fleet")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class Benchmark:
    """``BENCHMARK.json`` and the files it names, found under ``root``."""

    def __init__(self, spec: dict, root: Path = HERE):
        self.spec, self.root = spec, root

    @classmethod
    def load(cls, path: Path = ROOT / "BENCHMARK.json") -> "Benchmark":
        return cls(json.loads(path.read_text()))

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise KeyError(f"unknown workload {name!r}; the cells are {known}")

    def config(self, name: str) -> dict:
        return json.loads((self.root / "configs" / f"{name}.json")
                          .read_text())

    def mix(self, name: str) -> dict:
        from chipbench import traffic
        return traffic.load(name, self.root / "traffic")

    def kind(self, config: dict):
        path = self.root / "kinds" / f"{config['kind']}.py"
        return _module(path, f"chipbench.kinds.{config['kind']}")

    @staticmethod
    def _applies(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.spec["per_layer"] if self._applies(m, cell)]

    def reader(self, metric: str):
        path = self.root / "metrics" / f"{metric}.py"
        return _module(path, f"chipbench.metrics.{metric}").read


def _module(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise KeyError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


class LayerRun:
    """What a per-layer metric's reader gets: the reduced trace, the
    runner's counts and spans, and the chip's peaks."""

    def __init__(self, trace, ctx: dict, peak: dict):
        self.trace, self.ctx, self.peak = trace, ctx, peak


class CompileCounter:
    """Counts the programs traced or compiled while it is armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_) -> None:
        if self.armed and event in ("/jax/core/compile/jaxpr_trace_duration",
                                    "/jax/core/compile/backend_compile_duration"):
            self.count += 1


def accelerator(chips: int):
    """The devices this run measures on; None without enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        log(f"no accelerator with {chips} chip(s): JAX found "
            f"{len(devs)} {devs[0].platform} device(s)")
        return None
    return devs


def run_cell(bench: Benchmark, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True) -> dict:
    """One run of one cell; returns the result line's object, or raises."""
    import jax
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.mix(cell["traffic"])
    kind = bench.kind(config)
    import repro  # noqa: F401  (the system under test must be present)

    devs = accelerator(cell["chips"])
    if devs is None:
        if require_chip:
            raise SystemExit(NO_RESULT)
        devs = jax.devices()
    from chipbench.peaks import peak
    chip_peak = peak(devs[0].device_kind) if require_chip else {}
    if require_chip:
        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        log(f"device {devs[0].device_kind} x{len(devs)}; compile cache "
            f"{cache}")

    runner = kind.Runner(config, mix, seed, seconds, log=log)
    runner.setup()
    counter = CompileCounter()
    setup_s = time.perf_counter() - T_START
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    counter.armed = True
    try:
        with jax.profiler.TraceAnnotation("window"):
            runner.window()
    finally:
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
    mem = [d.memory_stats() or {} for d in devs[:cell["chips"]]]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    e2e, attempted, failed = runner.end_to_end()
    e2e["setup_s"] = setup_s
    log(f"set-up {setup_s:.3f} s; programs traced or compiled in the window: "
        f"{counter.count}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_bytes}
    out = {"metrics": {}, "breakdown": None}
    if trace:
        from chipbench import tracefile
        files = glob.glob(f"{tdir}/**/*.xplane.pb", recursive=True)
        red = tracefile.load(files[0], HOST_SPANS)
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: window {red.window_s:.6f} s, busy {red.busy_s:.6f} s; "
            f"programs {sorted(red.modules)}")
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        lr = LayerRun(red, runner.layer_context(), chip_peak)
        for m in bench.per_layer(workload):
            v = bench.reader(m["name"])(lr)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = {"device_ops": [[n, s] for n, s in red.ops],
                            "idle_gaps": [[n, s] for n, s in red.gaps]}
    else:
        for m in bench.end_to_end(workload):
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    runner.free()
    gc.collect()
    readings = runner.check()
    correct = readings.correct
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": out["metrics"], "device": device}
    if out["breakdown"] is not None:
        result["breakdown"] = out["breakdown"]
    result["checks"] = {k: {"value": readings.values[k],
                            "limit": readings.limits[k]}
                        for k in readings.compared()}
    for k, v in readings.values.items():
        if k not in readings.limits:
            log(f"{k} {v!r}")
    for line in readings.lines():
        log(line)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    try:
        bench = Benchmark.load()
        bench.cell(a.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"cannot run: {e}")
        return 2
    try:
        result = run_cell(bench, a.workload, a.seed, a.seconds,
                          bool(a.trace))
    except ImportError as e:
        log(f"the system under test is missing: {e}")
        return NO_RESULT
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
