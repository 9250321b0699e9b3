"""The benchmark's own files with each configuration cut to a size a CPU
test run holds; the limits, mixes and readers are the real ones."""
import json
import shutil
from pathlib import Path

from chipbench import run

SRC = Path(__file__).resolve().parents[1]

TINY = {
    "pair-mamba2-780m-qwen2-0.5b": {
        "train": dict(d_model=64, n_layer=2, d_state=16, headdim=16,
                      chunk_size=32, vocab_size=500,
                      pad_vocab_size_multiple=128, seq_len=32),
        "serve": dict(hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      intermediate_size=128, vocab_size=500,
                      pad_vocab_size_multiple=128, vision_tokens=8,
                      vision_width=32, text_tokens=16)},
    "fleet-mobilenet-k512": {"devices": 8},
}


def bench(tmp: Path) -> run.Benchmark:
    for d in ("configs", "traffic", "metrics", "kinds"):
        shutil.copytree(SRC / d, tmp / d)
    for name, cut in TINY.items():
        path = tmp / "configs" / f"{name}.json"
        c = json.loads(path.read_text())
        for key, val in cut.items():
            if isinstance(val, dict):
                c[key].update(val)
            else:
                c[key] = val
        path.write_text(json.dumps(c))
    for path in (tmp / "traffic").glob("*.json"):
        m = json.loads(path.read_text())
        if m["arrivals"] == "fleet_windows":
            m["windows_per_s"] = 2.0
        path.write_text(json.dumps(m))
    return run.Benchmark(run.Benchmark.load().spec, tmp)
