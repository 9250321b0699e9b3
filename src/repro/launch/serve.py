"""Serving launcher: batched prefill+decode requests against one arch.

  PYTHONPATH=src python -m repro.launch.serve --arch internvl2-1b \
      --requests 8 --prompt-len 32 --gen 16 [--reduced]

Serves the architecture at its published size on the default device;
``--reduced`` swaps in the 2-layer smoke variant (what fits a CPU run).
"""
from __future__ import annotations

import argparse
import time

from repro.configs import get_config, make_batch, reduced as reduce_cfg
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime.serving import GenerationServer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--bs", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke variant (default: published size)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
    max_seq = args.prompt_len + args.gen
    server = GenerationServer(cfg, max_seq=max_seq, bs=args.bs)
    print(f"serving {cfg.name}: bs={args.bs}, prompt {args.prompt_len}, "
          f"gen {args.gen}")
    batches = (args.requests + args.bs - 1) // args.bs
    for i in range(batches):
        prompt = make_batch(cfg, args.prompt_len, args.bs, "prefill", seed=i)
        t0 = time.time()
        tokens = server.generate(prompt, steps=args.gen,
                                 prompt_len=args.prompt_len)
        dt = time.time() - t0
        print(f"batch {i}: {tokens.shape[0]}x{tokens.shape[1]} tokens in "
              f"{dt*1e3:.0f} ms ({tokens.shape[0]*tokens.shape[1]/dt:.1f} tok/s) "
              f"first seq: {tokens[0][:8].tolist()}")


if __name__ == "__main__":
    main()
