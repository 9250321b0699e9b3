"""Training loop: jitted step + data pipeline + checkpointing + metrics."""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from repro import obs
from repro.checkpoint.checkpoint import restore_checkpoint, save_checkpoint
from repro.data.pipeline import ShardedPrefetcher, SyntheticTokenSource
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, init_opt_state
from repro.launch.steps import make_train_step
from repro.runtime.clock import Clock, WallClock


@dataclasses.dataclass
class TrainReport:
    steps: int
    losses: list[float]
    step_times: list[float]

    @property
    def final_loss(self) -> float:
        return self.losses[-1] if self.losses else float("nan")

    @property
    def mean_step_time(self) -> float:
        xs = self.step_times[2:] or self.step_times   # skip compile steps
        return float(np.mean(xs)) if xs else float("nan")


class Trainer:
    """Single-device trainer: the launcher, the examples and the real-mode
    runtime train through it, on one chip or on the CPU. The multi-chip
    path is the same train_step jitted with mesh shardings via
    launch.steps."""

    def __init__(self, cfg: M.ModelConfig, batch: int, seq_len: int,
                 opt_cfg: AdamWConfig = AdamWConfig(), seed: int = 0,
                 ckpt_path: Optional[str] = None,
                 clock: Optional[Clock] = None):
        self.cfg, self.batch, self.seq_len = cfg, batch, seq_len
        self.opt_cfg = opt_cfg
        self.ckpt_path = ckpt_path
        self.clock = clock if clock is not None else WallClock()
        self.params = M.init_params(jax.random.key(seed), cfg)
        self.opt_state = init_opt_state(self.params)
        self.step_fn = jax.jit(make_train_step(cfg, opt_cfg), donate_argnums=(0, 1))
        self.data = iter(ShardedPrefetcher(
            SyntheticTokenSource(cfg, batch, seq_len, seed=seed + 1)))
        self.step = 0

    def restore(self):
        if self.ckpt_path and Path(self.ckpt_path).exists():
            (self.params, self.opt_state), self.step = restore_checkpoint(
                self.ckpt_path, (self.params, self.opt_state))

    def step_minibatch(self) -> None:
        """One optimizer step on the next data batch — the unit the managed
        interleave runtime schedules into inference slack. Returns when the
        step has finished on the device: an asynchronously dispatched step
        would let the runtime queue more training than the slack holds,
        and inference would then wait behind it."""
        with obs.span("train.step") as sp:
            batch = next(self.data)
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            jax.block_until_ready(self.params)
            if sp:   # the step's routing counts, where the model routes
                sp.attrs.update({k: int(metrics[k]) for k in M.MOE_COUNTS
                                 if k in metrics})
        self.step += 1

    def train(self, num_steps: int, log_every: int = 10,
              ckpt_every: int = 0) -> TrainReport:
        losses, times = [], []
        for _ in range(num_steps):
            batch = next(self.data)
            t0 = self.clock.now()
            self.params, self.opt_state, metrics = self.step_fn(
                self.params, self.opt_state, batch)
            loss = float(metrics["loss"])
            times.append(self.clock.now() - t0)
            losses.append(loss)
            self.step += 1
            if log_every and self.step % log_every == 0:
                print(f"step {self.step:5d} loss {loss:.4f} "
                      f"({times[-1]*1e3:.0f} ms)", flush=True)
            if ckpt_every and self.ckpt_path and self.step % ckpt_every == 0:
                save_checkpoint(self.ckpt_path, (self.params, self.opt_state),
                                self.step)
        return TrainReport(self.step, losses, times)

    def train_minibatch_time(self, warmup: int = 2, iters: int = 3) -> float:
        """Profile one training minibatch (used by the real-mode Fulcrum):
        the mean time of ``step_minibatch``, the unit the runtime schedules.
        Steps dispatched back to back without waiting measured 0.965 s each
        on a TPU v5e where one waited-for step takes 0.581 s."""
        for _ in range(warmup):
            self.step_minibatch()
        t0 = self.clock.now()
        for _ in range(iters):
            self.step_minibatch()
        return (self.clock.now() - t0) / iters
