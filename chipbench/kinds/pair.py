"""The train-and-serve pair: one model trains through ``Trainer`` while
another serves through ``BatchInferenceServer``, interleaved by
``ManagedInterleaveRuntime`` on one chip, after the plan of
``Fulcrum.solve_concurrent``.

The benchmark hands the system its weights (made on the device from the
seed, in the type they are held in), its training rows and its requests;
it wraps the trainer, the server and the runtime's clock in spans of its
own, so every end-to-end number is taken on the host clock by the
benchmark. Each model's ``arch`` names its plain reference,
``chipbench/reference/<arch>.py``, and the mapping of its keys onto the
system's model configuration, ``chipbench/systems/<arch>.py``.

``correct`` compares, after the window, the trainer's first three steps
from the seed (its loss, its first gradient as the optimizer holds it, its
change of every leaf), which set-up drives through the runtime's own
``run``, and a sample of the window's served tokens with the plain float32
references.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np

from chipbench import traffic
from chipbench.checks import Readings
from chipbench.stats import nearest_rank
from chipbench.reference import adamw as ref_adamw

CHECK_STEPS = 3          # training steps the reference follows
CHECK_SLACK = 4          # steps' room the check run leaves past those,
                         # so a host stall of a second still gives 3 steps
SAMPLE_REQUESTS = 8      # served requests the reference runs again
TRAIN_ROWS = 96          # distinct training minibatches made per run
VISION_POOL = 64         # distinct images the requests draw from


def reference(arch: str):
    """The plain reference of an architecture, found by name."""
    return importlib.import_module(f"chipbench.reference.{arch}")


def system_config(arch: str, c: dict):
    """The system's model configuration of ``c``, by its architecture."""
    return importlib.import_module(f"chipbench.systems.{arch}") \
        .model_config(c)


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


_CHANGE: dict = {}


def change_norms(arch, c: dict, seed: int, stream: int, params) -> np.ndarray:
    """Per-leaf norm of ``params`` minus the weights the seed gives, each
    starting weight drawn again inside the reduction, so no second copy of
    the model is held."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference.numerics import draw, leaf_key
    key = (arch.__name__, c["name"])
    if key not in _CHANGE:
        spec = {path: (shape, how) for path, shape, how in arch.leaves(c)}

        def fn(p, k):
            flat, _ = jax.tree_util.tree_flatten_with_path(p)
            out = []
            for kp, x in flat:
                path = tuple(part.key for part in kp)
                shape, how = spec[path]
                out.append(jnp.linalg.norm(
                    (x.astype(jnp.float32)
                     - draw(leaf_key(k, path), shape, how)).ravel()))
            return jnp.stack(out)
        _CHANGE[key] = jax.jit(fn)
    return np.asarray(_CHANGE[key](params, seed_key(seed, stream)))


def seed_key(seed: int, stream: int):
    """A JAX key for one stream of the run, from a seed of any size."""
    import jax
    k = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 31), stream)


# -- inputs made from the seed, on the device ------------------------------

def make_rows(t: dict, seed: int, n: int, batch: int):
    """``n`` training minibatches of next-token rows, all distinct."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rows(key):
        toks = jax.random.randint(key, (n, batch, t["seq_len"] + 1), 0,
                                  t["vocab_size"], jnp.int32)
        return toks[..., :-1], toks[..., 1:]

    toks, labels = rows(seed_key(seed, 1))
    return [{"tokens": toks[i], "labels": labels[i]} for i in range(n)]


def make_requests(s: dict, seed: int, n: int):
    """``n`` requests: each its own text tokens and one of a pool of
    ``VISION_POOL`` images' embeddings. Returns the tokens and the image of
    each request on the host, where requests wait, and the pool on the
    device, where the vision tower the pool stands in for leaves its
    output."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def reqs(key):
        k1, k2, k3 = jax.random.split(key, 3)
        toks = jax.random.randint(k1, (n, s["text_tokens"]), 0,
                                  s["vocab_size"], jnp.int32)
        img = jax.random.randint(k2, (n,), 0, VISION_POOL, jnp.int32)
        pool = jax.random.normal(
            k3, (VISION_POOL, s["vision_tokens"], s["vision_width"]),
            jnp.float32).astype(jnp.bfloat16)
        return toks, img, pool

    toks, img, pool = reqs(seed_key(seed, 2))
    return np.asarray(toks), np.asarray(img), pool


def vision_batch(pool, img):
    """The image embeddings of one batch, gathered on the device."""
    import jax.numpy as jnp
    return jnp.take(pool, img, axis=0)


class Requests:
    """The requests of a run, batched in arrival order."""

    def __init__(self, s: dict, seed: int, n: int, bs: int):
        import jax
        self.toks, self.img, self.pool = make_requests(s, seed, n)
        self.bs = bs
        self.gather = jax.jit(vision_batch)

    def batch(self, k: int) -> dict:
        sl = slice(k * self.bs, (k + 1) * self.bs)
        return self.pick(sl)

    def pick(self, idx) -> dict:
        return {"tokens": self.toks[idx],
                "vision": self.gather(self.pool, self.img[idx])}


def make_weights(arch, c: dict, seed: int, stream: int):
    import jax
    return jax.jit(lambda k: arch.init(k, c))(seed_key(seed, stream))


# -- the benchmark's spans around the system's parts -----------------------

class SpanClock:
    """The runtime's clock: real time from ``start()``, with the wait for a
    batch to form as a host span."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def start(self) -> None:
        self.t0 = time.perf_counter()

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def sleep_until(self, t: float) -> None:
        import jax
        with jax.profiler.TraceAnnotation("wait_for_batch"):
            dt = t - self.now()
            if dt > 0:
                time.sleep(dt)


class TrainerSpans:
    """Duck-typed trainer for the runtime: counts and times the steps, and
    calls ``after_step`` (when set) with the count after each."""

    def __init__(self, trainer, clock: SpanClock):
        self.trainer, self.clock = trainer, clock
        self.steps: list[tuple[float, float]] = []
        self.after_step = None

    def train_minibatch_time(self) -> float:
        return self.trainer.train_minibatch_time()

    def step_minibatch(self) -> None:
        import jax
        t = self.clock.now()
        with jax.profiler.TraceAnnotation("train_step"):
            self.trainer.step_minibatch()
        self.steps.append((t, self.clock.now()))
        if self.after_step is not None:
            self.after_step(len(self.steps))


class ServerSpans:
    """Duck-typed server for the runtime: feeds each call the next batch of
    requests, waits for the forward, stamps its end, and keeps the served
    tokens (the argmax at every position) for the check. It waits for those
    tokens too before it returns, so the batch's logits (1.86 GB at the
    cell's sizes) are free before the runtime can launch a training step,
    whose temporaries would not fit beside them."""

    def __init__(self, server, requests: Requests, clock: SpanClock,
                 served_fn):
        self.server, self.requests, self.clock = server, requests, clock
        self.served_fn = served_fn
        self.calls: list[tuple[float, float]] = []
        self.tokens: list = []

    def infer(self):
        import jax
        t = self.clock.now()
        with jax.profiler.TraceAnnotation("serve_forward"):
            out = self.server.infer(self.requests.batch(len(self.calls)))
            out.block_until_ready()
        self.calls.append((t, self.clock.now()))
        tokens = self.served_fn(out)
        tokens.block_until_ready()
        self.tokens.append(tokens)
        return out


# -- the cell ---------------------------------------------------------------

def plan(c: dict, mix: dict):
    """The system's plan for the pair under the mix: its solution (the
    served batch size ``bs`` and ``tau_tr``) and the training batch."""
    import jax
    from repro.core import problem as P
    from repro.core.device_model import (DeviceModel,
                                         workload_from_model_config)
    from repro.core.scheduler import Fulcrum
    t, s = c["train"], c["serve"]
    w_tr = workload_from_model_config(system_config(t["arch"], t), "train",
                                      tokens_per_sample=t["seq_len"])
    w_in = workload_from_model_config(system_config(s["arch"], s), "infer",
                                      tokens_per_sample=s["text_tokens"])
    with jax.profiler.TraceAnnotation("plan"):
        found = Fulcrum(DeviceModel()).solve_concurrent(
            w_tr, w_in, P.ConcurrentProblem(
                mix["power_w"], mix["latency_budget_s"], mix["rate_per_s"]))
    if found is None:
        raise RuntimeError("no feasible plan for the pair")
    return found.solution, w_tr.train_bs


class Runner:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float,
                 log=print):
        self.c, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.log = log
        self.t_arch = reference(config["train"]["arch"])
        self.s_arch = reference(config["serve"]["arch"])

    # set-up: everything the window needs, warmed
    def setup(self) -> None:
        import jax
        from repro.core.simulate import ArrivalTrace
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.runtime.interleave_runtime import (InterleaveConfig,
                                                      ManagedInterleaveRuntime)
        from repro.runtime.serving import BatchInferenceServer
        from repro.runtime.train_loop import Trainer

        t, s, mix = self.c["train"], self.c["serve"], self.mix
        sol, self.batch = plan(self.c, mix)
        bs = self.bs = sol.bs
        self.log(f"plan pm={sol.pm} bs={sol.bs} tau_tr={sol.tau_tr}; "
                 f"train batch={self.batch}x{t['seq_len']}")
        seq_in = s["vision_tokens"] + s["text_tokens"]

        self.arrivals = traffic.poisson_blocks(mix, self.seconds, self.seed,
                                               bs)
        n_req = self.arrivals.size
        requests = Requests(s, self.seed, n_req, bs)

        # the server, holding the benchmark's weights
        server = BatchInferenceServer(system_config(s["arch"], s),
                                      seq_len=seq_in, bs=bs)
        server.params = None
        gc.collect()
        server.params = make_weights(self.s_arch, s, self.seed, 3)
        served_fn = jax.jit(served_tokens)
        served_fn(server.infer(requests.batch(0))).block_until_ready()

        # the trainer and the runtime, which profiles the training step on
        # the trainer's own first weights
        o = t["optimizer"]
        trainer = Trainer(system_config(t["arch"], t), self.batch,
                          t["seq_len"], AdamWConfig(
                              lr=o["lr"], b1=o["b1"], b2=o["b2"],
                              eps=o["eps"], weight_decay=o["weight_decay"],
                              grad_clip=o["grad_clip"],
                              warmup_steps=o["warmup_steps"],
                              total_steps=o["total_steps"],
                              min_lr_ratio=o["min_lr_ratio"]))
        rows = make_rows(t, self.seed, TRAIN_ROWS, self.batch)
        trainer.data = iter(rows[i % TRAIN_ROWS] for i in range(10 ** 9))
        clock = SpanClock()
        self.tr = TrainerSpans(trainer, clock)
        self.sv = ServerSpans(server, requests, clock, served_fn)
        self.clock = clock
        self.window_trace = ArrivalTrace(self.arrivals, float(self.seconds),
                                         "poisson")
        self.runtime = ManagedInterleaveRuntime(
            self.tr, self.sv,
            InterleaveConfig(arrival_rate=mix["rate_per_s"], infer_bs=bs,
                             latency_budget=mix["latency_budget_s"],
                             duration=self.seconds),
            trace=self.window_trace, clock=clock)
        self.log(f"measured train step {self.runtime.t_tr * 1e3:.1f} ms; "
                 f"{n_req} requests in {n_req // bs} batches")

        # then the benchmark's weights and rows, from the seed, and the
        # first steps from them through the runtime's own run
        trainer.params = trainer.opt_state = None
        gc.collect()
        trainer.params = make_weights(self.t_arch, t, self.seed, 4)
        trainer.opt_state = init_opt_state(trainer.params)
        trainer.data = iter(rows[i % TRAIN_ROWS] for i in range(10 ** 9))
        self._first_steps(trainer)
        gc.collect()
        self.flops_train = self.t_arch.train_flops(t, self.batch,
                                                   t["seq_len"])
        self.flops_infer = self.s_arch.forward_flops(s, bs, seq_in)

    def _first_steps(self, trainer) -> None:
        """The trainer's first ``CHECK_STEPS`` steps from the seed, driven
        by ``runtime.run`` over a short trace of one batch, due when
        ``CHECK_SLACK`` more steps than those would fit before it; with the
        readings the reference is compared on, taken after the first step
        and after the last of them."""
        import jax
        from repro.core.simulate import ArrivalTrace
        t = self.c["train"]
        step_fn, losses = trainer.step_fn, []
        b1 = t["optimizer"]["b1"]
        norms = jax.jit(_leaf_norms)
        np.asarray(norms(trainer.opt_state["m"]))    # compiled before the run

        def recording(params, opt_state, batch):
            out = step_fn(params, opt_state, batch)
            losses.append(out[2]["loss"])
            return out

        def after(n: int) -> None:
            if n == 1:   # the clipped gradient the optimizer got
                self.grad_norms = np.asarray(
                    norms(trainer.opt_state["m"])) / (1 - b1)
            if n == CHECK_STEPS:
                self.change_norms = change_norms(self.t_arch, t, self.seed,
                                                 4, trainer.params)

        due = (CHECK_STEPS + CHECK_SLACK) * self.runtime.t_tr
        self.runtime.trace = ArrivalTrace(np.full(self.bs, due), due, "check")
        trainer.step_fn, self.tr.after_step = recording, after
        try:
            self.clock.start()
            self.runtime.run()
        finally:
            trainer.step_fn, self.tr.after_step = step_fn, None
            self.runtime.trace = self.window_trace
        if len(losses) < CHECK_STEPS:
            raise RuntimeError(f"the check run made {len(losses)} training "
                               f"steps, fewer than {CHECK_STEPS}")
        self.losses = [float(x) for x in losses[:CHECK_STEPS]]
        self.log(f"check run: {len(losses)} training steps before its batch")
        self.tr.steps.clear()
        self.sv.calls.clear()
        self.sv.tokens.clear()

    # the measured window
    def window(self) -> None:
        self.tr.steps.clear()
        self.clock.start()
        t0 = time.perf_counter()
        self.report = self.runtime.run()
        self.window_s = time.perf_counter() - t0

    def end_to_end(self) -> tuple[dict, int, int]:
        """All end-to-end numbers this kind can give; the harness keeps
        those the cell reports."""
        bs = self.bs
        n_batches = self.arrivals.size // bs
        attempted = n_batches * bs
        served = len(self.sv.calls) * bs
        lat = [end - self.arrivals[k * bs + i]
               for k, (_, end) in enumerate(self.sv.calls) for i in range(bs)]
        tokens = len(self.tr.steps) * self.batch * self.c["train"]["seq_len"]
        out = {"infer_p95_ms": 1e3 * nearest_rank(lat, 0.95),
               "train_tokens_per_s": tokens / self.window_s}
        self.log(f"window {self.window_s:.3f} s: served {served}/{attempted}; "
                 f"training steps {len(self.tr.steps)}; p95 "
                 f"{out['infer_p95_ms']:.1f} ms")
        self.unserved = attempted - served
        return out, attempted, attempted - served

    def layer_context(self) -> dict:
        bs = self.bs
        lags = [start - self.arrivals[k * bs + bs - 1]
                for k, (start, _) in enumerate(self.sv.calls)]
        return {"infer_lag_s": lags, "flops": {
                    "train": self.flops_train, "infer": self.flops_infer},
                "programs": {"train": "jit_train_step",
                             "infer": "jit__lambda"},
                "train_steps": len(self.tr.steps),
                "infer_calls": len(self.sv.calls)}

    def free(self) -> None:
        self.served = [np.asarray(x) for x in self.sv.tokens]
        self.served_n = len(self.sv.calls) * self.bs
        del self.runtime, self.tr, self.sv
        gc.collect()

    # the comparison with the plain references
    def check(self) -> Readings:
        t, s = self.c["train"], self.c["serve"]
        ref = reference_train(self.t_arch, t, self.seed, self.batch)
        vals = compare_train(
            {"losses": self.losses, "grad_norms": self.grad_norms,
             "change_norms": self.change_norms}, ref)
        pick = sample_requests(self.seed, self.served_n)
        served = np.concatenate(self.served)[pick]
        vals["token_gap"] = served_gap(self.s_arch, s, self.seed,
                                       self.arrivals.size, pick, served)
        vals["unserved_requests"] = self.unserved
        return Readings(vals, dict(self.c["limits"]))


def served_tokens(logits):
    """The token a forward serves at each position: its argmax."""
    import jax.numpy as jnp
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sample_requests(seed: int, n_served: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 7])
    return np.sort(rng.choice(n_served, min(SAMPLE_REQUESTS, n_served),
                              replace=False))


# -- references, run after the window, once the system's state is freed ----

def reference_train(arch, t: dict, seed: int, batch: int,
                    prec: str = "f32", rows_kept: int = 1) -> dict:
    """The first ``CHECK_STEPS`` steps of plain AdamW on the same weights
    and rows. ``rows_kept`` < 1 trains on that share of each minibatch's
    rows (a planted fault: part of the batch left out)."""
    import jax
    import jax.numpy as jnp
    o = t["optimizer"]
    params = make_weights(arch, t, seed, 4)
    rows = make_rows(t, seed, CHECK_STEPS, batch)
    keep = max(1, int(round(batch * rows_kept)))

    @jax.jit
    def grads(p, b):
        loss, g = jax.value_and_grad(lambda q: arch.loss(q, b, t, prec))(p)
        return loss, ref_adamw.clipped(g, o)

    upd = jax.jit(lambda p, m, v, g, step, lr: ref_adamw.update(
        p, m, v, g, o, step, lr), static_argnums=(4,),
        donate_argnums=(0, 1, 2))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
    m, v = zeros(params), zeros(params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(CHECK_STEPS):
            b = jax.tree.map(lambda x: x[:keep], rows[i])
            loss, g = grads(params, b)
            if i == 0:
                first = np.asarray(jax.jit(_leaf_norms)(g))
            losses.append(float(loss))
            params, m, v = upd(params, m, v, g, i + 1,
                               ref_adamw.lr_at(o, i + 1))
            del g
        del m, v
        change = change_norms(arch, t, seed, 4, params)
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def _worst_leaf(prog: np.ndarray, ref: np.ndarray, moved) -> float:
    floor = np.median(ref[moved])
    den = np.maximum(ref, floor)
    return float(np.max((np.abs(prog - ref) / den)[moved]))


def compare_train(prog: dict, ref: dict) -> dict:
    """Loss: worst relative gap over the steps. Gradient and change: the
    worst leaf's gap of norms against the larger of its reference norm and
    the median leaf's. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left out
    of the change."""
    g_ref = np.asarray(ref["grad_norms"])
    moved = g_ref >= 1e-3 * np.median(g_ref)
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": float(loss_gap),
            "grad_gap": _worst_leaf(np.asarray(prog["grad_norms"]), g_ref,
                                    np.ones_like(moved)),
            "change_gap": _worst_leaf(np.asarray(prog["change_norms"]),
                                      np.asarray(ref["change_norms"]), moved)}


def reference_logits(arch, s: dict, seed: int, n_req: int, pick, prec: str):
    import jax
    params = make_weights(arch, s, seed, 3)
    batch = Requests(s, seed, n_req, 1).pick(pick)
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda p, b: arch.logits(p, b, s, prec))(params, batch)


def served_gap(arch, s: dict, seed: int, n_req: int, pick,
               served: np.ndarray) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over every position of the sampled requests."""
    import jax.numpy as jnp
    ref = reference_logits(arch, s, seed, n_req, pick, "f32")
    got = jnp.take_along_axis(ref, jnp.asarray(served)[..., None], -1)[..., 0]
    return float(jnp.max(jnp.max(ref, -1) - got))


def control(config: dict, mix: dict, seed: int, seconds: float,
            log=print) -> dict:
    """The readings of the control and of the planted faults, each put in
    the system's place and compared with the float32 references as a run
    is: the references in float8 (the control), the training step on half
    of each minibatch's rows, and one served token altered."""
    import jax.numpy as jnp
    t, s = config["train"], config["serve"]
    ta, sa = reference(t["arch"]), reference(s["arch"])
    sol, batch = plan(config, mix)
    n_req = traffic.poisson_blocks(mix, seconds, seed, sol.bs).size
    ref = reference_train(ta, t, seed, batch)
    out = {"sound_reference": compare_train(ref, ref)}
    out["control_fp8"] = compare_train(
        reference_train(ta, t, seed, batch, prec="fp8"), ref)
    out["fault_half_batch"] = compare_train(
        reference_train(ta, t, seed, batch, rows_kept=0.5), ref)
    pick = sample_requests(seed, n_req)
    hi = reference_logits(sa, s, seed, n_req, pick, "f32")
    lo = reference_logits(sa, s, seed, n_req, pick, "fp8")
    best = jnp.max(hi, -1)
    gap = lambda tok: float(jnp.max(best - jnp.take_along_axis(
        hi, tok[..., None], -1)[..., 0]))
    top = jnp.argmax(hi, -1)
    out["control_fp8"]["token_gap"] = gap(jnp.argmax(lo, -1))
    out["fault_token_altered"] = {"token_gap": gap(
        top.at[0, -1].set((top[0, -1] + 1) % s["vocab_size"]))}
    out["sound_reference"]["token_gap"] = gap(top)
    return out
