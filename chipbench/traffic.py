"""The one traffic generator. A mix is a JSON file under ``traffic/`` whose
parameters this module reads; no mix has code of its own.

Request arrivals (``"arrivals": "poisson_blocks"``): exponential gaps at
``rate_per_s``, drawn once from the mix's own ``draw_seed`` and cut into
blocks of one minibatch each; the run's ``--seed`` only permutes the
blocks after the first, which stays first because it alone has no forward
before it. So every seed serves the same set of batch-forming intervals,
in another order, and the seed changes which requests wait behind which,
not how much work the window holds. Only whole blocks that end inside the
window are kept: a trailing partial batch is never formed and is not
offered.

Fleet windows (``"arrivals": "fleet_windows"``): the aggregate rate of each
control window, ``rate_per_device`` x fleet size x the multipliers in turn,
for as many windows as ``windows_per_s`` x the run's seconds.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str, root: Path = DIR) -> dict:
    path = root / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} (looked for {path})")
    return json.loads(path.read_text())


def poisson_blocks(mix: dict, seconds: float, seed: int, bs: int
                   ) -> np.ndarray:
    """Arrival times (s from the window's start) of every request offered."""
    rate = float(mix["rate_per_s"])
    rng = np.random.default_rng(int(mix["draw_seed"]))
    n = int(rate * seconds + 10 * math.sqrt(rate * seconds) + 10)
    gaps = rng.exponential(1.0 / rate, n)
    blocks = gaps[: n - n % bs].reshape(-1, bs)
    nb = int(np.searchsorted(np.cumsum(blocks.sum(1)), seconds, side="right"))
    blocks = blocks[:nb]
    rest = np.random.default_rng(seed).permutation(max(nb - 1, 0))
    order = np.concatenate([[0], 1 + rest])[:nb]
    return np.cumsum(blocks[order].ravel())


def fleet_rates(mix: dict, seconds: float, n_devices: int) -> list[float]:
    n = max(1, round(float(mix["windows_per_s"]) * seconds))
    mult = mix["multipliers"]
    return [float(mix["rate_per_device"]) * n_devices * mult[i % len(mult)]
            for i in range(n)]


def poisson_window(rate: float, duration: float, seed: int) -> np.ndarray:
    """One window of a seeded Poisson process, the draw ``serve_fleet``
    makes for each control window (exponential gaps from
    ``default_rng(seed)``, extended until past the end, then cut)."""
    if rate <= 0.0:
        return np.empty(0)
    rng = np.random.default_rng(seed)
    mean = rate * duration
    n = max(8, int(mean + 6.0 * math.sqrt(mean) + 8))
    t = np.cumsum(rng.exponential(1.0 / rate, n))
    while t.size and t[-1] < duration:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate,
                                                                 n))])
    return t[t < duration]
