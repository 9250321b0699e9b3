"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device planes
are named ``/device:TPU:<n>``; on each, the line ``XLA Ops`` holds one event
per operation that ran and ``XLA Modules`` one event per launch of a
compiled program. Host spans are the benchmark's own
``jax.profiler.TraceAnnotation``s, on the host plane and on the same clock.

``reduce_events`` is the whole arithmetic, on plain tuples, so a test can
check it against a hand count; ``load`` only pulls those tuples out of the
file.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Iterable, Sequence

Event = tuple[str, float, float]       # (name, start s, end s)

WINDOW_SPAN = "window"                 # host span around the measured window
_ID_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                      # union of op intervals, mean over chips
    modules: dict                      # program name -> [device seconds]
    ops: list                          # [(op name, total s)], longest first
    gaps: list                         # [(host span, idle s)], longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, name: str) -> list[float]:
        """Device seconds of each launch of the program of this name
        (``jit_train_step`` and the like), its launch id left off."""
        return list(self.modules.get(name, []))


def _union(intervals: Iterable[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def reduce_events(chips: Sequence[dict], host: Sequence[Event],
                  top: int = 10) -> Reduced:
    """``chips``: one dict per device with ``ops`` and ``modules`` event
    lists; ``host``: the benchmark's host spans, one of them ``window``.
    Everything is clipped to the window."""
    wins = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(wins)}")
    lo, hi = wins[0]
    spans: dict = defaultdict(list)
    for n, a, b in host:
        if n != WINDOW_SPAN:
            spans[n].append((a, b))
    busy, op_tot, modules, gaps = [], defaultdict(float), defaultdict(list), []
    for chip in chips:
        ivs = []
        for n, a, b in chip["ops"]:
            c = _clip(a, b, lo, hi)
            if c:
                ivs.append(c)
                op_tot[short_name(n)] += c[1] - c[0]
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged))
        for n, a, b in chip["modules"]:
            c = _clip(a, b, lo, hi)
            if c:
                modules[_ID_SUFFIX.sub("", n)].append(c[1] - c[0])
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(a, b, spans), b - a))
    if not chips:
        raise ValueError("the trace holds no device plane")
    ops = sorted(op_tot.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    return Reduced(hi - lo, sum(busy) / len(chips), dict(modules), ops,
                   gaps[:top])


def short_name(op: str) -> str:
    """An operation's name without the instruction text a TPU trace gives
    it: ``%fusion.572 = s32[512,64]... fusion(...)`` -> ``fusion.572``.
    Operations of one name in several compiled variants of a program are
    counted together."""
    return op.split(" = ", 1)[0].lstrip("%")


def _label(a: float, b: float, spans: dict) -> str:
    """The host span that covers most of the idle interval [a, b]."""
    best, best_cover = "no_span", 0.0
    for name, ivs in spans.items():
        cover = sum(max(0.0, min(b, y) - max(a, x)) for x, y in ivs)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def load(path: str, host_names: Sequence[str]) -> Reduced:
    """Read an ``.xplane.pb`` and reduce it; ``host_names`` are the host
    spans to keep (the window's and those idle gaps are labelled by)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    keep = set(host_names) | {WINDOW_SPAN}
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            chips.append({
                key: [(e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)
                      for e in lines[name].events]
                for key, name in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))
                if name in lines})
            chips[-1].setdefault("modules", [])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in ln.events if e.name in keep)
    return reduce_events(chips, host)
