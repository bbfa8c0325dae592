"""From a profiler trace (`*.xplane.pb`) to the few numbers the
per-layer readers need. Read with `jax.profiler.ProfileData` and
nothing else; checked on testdata/ by check_benchmark.py.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Modules` has one event per run of a
compiled program, named `jit_<function>(<fingerprint>)`, and whose line
`XLA Ops` has one event per HLO operation inside it, named by the
whole HLO instruction (`%tick.244 = f32[64,16384]{...} custom-call(f32[64,4096]{...}
%multiply_add_fusion.71, s8[4096,16384]{...} %params..., ...),
custom_call_target="tpu_custom_call", ...`). A Pallas kernel is such a
custom call; nothing in the event names the kernel function, so a
kernel is told by its target and its operands' shapes (short_name).
Asynchronous copies have a line of their own, `Async XLA Ops`, and are
not counted as the device being busy.

All times here are seconds; event tuples are (name, start, duration).
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import gzip
import re
import statistics
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Chip:
    modules: list      # (name without fingerprint, start, duration)
    ops: list          # (short_name, start, duration)


@dataclasses.dataclass
class Trace:
    chips: list        # one Chip per device plane that ran something

    # -- the window --------------------------------------------------
    @functools.cached_property
    def window(self) -> tuple[float, float]:
        """From the first to the last device operation on any chip."""
        starts = [c.ops[0][1] for c in self.chips]
        ends = [max(s + d for _, s, d in c.ops) for c in self.chips]
        return min(starts), max(ends)

    @property
    def window_s(self) -> float:
        a, b = self.window
        return b - a

    @functools.cached_property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(busy_union([(s, d) for _, s, d in c.ops])
                   for c in self.chips) / len(self.chips)

    # -- by name -----------------------------------------------------
    def module_durations(self, name: str) -> list[float]:
        """Device time of every run of module `name`, all chips."""
        return [d for c in self.chips for n, _, d in c.modules if n == name]

    def module_median_ms(self, name: str) -> float | None:
        """Median device time of one run of module `name`, in ms; None
        where it never ran."""
        runs = self.module_durations(name)
        return 1e3 * statistics.median(runs) if runs else None

    def op_seconds(self, pattern: str) -> tuple[float, int]:
        """(summed device time, count) of the operations whose short
        name matches `pattern`, averaged over the chips."""
        rx = re.compile(pattern)
        hits = [d for c in self.chips for n, _, d in c.ops if rx.search(n)]
        return sum(hits) / len(self.chips), len(hits) // len(self.chips)

    def top_ops(self, k: int = 10) -> list[list]:
        """The k kinds of operation that took most device time on chip
        0, summed by short name."""
        total: dict[str, float] = {}
        for n, _, d in self.chips[0].ops:
            total[n] = total.get(n, 0.0) + d
        return [[n, t] for n, t in
                sorted(total.items(), key=lambda kv: -kv[1])[:k]]

    def gaps(self, k: int = 10) -> list[tuple[float, float, str, int]]:
        """The k longest idle gaps on chip 0: (start, seconds, the
        module whose operation ended the gap, that module's index among
        its own runs). A gap between two operations of one module run
        belongs to that run."""
        c = self.chips[0]
        spans = merged([(s, d) for _, s, d in c.ops])
        longest = sorted(((b0 - a1, a1, b0) for (_, a1), (b0, _)
                          in zip(spans, spans[1:])), reverse=True)[:k]
        mods = sorted(c.modules, key=lambda m: m[1])
        starts = [m[1] for m in mods]
        nth, seen = [], {}
        for n, _, _ in mods:
            nth.append(seen.get(n, 0))
            seen[n] = nth[-1] + 1
        out = []
        for seconds, a1, b0 in longest:
            i = bisect.bisect_right(starts, b0 + 1e-9) - 1
            out.append((a1, seconds, mods[i][0], nth[i]) if i >= 0
                       else (a1, seconds, "", -1))
        return out


def merged(intervals) -> list[tuple[float, float]]:
    """Union of (start, duration) intervals as sorted disjoint
    (start, end) spans."""
    spans: list[list[float]] = []
    for s, d in sorted(intervals):
        if spans and s <= spans[-1][1]:
            spans[-1][1] = max(spans[-1][1], s + d)
        else:
            spans.append([s, s + d])
    return [(a, b) for a, b in spans]


def busy_union(intervals) -> float:
    return sum(b - a for a, b in merged(intervals))


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


_SHAPE = r"[a-z0-9]+\[[0-9,]*\]"


def short_name(hlo: str) -> str:
    """An XLA Ops event is named by its whole HLO instruction. Kept is
    what tells one kind of work from another: the instruction's name
    without its number, its result's shape and, for a custom call, the
    target and the operands' shapes — `tick f32[64,16384] =
    tpu_custom_call(f32[64,4096], s8[4096,16384], f32[1,16384])`. All
    42 layers' calls of one shape then add up under one name."""
    m = re.match(r"%([^ ]+?)(?:\.\d+)? = \(?(" + _SHAPE + ")", hlo)
    if not m:
        return hlo[:80]
    name, shape = m.groups()
    target = re.search(r'custom_call_target="([^"]+)"', hlo)
    if target:
        args = hlo[hlo.index("custom-call(") + 12:]
        shapes = re.findall("(" + _SHAPE + r")[^ ]* %", args)
        return f"{name} {shape} = {target.group(1)}({', '.join(shapes)})"
    return f"{name} {shape}"


def load(path) -> Trace:
    """The trace in `path`, an .xplane.pb as the profiler writes it or
    the same gzipped (testdata/)."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        raw = gzip.decompress(raw)
    data = ProfileData.from_serialized_xspace(raw)
    chips = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        modules, ops = [], []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                modules = [(re.sub(r"\(.*\)$", "", e.name), e.start_ns * 1e-9,
                            e.duration_ns * 1e-9) for e in line.events]
            elif line.name == OPS_LINE:
                ops = [(short_name(e.name), e.start_ns * 1e-9,
                        e.duration_ns * 1e-9) for e in line.events]
        if ops:
            ops.sort(key=lambda o: o[1])
            chips.append(Chip(modules=modules, ops=ops))
    if not chips:
        raise ValueError(f"{path}: no device operation in the trace")
    return Trace(chips=chips)
