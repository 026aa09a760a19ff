"""Reduction of a profiler trace to device busy, idle, per-op and collective time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a small
plain structure, ``Trace``: per device the intervals of its operations, and
the host spans that the harness annotated.  Everything after ``load`` works
on that structure alone, so the tests check it on a recorded trace kept
beside them as JSON.

Times are nanoseconds on the profiler's clock, which it shares between the
host and the devices.
"""

from __future__ import annotations

import json
import re
from dataclasses import dataclass, field
from pathlib import Path

# The line of a device plane that holds one event per executed operation.
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|send|recv", re.I)


@dataclass
class Trace:
    ops: dict[int, list[tuple[str, int, int]]] = field(default_factory=dict)  # dev -> (name, t0, t1)
    spans: list[tuple[str, int, int]] = field(default_factory=list)           # host (name, t0, t1)

    def to_json(self) -> str:
        return json.dumps({"ops": {str(k): v for k, v in self.ops.items()}, "spans": self.spans})

    @classmethod
    def from_json(cls, blob: str) -> "Trace":
        d = json.loads(blob)
        return cls({int(k): [tuple(e) for e in v] for k, v in d["ops"].items()},
                   [tuple(s) for s in d["spans"]])


def load(log_dir: str, span_names: set[str]) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(str(files[-1]))
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                tr.ops.setdefault(int(m.group(2)), []).extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                tr.spans.extend(
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events if e.name in span_names)
    return tr


# ------------------------------------------------------------ interval sets
def union(intervals) -> list[tuple[int, int]]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[int]] = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover) -> list[tuple[int, int]]:
    """Parts of ``intervals`` (disjoint, sorted) that ``cover`` (disjoint, sorted) leaves."""
    out, j = [], 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > cur:
                out.append((cur, cover[k][0]))
            cur = max(cur, cover[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy, lo: int, hi: int) -> list[tuple[int, int]]:
    """Idle intervals of ``[lo, hi)`` around the disjoint, sorted ``busy``."""
    return subtract([(lo, hi)], busy)


# ------------------------------------------------------------- reductions
@dataclass
class Reduction:
    window_s: float
    busy_s: list[float]                 # per device, inside the window
    exposed_collective_s: list[float]   # per device: collective time with no compute
    top_ops: list[tuple[str, float]]    # by total seconds, averaged over devices
    idle_by_span: list[tuple[str, float]]   # longest idle gaps of device 0, by host span

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def _strip(name: str) -> str:
    """Group an op's trace name by its HLO name without the numeric suffix."""
    return re.sub(r"[.]\d+$", "", name)


def reduce(tr: Trace, lo: int, hi: int, devices: list[int], top: int = 10) -> Reduction:
    if not devices or any(d not in tr.ops for d in devices):
        raise ValueError(f"trace has devices {sorted(tr.ops)}, need {devices}")
    busy, exposed, by_op = [], [], {}
    busy0 = []
    for d in devices:
        ops = [(n, a, b) for n, a, b in tr.ops[d] if min(b, hi) > max(a, lo)]
        merged = clip(union((a, b) for _, a, b in ops), lo, hi)
        busy.append(total(merged) / 1e9)
        if d == devices[0]:
            busy0 = merged
        coll = union((a, b) for n, a, b in ops if COLLECTIVE.search(n))
        comp = union((a, b) for n, a, b in ops if not COLLECTIVE.search(n))
        exposed.append(total(clip(subtract(coll, comp), lo, hi)) / 1e9)
        for n, a, b in ops:
            key = _strip(n)
            by_op[key] = by_op.get(key, 0) + (min(b, hi) - max(a, lo)) / 1e9 / len(devices)
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(busy0, lo, hi), key=lambda g: g[0] - g[1])[:top]
    spans = sorted(tr.spans, key=lambda s: s[1])
    idle_by_span = [(_span_during(spans, a, b), (b - a) / 1e9) for a, b in idle]
    return Reduction((hi - lo) / 1e9, busy, exposed, top_ops, idle_by_span)


def _span_during(spans, a: int, b: int) -> str:
    """The host span that overlaps ``[a, b)`` most, or "other"."""
    best, name = 0, "other"
    for n, s0, s1 in spans:
        if s0 >= b:
            break
        ov = min(s1, b) - max(s0, a)
        if ov > best:
            best, name = ov, n
    return name
