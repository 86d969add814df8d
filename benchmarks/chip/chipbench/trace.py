"""Device trace: capture with JAX's profiler, read the ``.xplane.pb`` it
writes, and reduce it to intervals.

Planes whose name starts with ``/device:`` are devices; on a TPU each has
an ``XLA Ops`` line (one event per HLO op or kernel) and an ``XLA
Modules`` line (one event per program run).  Host planes hold the
runtime's and the threads' own events.  Times are nanoseconds on one
clock for every plane.
"""

from __future__ import annotations

import dataclasses
import gzip
import pathlib
import re
from collections import defaultdict

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(log_dir: str) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@dataclasses.dataclass
class Line:
    plane: str
    line: str
    names: list
    start_ns: np.ndarray     # int64
    dur_ns: np.ndarray       # int64

    def select(self, pattern: str) -> "Line":
        rx = re.compile(pattern)
        keep = [i for i, n in enumerate(self.names) if rx.search(n)]
        return Line(self.plane, self.line, [self.names[i] for i in keep],
                    self.start_ns[keep], self.dur_ns[keep])

    def total_s(self) -> float:
        return float(self.dur_ns.sum()) * 1e-9

    def __len__(self) -> int:
        return len(self.names)


class Trace:
    """Every line of every plane, as arrays."""

    def __init__(self, lines: list):
        self.lines = lines

    @classmethod
    def from_bytes(cls, data: bytes) -> "Trace":
        from jax.profiler import ProfileData

        if data[:2] == b"\x1f\x8b":
            data = gzip.decompress(data)
        prof = ProfileData.from_serialized_xspace(data)
        lines = []
        for plane in prof.planes:
            for line in plane.lines:
                names, starts, durs = [], [], []
                for ev in line.events:
                    names.append(ev.name)
                    starts.append(ev.start_ns)
                    durs.append(ev.duration_ns)
                lines.append(Line(plane.name, line.name, names,
                                  np.asarray(starts, np.float64).astype(
                                      np.int64),
                                  np.asarray(durs, np.float64).astype(
                                      np.int64)))
        return cls(lines)

    @classmethod
    def from_file(cls, path) -> "Trace":
        return cls.from_bytes(pathlib.Path(path).read_bytes())

    # ---- selection ---------------------------------------------------------
    def devices(self) -> list:
        return sorted({ln.plane for ln in self.lines
                       if ln.plane.startswith("/device:")})

    def line(self, plane: str, name: str) -> Line:
        for ln in self.lines:
            if ln.plane == plane and ln.line == name:
                return ln
        return Line(plane, name, [], np.zeros(0, np.int64),
                    np.zeros(0, np.int64))

    def device_ops(self) -> list:
        """The ``XLA Ops`` line of each device that ran something."""
        out = [self.line(d, OPS_LINE) for d in self.devices()]
        return [ln for ln in out if len(ln)]

    def device_modules(self) -> list:
        out = [self.line(d, MODULES_LINE) for d in self.devices()]
        return [ln for ln in out if len(ln)]

    def host_lines(self) -> list:
        return [ln for ln in self.lines
                if not ln.plane.startswith("/device:") and len(ln)]


def union(starts: np.ndarray, durs: np.ndarray) -> list:
    """Merged [start, end) intervals of a set of events, in order."""
    if len(starts) == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], starts[order] + durs[order]
    out = [[int(s[0]), int(e[0])]]
    for a, b in zip(s[1:].tolist(), e[1:].tolist()):
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_s(line: Line) -> float:
    """Seconds in which at least one event of ``line`` runs."""
    return sum(b - a for a, b in union(line.start_ns, line.dur_ns)) * 1e-9


def top_ops(lines: list, k: int = 10) -> list:
    """[name, seconds] of the k ops that took most device time."""
    tot = defaultdict(int)
    for ln in lines:
        for name, d in zip(ln.names, ln.dur_ns.tolist()):
            tot[name] += d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def idle_gaps(trace: Trace, k: int = 10) -> list:
    """[host activity, seconds] of device idle time, by what the host was
    doing: each gap between device ops is charged to the host event
    (shorter than the gap's span plus a millisecond, so that whole-thread
    umbrella events do not swallow every gap) that overlaps it most."""
    ops = trace.device_ops()
    if not ops:
        return []
    iv = union(ops[0].start_ns, ops[0].dur_ns)
    gaps = [(iv[i][1], iv[i + 1][0]) for i in range(len(iv) - 1)
            if iv[i + 1][0] > iv[i][1]]
    if not gaps:
        return []
    hs, he, hn = [], [], []
    for ln in trace.host_lines():
        hs.append(ln.start_ns)
        he.append(ln.start_ns + ln.dur_ns)
        hn.extend(ln.names)
    hs = np.concatenate(hs) if hs else np.zeros(0, np.int64)
    he = np.concatenate(he) if he else np.zeros(0, np.int64)
    order = np.argsort(hs, kind="stable")
    hs, he = hs[order], he[order]
    hn = [hn[i] for i in order.tolist()]
    longest = int((he - hs).max()) if len(hs) else 0
    tot = defaultdict(int)
    for a, b in gaps:
        lo = int(np.searchsorted(hs, a - longest, side="left"))
        hi = int(np.searchsorted(hs, b, side="left"))
        best, best_ov = "no host event", 0
        for j in range(lo, hi):
            s, e = int(hs[j]), int(he[j])
            if e - s > (b - a) + 1_000_000:
                continue
            ov = min(b, e) - max(a, s)
            if ov > best_ov:
                best, best_ov = hn[j], ov
        tot[best] += b - a
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in top]
