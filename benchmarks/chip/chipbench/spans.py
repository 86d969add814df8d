"""The serving plane's own spans in a device trace, set against the
device's idle time.

The program marks its host steps with ``jax.profiler.TraceAnnotation``:
``plane.job`` for each piece of work the farm worker thread runs,
``hhe.device_wait`` for the wait on the device inside it, and others
that no reader here needs.  They land on their thread's line of a host
plane, under their plain names, on the clock the device planes use.  A
step that can wait on the device, as these two can, is two instant
marks, ``<name>`` where it starts and ``<name>.end`` where it ends,
which ``steps`` pairs into intervals.

Intervals are lists of ``[start_ns, end_ns)`` pairs, merged and in
order, as ``chipbench.trace.union`` returns them.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from chipbench.trace import Trace, union

JOB = "plane.job"
DEVICE_WAIT = "hhe.device_wait"
END = ".end"


def steps(line, name: str) -> list:
    """Merged intervals of the marked steps ``name`` on ``line``: each from
    the start of a ``name`` mark to the end of the ``name.end`` mark that
    closes it.  A mark with no partner, cut off where the trace starts or
    stops, is left out."""
    marks = sorted([(int(t), 0, int(t)) for n, t in
                    zip(line.names, line.start_ns) if n == name]
                   + [(int(t), 1, int(t + d)) for n, t, d in
                      zip(line.names, line.start_ns, line.dur_ns)
                      if n == name + END])
    open_, starts, durs = [], [], []
    for _, is_end, t in marks:
        if not is_end:
            open_.append(t)
        elif open_:
            a = open_.pop()
            starts.append(a)
            durs.append(t - a)
    return union(np.asarray(starts, np.int64), np.asarray(durs, np.int64))


def intersect(a: list, b: list) -> list:
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: list, b: list) -> list:
    """The parts of ``a`` that lie in no interval of ``b``."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while lo < hi and k < len(b) and b[k][0] < hi:
            if b[k][0] > lo:
                out.append([lo, b[k][0]])
            lo = max(lo, b[k][1])
            k += 1
        if lo < hi:
            out.append([lo, hi])
    return out


def length_s(iv: list) -> float:
    return sum(b - a for a, b in iv) * 1e-9


def device_gaps(ops) -> list:
    """The gaps between one device's merged op intervals (the idle time
    ``trace.idle_gaps`` charges to host events)."""
    iv = union(ops.start_ns, ops.dur_ns)
    return [[iv[i][1], iv[i + 1][0]] for i in range(len(iv) - 1)
            if iv[i + 1][0] > iv[i][1]]


@dataclasses.dataclass
class Worker:
    """The farm worker's thread: when it ran a job, and when, inside one,
    it waited on the device."""

    jobs: list
    waits: list

    @property
    def host(self) -> list:
        """Inside a job and not waiting on the device."""
        return subtract(self.jobs, self.waits)


def worker(trace: Trace) -> Optional[Worker]:
    """The host line that holds ``plane.job`` spans, or None where no
    line does (a program without the spans)."""
    for ln in trace.host_lines():
        if JOB in ln.names:
            jobs = steps(ln, JOB)
            return Worker(jobs, intersect(steps(ln, DEVICE_WAIT), jobs))
    return None


def idle_split(trace: Trace, w: Worker) -> tuple:
    """(seconds, seconds) of device idle time during which the worker ran
    host steps of a job, and during which it ran no job; averaged over
    the devices that ran something, as ``device_idle_pct`` averages."""
    ops = trace.device_ops()
    if not ops:
        return 0.0, 0.0
    host = w.host
    in_host = free = 0.0
    for ln in ops:
        gaps = device_gaps(ln)
        in_host += length_s(intersect(gaps, host))
        free += length_s(subtract(gaps, w.jobs))
    return in_host / len(ops), free / len(ops)
