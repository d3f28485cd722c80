"""The device trace of a steady stretch, and its reduction to numbers.

``Stretch`` runs the JAX profiler over a stretch of the window from a
thread of its own, so the serve call itself is not touched.  At the
start it writes one ``bench_anchor`` annotation at a known host
``perf_counter`` time, which puts the device's events and the serve
loop's tracer events on one clock: seconds since the serve call began.

``device_events`` reads the trace: per device plane, every operation
("XLA Ops") and every program execution ("XLA Modules").  ``reduce``
turns those and the tracer's events into busy time, time per operation
and per program, and the idle gaps named by what the host was doing.
The reduction is pure, so a recorded trace checks it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import threading
import time
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

ANCHOR = "bench_anchor"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


class Stretch:
    """Profile ``length`` seconds starting ``start_after`` seconds after
    ``begin()``, from a thread; ``join()`` waits for the trace to be
    written."""

    def __init__(self, out_dir: str, start_after: float, length: float):
        self.out_dir, self.start_after, self.length = \
            out_dir, start_after, length
        self.anchor_pc = self.t_begin = self.t_end = None
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def begin(self):
        self._thread.start()

    def _run(self):
        import jax
        try:
            time.sleep(self.start_after)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.anchor_pc = time.perf_counter()
            with jax.profiler.TraceAnnotation(ANCHOR):
                pass
            time.sleep(self.length)
            self.t_end = time.perf_counter()
            jax.profiler.stop_trace()
        except BaseException as e:          # re-raised by join()
            self.error = e

    def join(self):
        self._thread.join()
        if self.error is not None:
            raise self.error
        if self.t_end is None:
            raise RuntimeError("the profiled stretch never ran")

    def xplane(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        return found[-1]


def device_events(xplane_path: str, anchor_pc: float, origin_pc: float,
                  devices: List[int]) -> Dict[str, list]:
    """Operations ("XLA Ops") and program executions ("XLA Modules") on
    ``devices``, each as [name, start, duration, device] with times in
    seconds since ``origin_pc`` on the host's ``perf_counter`` clock.  An
    operation keeps the name the HLO gives it (``quant_matmul.61``), not
    its whole instruction text."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    anchor_ns = None
    planes = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in devices:
            planes.append((int(m.group(1)), plane))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANCHOR:
                        anchor_ns = ev.start_ns
    if anchor_ns is None or not planes:
        raise RuntimeError(
            f"trace without {'the anchor' if anchor_ns is None else ''} "
            f"{'' if planes else 'a device plane'}; planes: "
            f"{[p.name for p in pd.planes]}")
    shift = anchor_pc - origin_pc - anchor_ns * 1e-9
    out = {"ops": [], "modules": []}
    for dev, plane in planes:
        lines = {line.name: line for line in plane.lines}
        for key, lname in (("ops", OPS_LINE), ("modules", MODULES_LINE)):
            if lname not in lines:
                raise RuntimeError(f"{plane.name} has no line {lname!r}: "
                                   f"{sorted(lines)}")
            for ev in lines[lname].events:
                name = ev.name.split(" = ")[0].lstrip("%") if key == "ops" \
                    else ev.name
                out[key].append([name, ev.start_ns * 1e-9 + shift,
                                 ev.duration_ns * 1e-9, dev])
    return out


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], b))
        else:
            merged.append((a, b))
    return merged


def family(op_name: str) -> str:
    """``quant_matmul.61`` -> ``quant_matmul``."""
    return re.sub(r"\.\d+$", "", op_name)


def program(module_name: str) -> str:
    """``jit_packed_fn(1112...)`` -> ``packed_fn``."""
    return re.sub(r"^jit_|\(\d+\)$", "", module_name)


@dataclass
class Reduction:
    window_s: float
    busy_s: float                  # busy seconds, averaged over devices
    ops: List[list]                # leaf ops [start, name, start, dur, dev]
    modules: List[list]            # executions [name, start, dur, dev]
    gaps: List[Tuple[float, float]]       # idle intervals of the first device
    op_time: Dict[str, float] = field(default_factory=dict)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def executions(self, name: str) -> List[list]:
        """Executions of the programs whose name holds ``name``."""
        return [m for m in self.modules if name in m[0]]

    def ops_within(self, module: list, pattern: str) -> List[list]:
        """Operations inside one execution, on its device, whose name
        holds ``pattern``."""
        a, b = module[1], module[1] + module[2]
        lo = bisect.bisect_left(self.ops, [a])
        hi = bisect.bisect_right(self.ops, [b])
        return [o for o in (self.ops[i][1:] for i in range(lo, hi))
                if o[1] + o[2] <= b and o[3] == module[3] and pattern in o[0]]


def leaves(ops: List[list]) -> List[list]:
    """The operations that hold no other: a loop or call operation spans
    the operations of its body, which the trace lists beside it."""
    out = []
    by_dev = defaultdict(list)
    for o in ops:
        by_dev[o[3]].append(o)
    for dev_ops in by_dev.values():
        dev_ops.sort(key=lambda o: (o[1], -o[2]))
        for i, o in enumerate(dev_ops):
            nxt = dev_ops[i + 1] if i + 1 < len(dev_ops) else None
            if nxt is None or not (nxt[1] < o[1] + o[2]
                                   and nxt[1] + nxt[2] <= o[1] + o[2]):
                out.append(o)
    return out


def reduce(events: Dict[str, list], w0: float, w1: float) -> Reduction:
    """Busy time, time per operation and idle gaps of the window
    [w0, w1] (seconds since the serve call began).  Events are clipped
    to the window; only executions wholly inside it are kept.  Time per
    operation is keyed ``<program>:<operation family>`` and counts leaf
    operations only, so no second counts twice."""
    devs = sorted({o[3] for o in events["ops"]}) or [None]
    busy_by_dev = {}
    for dev in devs:
        clip = [(max(w0, s), min(w1, s + d))
                for _, s, d, dv in events["ops"] if dv == dev]
        busy_by_dev[dev] = union([(a, b) for a, b in clip if b > a])
    gaps, t = [], w0
    for a, b in busy_by_dev[devs[0]]:
        if a > t:
            gaps.append((t, a))
        t = b
    if t < w1:
        gaps.append((t, w1))
    inside = lambda e: e[1] >= w0 and e[1] + e[2] <= w1
    modules = sorted((m for m in events["modules"] if inside(m)),
                     key=lambda m: m[1])
    runs = defaultdict(list)                 # executions by device
    for m in sorted(events["modules"], key=lambda m: m[1]):
        runs[m[3]].append(m)
    starts = {dev: [m[1] for m in ms] for dev, ms in runs.items()}
    op_time = defaultdict(float)
    leaf = leaves(events["ops"])
    for name, s, d, dev in leaf:
        a, b = max(w0, s), min(w1, s + d)
        if b <= a:
            continue
        i = bisect.bisect_right(starts.get(dev, []), s) - 1
        m = runs[dev][i] if i >= 0 else None
        prog = program(m[0]) if m and s + d <= m[1] + m[2] else "no program"
        op_time[f"{prog}:{family(name)}"] += b - a
    busy = sum(b - a for iv in busy_by_dev.values() for a, b in iv)
    return Reduction(window_s=w1 - w0, busy_s=busy / len(devs),
                     ops=sorted([o[1]] + o for o in leaf if inside(o)),
                     modules=modules, gaps=gaps, op_time=dict(op_time))


class HostTimeline:
    """What the serve loop was doing at a given serve time, from the
    tracer's iteration records and device-dispatch spans."""

    def __init__(self, tracer_events: List[dict]):
        self.spans = sorted((e["t"], e["t"] + e["dur"], e["name"])
                            for e in tracer_events if e["kind"] == "span")
        self.iters = sorted((e["t"], e["t"] + e["dur"], e["idle"])
                            for e in tracer_events
                            if e["kind"] == "iteration")

    @staticmethod
    def _at(items, t):
        i = bisect.bisect_right(items, (t, float("inf"))) - 1
        return items[i] if i >= 0 and items[i][0] <= t <= items[i][1] \
            else None

    def activity(self, t: float) -> str:
        span = self._at(self.spans, t)
        if span is not None:
            return f"inside the {span[2]} dispatch call (launch, sync)"
        it = self._at(self.iters, t)
        if it is None:
            return "outside any serve-loop iteration"
        if it[2]:
            return "serve loop idle: no request to run"
        j = bisect.bisect_left(self.spans, (it[0],))
        span = self.spans[j] if j < len(self.spans) \
            and self.spans[j][0] <= it[1] else None
        if span is None:
            return "host in an iteration without a dispatch"
        if t < span[0]:
            return (f"host before the {span[2]} dispatch: arrivals, "
                    f"admission, planning, packing")
        return f"host after the {span[2]} dispatch: emits, retires"


def breakdown(red: Reduction, tracer_events: List[dict]) -> dict:
    """The ten operations that took most device time, and the idle time
    by what the host was doing, longest first."""
    ops = sorted(red.op_time.items(), key=lambda kv: -kv[1])[:10]
    host = HostTimeline(tracer_events)
    idle = defaultdict(float)
    count = defaultdict(int)
    for a, b in red.gaps:
        what = host.activity(0.5 * (a + b))
        idle[what] += b - a
        count[what] += 1
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[f"{n} ({count[n]} gaps)", s] for n, s in gaps]}
