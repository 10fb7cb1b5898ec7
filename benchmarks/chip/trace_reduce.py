"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
and the result's ``device``/``breakdown`` keys read.

- Device ops are the events of each ``/device:TPU:<n>`` plane's ``XLA Ops``
  line.  Control-flow ops (``while``, ``conditional``, ``call``) span their
  bodies, whose ops are events of their own, so they are left out of the
  per-op times (the union below is the same with or without them).
- Busy time is the union of op intervals inside the traced window, averaged
  over the devices; idle is the window less busy.
- A kernel's time is the sum of the durations of the ops named
  ``%<scope>.<op>`` or ``%<scope>.<op>.<n>``: the program wraps every
  kernel in the named scope ``repro_kernel.<op>``, and the TPU compiler
  names the custom call after it.
- Idle gaps are the stretches between device ops, each attributed to the
  host span (a ``TraceAnnotation`` of the harness, on the profiler's clock)
  that covers its midpoint, or ``"no host span"``, and to the device
  program (``XLA Modules`` line) that it falls inside or else ended last
  before it: ``bench.tick after jit_decode_step`` is host work between a
  decode step and the next device call, ``inside jit_decode_step`` a stall
  of the device within that program.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

KERNEL_SCOPE = "repro_kernel"
_CONTROL = re.compile(r"^%(while|conditional|call)(\.|\s|$)")
_SHORT = re.compile(r"^(%?[^\s=]+)")


def short_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    m = _SHORT.match(event_name)
    return m.group(1) if m else event_name


def op_family(short: str) -> str:
    """Drop the instance suffix: ``%copy.338`` -> ``%copy``."""
    return re.sub(r"(\.\d+)+$", "", short)


@dataclass
class Reduction:
    window_ns: Tuple[float, float]
    busy_ns: float                       # averaged over devices
    n_devices: int
    op_ns: Dict[str, float]              # op family -> summed device time
    kernel_ns: Dict[str, float]          # kernel op name -> summed time
    kernel_calls: Dict[str, int]
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def breakdown(self, n: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        idle: Dict[str, float] = defaultdict(float)
        for span, dur in self.gaps:
            idle[span] += dur
        top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in top_idle]}


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _kernel_of(short: str) -> Optional[str]:
    prefix = "%" + KERNEL_SCOPE + "."
    if not short.startswith(prefix):
        return None
    return re.sub(r"\.\d+$", "", short[len(prefix):])


def _module_name(name: str) -> str:
    """``jit_decode_step(8655...)`` -> ``jit_decode_step``."""
    return name.split("(", 1)[0]


def reduce_events(device_events: Dict[str, Sequence[Tuple[str, float, float]]],
                  host_spans: Sequence[Tuple[str, float, float]],
                  window_ns: Tuple[float, float],
                  modules: Optional[Dict[str, Sequence[Tuple[str, float, float]]]] = None
                  ) -> Reduction:
    """The reduction from plain event lists: ``device_events`` maps a device
    to ``(name, start_ns, duration_ns)`` op events, ``modules`` to its
    program events; ``host_spans`` are the harness's annotations
    ``(name, start_ns, duration_ns)``; ``window_ns`` is the traced window on
    the same clock."""
    lo, hi = window_ns
    op_ns: Dict[str, float] = defaultdict(float)
    kernel_ns: Dict[str, float] = defaultdict(float)
    kernel_calls: Dict[str, int] = defaultdict(int)
    busy_total = 0.0
    gaps: List[Tuple[str, float]] = []
    spans = sorted((s, s + d, n) for n, s, d in host_spans)
    for dev, events in device_events.items():
        mods = sorted((s, s + d, _module_name(n))
                      for n, s, d in (modules or {}).get(dev, ()))
        mod_starts = [m[0] for m in mods]
        ivs = []
        for name, start, dur in events:
            end = start + dur
            if end <= lo or start >= hi:
                continue
            s, e = max(start, lo), min(end, hi)
            ivs.append((s, e))
            short = short_name(name)
            if _CONTROL.match(short):
                continue
            op_ns[op_family(short)] += e - s
            k = _kernel_of(short)
            if k is not None:
                kernel_ns[k] += e - s
                kernel_calls[k] += 1
        merged = _union(ivs)
        busy_total += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                mid = 0.5 * (g0 + g1)
                owner = "no host span"
                for s, e, n in spans:
                    if s <= mid < e:
                        owner = n
                # the program that started last before the gap
                j = bisect.bisect_right(mod_starts, g0) - 1
                if j >= 0:
                    where = " inside " if mods[j][1] > mid else " after "
                    owner += where + mods[j][2]
                gaps.append((owner, g1 - g0))
    n = max(len(device_events), 1)
    return Reduction(window_ns=window_ns, busy_ns=busy_total / n,
                     n_devices=len(device_events), op_ns=dict(op_ns),
                     kernel_ns=dict(kernel_ns),
                     kernel_calls=dict(kernel_calls), gaps=gaps)


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str, window_span: str):
    """Device op events and host annotation spans from a trace file, and the
    traced window: from the start of the first host span named
    ``window_span`` to the end of the last one."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_events: Dict[str, list] = {}
    modules: Dict[str, list] = {}
    host_spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    evs = [(e.name, e.start_ns, e.duration_ns)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        device_events[plane.name] = evs
                    else:
                        modules[plane.name] = evs
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host_spans.append((e.name, e.start_ns,
                                           e.duration_ns))
    marks = [(s, s + d) for n, s, d in host_spans if n == window_span]
    if not marks:
        raise ValueError(f"no host span {window_span!r} in {path}")
    window = (min(s for s, _ in marks), max(e for _, e in marks))
    return device_events, modules, host_spans, window


def reduce_xplane(path: str, window_span: str = "bench.tick") -> Reduction:
    device_events, modules, host_spans, window = read_xplane(path,
                                                             window_span)
    return reduce_events(device_events, host_spans, window, modules)
