"""Reduce a profiler trace to the numbers the benchmark reports.

``read_profile`` pulls two kinds of events out of the ``.xplane.pb`` the
JAX profiler writes: operations on each device (the ``XLA Ops`` line of
every ``/device:TPU:<n>`` plane) and host activity (every line of the
``/host:CPU`` plane).  The measured window is the host span the harness
opens around it (``WINDOW_SPAN``), on the same clock.  From these:

* busy seconds: the union of the device's operation intervals inside
  the window, averaged over the chips used;
* the seconds of the operations whose name a reader selects (a kernel);
* the operations that took most time, and the longest idle gaps named
  after the host activity that covered most of each.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
# an HLO instruction's numeric suffix (``fusion.12``) names one instance
# of an operation in one program; the breakdown adds instances together
_SUFFIX = re.compile(r"(\.\d+)+$")


@dataclasses.dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Profile:
    devices: Dict[str, List[Event]]   # plane name -> operations, by start
    host: List[Event]                 # host activity, by start

    def window(self, span: str = WINDOW_SPAN) -> Tuple[float, float]:
        marks = [e for e in self.host if e.name == span]
        if not marks:
            raise ValueError(f"no {span!r} span in the trace")
        return marks[0].start_ns, marks[0].end_ns


def profiler_options():
    """Device and runtime events, no Python function tracing: the Python
    tracer would slow the host that the window measures."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_profile(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read_profile(path: str, device_prefix: str = "/device:TPU:",
                 device_lines: Sequence[str] = ("XLA Ops",),
                 host_prefix: str = "/host:CPU") -> Profile:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        is_dev = plane.name.startswith(device_prefix)
        is_host = plane.name.startswith(host_prefix)
        if not (is_dev or is_host):
            continue
        for line in plane.lines:
            dev_line = is_dev and line.name.startswith(tuple(device_lines))
            if not (dev_line or is_host):
                continue
            evs = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if dev_line:
                devices.setdefault(plane.name, []).extend(evs)
            if is_host:
                host.extend(evs)
    for evs in devices.values():
        evs.sort(key=lambda e: e.start_ns)
    host.sort(key=lambda e: e.start_ns)
    return Profile(devices=devices, host=host)


def _clipped(events: List[Event], lo: float, hi: float):
    for e in events:
        s, t = max(e.start_ns, lo), min(e.end_ns, hi)
        if t > s:
            yield e, s, t


def busy_intervals(events: List[Event], lo: float, hi: float
                   ) -> List[Tuple[float, float]]:
    """Union of the operations' intervals inside ``[lo, hi]``."""
    out: List[Tuple[float, float]] = []
    for _, s, t in sorted(((e, s, t) for e, s, t in _clipped(events, lo, hi)),
                          key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1] = (out[-1][0], t)
        else:
            out.append((s, t))
    return out


def busy_seconds(profile: Profile, lo: float, hi: float) -> float:
    """Busy seconds inside the window, averaged over the devices."""
    if not profile.devices:
        return 0.0
    per = [sum(t - s for s, t in busy_intervals(evs, lo, hi))
           for evs in profile.devices.values()]
    return sum(per) / len(per) / 1e9


def op_seconds(profile: Profile, lo: float, hi: float,
               match: Callable[[str], bool]) -> float:
    """Seconds of the operations whose kind (``op_label``) ``match``
    selects, averaged over devices."""
    if not profile.devices:
        return 0.0
    per = [sum(t - s for e, s, t in _clipped(evs, lo, hi)
               if match(op_label(e.name)))
           for evs in profile.devices.values()]
    return sum(per) / len(per) / 1e9


def op_label(name: str) -> str:
    """The operation's kind from its event name: the HLO instruction's
    name without ``%`` and its numeric suffix (``%fusion.12 = ...`` ->
    ``fusion``; a Pallas kernel is named after its jitted wrapper,
    ``%decode_attention_paged.1 = ...`` -> ``decode_attention_paged``)."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head)


def self_seconds(events: List[Event], lo: float, hi: float
                 ) -> Dict[str, float]:
    """Seconds per operation kind, each operation's own time without the
    operations nested inside it (a loop's body ops are listed inside the
    loop's own event)."""
    spans = sorted(_clipped(events, lo, hi), key=lambda x: (x[1], -x[2]))
    own: List[float] = []
    labels: List[str] = []
    stack: List[Tuple[float, int]] = []
    for e, s, t in spans:
        while stack and stack[-1][0] <= s:
            stack.pop()
        own.append(t - s)
        labels.append(op_label(e.name))
        if stack:
            own[stack[-1][1]] -= t - s
        stack.append((t, len(own) - 1))
    total: Dict[str, float] = collections.defaultdict(float)
    for lab, x in zip(labels, own):
        total[lab] += x / 1e9
    return total


def top_ops(profile: Profile, lo: float, hi: float, n: int = 10
            ) -> List[List]:
    """The ``n`` operation kinds that took most device time of their own
    (seconds, averaged over devices)."""
    total: Dict[str, float] = collections.defaultdict(float)
    k = max(len(profile.devices), 1)
    for evs in profile.devices.values():
        for lab, sec in self_seconds(evs, lo, hi).items():
            total[lab] += sec / k
    return [[name, sec] for name, sec in
            sorted(total.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(profile: Profile, lo: float, hi: float, n: int = 10,
              ignore: Sequence[str] = (WINDOW_SPAN,)) -> List[List]:
    """The ``n`` longest device idle gaps in the window (on the first
    device), each named after the innermost host activity that covers at
    least half of it (the shortest such span), else the one that covers
    most of it."""
    if not profile.devices:
        return []
    evs = profile.devices[sorted(profile.devices)[0]]
    busy = busy_intervals(evs, lo, hi)
    edges = [lo] + [x for s, t in busy for x in (s, t)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in profile.host if e.name not in ignore]
    out = []
    for s, t in gaps[:n]:
        best, cover, inner = "no host activity", 0.0, None
        for e in host:
            if e.start_ns >= t:
                break
            c = min(e.end_ns, t) - max(e.start_ns, s)
            if c <= 0:
                continue
            dur = e.end_ns - e.start_ns
            if 2 * c >= t - s and (inner is None or dur < inner[1]):
                inner = (e.name, dur)
            if c > cover:
                best, cover = e.name, c
        out.append([inner[0] if inner else best, (t - s) / 1e9])
    return out


def summarize(profile: Profile, match: Optional[Dict[str, Callable]] = None
              ) -> Dict:
    """Window, busy seconds, selected operation seconds and the breakdown."""
    lo, hi = profile.window()
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_seconds(profile, lo, hi),
        "ops_s": {k: op_seconds(profile, lo, hi, f)
                  for k, f in (match or {}).items()},
        "breakdown": {"device_ops": top_ops(profile, lo, hi),
                      "idle_gaps": idle_gaps(profile, lo, hi)},
    }
