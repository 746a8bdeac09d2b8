"""Reduce a profiler trace by the program's own names.

The serve path opens host spans (``jax.profiler.TraceAnnotation``) named
``engine.*`` and ``service.*``, and its step programs carry
``jax.named_scope`` names in each HLO operation's ``op_name``.  Beside
``trace.py``'s reduction, whose functions it reuses, this module keeps
each host event's thread and arguments and each device operation's
``op_name``, and gives:

* the durations of each program span that ends in the window;
* idle by span: every idle nanosecond of the device in the window put
  down to the innermost program span open on the engine's thread (the
  thread that carries ``engine.step``), ``none`` where none is open;
* device own time by innermost model scope, ``(no scope)`` for an
  operation whose ``op_name`` holds none;
* the device time of each execution of each step program;
* the per-layer numbers read from them (``METRICS``).

The trace does not carry an operation's ``op_name``: a TPU's operation
event is named after its HLO instruction (``%fusion.12 = bf16[..] ...``)
and lies inside its program's execution on the device's ``XLA Modules``
line (``jit__step(<fingerprint>)``).  ``op_names_from_hlo`` maps
(program, instruction, result shape) to the ``op_name`` in the compiled
HLO text of the programs the window ran, which the caller takes after
the window.
"""
from __future__ import annotations

import collections
import dataclasses
import re
import statistics
import sys
from typing import (Callable, Dict, Iterable, List, Mapping, Optional,
                    Tuple)

from bench.harness import trace as tr

PROGRAM_SPANS = ("engine.", "service.")
ENGINE_SPAN = "engine.step"
NO_SPAN = "none"
# the model scopes of the serve path (``train/step.py``, ``models/lm.py``,
# ``models/blocks.py``, the engine's sampler), outermost first
SCOPES = ("decode_step", "prefill_chunk", "sample", "embed", "layers",
          "final", "attn.qkv", "attn.kv_append", "attn.core", "attn.out",
          "mlp", "moe")
NO_SCOPE = "(no scope)"
# where ``trace.read_profile`` looks: each TPU's operations and the host
DEVICE_PLANE, OPS_LINE, HOST_PLANE = "/device:TPU:", "XLA Ops", "/host:CPU"
MODULE_LINE = "XLA Modules"
# the prefill-chunk step's program: ``train/step.py``'s ``chunk_step``
CHUNK_PROGRAM = "jit_chunk_step"
_HLO_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
# an HLO instruction's name and result shape, before its opcode
_HEAD = r'(?:ROOT )?%?([^\s=]+) = (.+?) [a-z][\w\-]*\('
_HLO_OP = re.compile(r'^\s*' + _HEAD + r'.*?op_name="([^"]*)"', re.M)
_EVENT = re.compile(_HEAD)
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class HostEvent(tr.Event):
    thread: int = 0                   # the host line (one per thread)
    args: Dict[str, str] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class DeviceOp(tr.Event):
    op_name: str = ""


@dataclasses.dataclass
class SpanProfile(tr.Profile):
    # device plane -> executions of its step programs (XLA Modules line)
    programs: Dict[str, List[tr.Event]] = dataclasses.field(
        default_factory=dict)


OpNames = Mapping[Tuple[str, ...], str]


def read_spans(path: str, op_names: Optional[OpNames] = None
               ) -> SpanProfile:
    """``trace.read_profile``'s events, each host event with its thread
    and arguments, each device operation with its ``op_name`` (looked up
    in ``op_names``), and each device's program executions."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, List[tr.Event]] = {}
    programs: Dict[str, List[tr.Event]] = {}
    host: List[tr.Event] = []
    for plane in data.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE)
        is_host = plane.name.startswith(HOST_PLANE)
        if not (is_dev or is_host):
            continue
        ops: List[Tuple[object, float, float]] = []
        for i, line in enumerate(plane.lines):
            if is_dev and line.name == MODULE_LINE:
                programs[plane.name] = sorted(
                    (tr.Event(_FINGERPRINT.sub("", e.name), e.start_ns,
                              e.start_ns + e.duration_ns)
                     for e in line.events), key=lambda e: e.start_ns)
            dev_line = is_dev and line.name == OPS_LINE
            if not (dev_line or is_host):
                continue
            for e in line.events:
                end = e.start_ns + e.duration_ns
                if dev_line:
                    ops.append((e, e.start_ns, end))
                if is_host:
                    # arguments only where a reader looks: program spans
                    args = _stats(e) if e.name.startswith(
                        PROGRAM_SPANS) else {}
                    host.append(HostEvent(e.name, e.start_ns, end,
                                          thread=i, args=args))
        if ops:
            devices[plane.name] = _named_ops(
                ops, programs.get(plane.name, []), op_names or {})
    host.sort(key=lambda e: e.start_ns)
    return SpanProfile(devices=devices, host=host, programs=programs)


def _stats(event) -> Dict[str, str]:
    return {k: str(v) for k, v in event.stats}


def _named_ops(ops, programs: List[tr.Event], op_names: OpNames
               ) -> List[DeviceOp]:
    """Each operation with the ``op_name`` of its (program, instruction,
    result shape), else of its (program, instruction); its program is
    the program execution that holds it."""
    ops.sort(key=lambda x: x[1])
    out: List[DeviceOp] = []
    j = 0
    for e, start, end in ops:
        while j < len(programs) and programs[j].end_ns <= start:
            j += 1
        module = (programs[j].name if j < len(programs)
                  and programs[j].start_ns <= start else "")
        name = ""
        m = _EVENT.match(e.name)
        if m:
            key = (module, m.group(1))
            name = op_names.get(key + (m.group(2),), op_names.get(key, ""))
        out.append(DeviceOp(e.name, start, end, op_name=name))
    return out


def op_names_from_hlo(texts: Iterable[str]) -> Dict[Tuple[str, ...], str]:
    """``(program, instruction, result shape)`` and ``(program,
    instruction)`` -> ``op_name``, from compiled HLO texts
    (``Compiled.as_text()``)."""
    out: Dict[Tuple[str, ...], str] = {}
    for text in texts:
        m = _HLO_MODULE.search(text)
        module = m.group(1) if m else ""
        for op, shape, name in _HLO_OP.findall(text):
            out[(module, op, shape)] = name
            out[(module, op)] = name
    return out


def scope_of(op_name: str) -> str:
    """The innermost model scope named in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def _program(e: tr.Event) -> bool:
    return e.name.startswith(PROGRAM_SPANS)


def span_seconds(profile: SpanProfile, lo: float, hi: float
                 ) -> Dict[str, List[float]]:
    """Durations (seconds) of the program spans that end in the window,
    by name, in the order they started."""
    out: Dict[str, List[float]] = collections.defaultdict(list)
    for e in profile.host:
        if _program(e) and lo < e.end_ns <= hi:
            out[e.name].append((e.end_ns - e.start_ns) / 1e9)
    return dict(out)


def engine_thread(profile: SpanProfile) -> Optional[int]:
    for e in profile.host:
        if e.name == ENGINE_SPAN:
            return e.thread
    return None


def innermost(spans: Iterable[tr.Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """``(start, end, name)`` pieces that cover ``[lo, hi]``, each named
    after the innermost of the (nested) spans open in it, ``NO_SPAN``
    where none is."""
    out: List[Tuple[float, float, str]] = []
    stack: List[tr.Event] = []
    at = lo

    def cut(upto: float) -> None:
        nonlocal at
        upto = min(upto, hi)
        if upto > at:
            out.append((at, upto, stack[-1].name if stack else NO_SPAN))
            at = upto

    for e in sorted(spans, key=lambda e: (e.start_ns, -e.end_ns)):
        while stack and stack[-1].end_ns <= e.start_ns:
            cut(stack[-1].end_ns)
            stack.pop()
        cut(e.start_ns)
        stack.append(e)
    while stack:
        cut(stack[-1].end_ns)
        stack.pop()
    cut(hi)
    return out


def idle_by_span(profile: SpanProfile, lo: float, hi: float
                 ) -> Dict[str, float]:
    """Idle seconds of the first device in the window, by the innermost
    program span open on the engine's thread; empty where the trace has
    no device or no ``engine.step`` span."""
    thread = engine_thread(profile)
    if not profile.devices or thread is None:
        return {}
    evs = profile.devices[sorted(profile.devices)[0]]
    edges = [lo] + [x for s, t in tr.busy_intervals(evs, lo, hi)
                    for x in (s, t)] + [hi]
    idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    pieces = innermost([e for e in profile.host
                        if _program(e) and e.thread == thread], lo, hi)
    out: Dict[str, float] = collections.defaultdict(float)
    j = 0
    for s, t in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < t:
            a, b, name = pieces[k]
            out[name] += (min(b, t) - max(a, s)) / 1e9
            k += 1
    return dict(out)


def scope_seconds(profile: SpanProfile, lo: float, hi: float
                  ) -> Dict[str, float]:
    """Device own time (seconds, averaged over devices) by each
    operation's innermost model scope."""
    total: Dict[str, float] = collections.defaultdict(float)
    k = max(len(profile.devices), 1)
    for evs in profile.devices.values():
        named = [tr.Event(scope_of(e.op_name), e.start_ns, e.end_ns)
                 for e in evs]
        for scope, sec in tr.self_seconds(named, lo, hi).items():
            total[scope] += sec / k
    return dict(total)


def program_seconds(profile: SpanProfile, lo: float, hi: float
                    ) -> Dict[str, List[float]]:
    """Device seconds of each execution of each step program that ends in
    the window, by program, on every device."""
    out: Dict[str, List[float]] = collections.defaultdict(list)
    for evs in profile.programs.values():
        for e in evs:
            if lo < e.end_ns <= hi:
                out[e.name].append((e.end_ns - e.start_ns) / 1e9)
    return dict(out)


def summarize(profile: SpanProfile) -> Dict:
    """The window, busy seconds, and the span, idle, scope and program
    tables."""
    lo, hi = profile.window()
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": tr.busy_seconds(profile, lo, hi),
        "spans": span_seconds(profile, lo, hi),
        "idle_by_span": idle_by_span(profile, lo, hi),
        "scopes": scope_seconds(profile, lo, hi),
        "programs": program_seconds(profile, lo, hi),
    }


def log_tables(summary: Dict) -> None:
    """The span, program, idle and scope tables as ``[breakdown]``
    lines on standard error."""
    for table, kind in (("spans", "span"), ("programs", "program")):
        for name, secs in sorted(summary[table].items()):
            print(f"[breakdown] {kind} {name} n={len(secs)} "
                  f"total_s={sum(secs):.6f} "
                  f"median_ms={1e3 * statistics.median(secs):.6f}",
                  file=sys.stderr)
    for table in ("idle_by_span", "scopes"):
        for name, sec in sorted(summary[table].items(), key=lambda x: -x[1]):
            print(f"[breakdown] {table} {name} {sec:.6f}", file=sys.stderr)
    sys.stderr.flush()


# -- per-layer numbers: each reads the summary, None where it finds nothing


def _median_ms(trace: Optional[Dict], table: str, name: str
               ) -> Optional[float]:
    secs = (trace or {}).get(table, {}).get(name)
    return 1e3 * statistics.median(secs) if secs else None


def decode_step_ms(trace: Optional[Dict]) -> Optional[float]:
    """Median duration of the ``engine.decode`` spans ending in the
    window: building the inputs, the step, the fetch and the tokens'
    bookkeeping.  Layer: model step.  Moves serve_tok_s."""
    return _median_ms(trace, "spans", "engine.decode")


def prefill_chunk_ms(trace: Optional[Dict]) -> Optional[float]:
    """Median device time of the prefill-chunk program's executions
    (``CHUNK_PROGRAM``) ending in the window.  Not the ``engine.prefill``
    span: the chunk is dispatched without a wait, and its device time
    lands in the next fetch.  Layer: model step.  Moves itl_p95_ms."""
    return _median_ms(trace, "programs", CHUNK_PROGRAM)


def host_work(span: str) -> bool:
    """Whether a span is the engine's host work: every ``engine.*`` span
    but the fetches (which wait on the device), and ``service.take``."""
    return ((span.startswith("engine.") and not span.endswith(".fetch"))
            or span == "service.take")


def idle_host(trace: Optional[Dict]) -> Optional[float]:
    """Share of the window (%) in which the device is idle while the
    engine's thread is in host work (``host_work``).  Layer: serve
    engine.  Moves serve_tok_s."""
    idle = (trace or {}).get("idle_by_span")
    if not idle or trace["window_s"] <= 0:
        return None
    return 100.0 * sum(s for n, s in idle.items() if host_work(n)) \
        / trace["window_s"]


def layer_loop_overhead(trace: Optional[Dict]) -> Optional[float]:
    """Share of the window (%) in device own time of the operations whose
    innermost scope is ``layers`` itself: inside the layer loop, outside
    every block's scope (the loop's slicing and stacking).  Layer: model
    step.  Moves serve_tok_s."""
    scopes = (trace or {}).get("scopes")
    if not scopes or set(scopes) <= {NO_SCOPE} or trace["window_s"] <= 0:
        return None
    return 100.0 * scopes.get("layers", 0.0) / trace["window_s"]


METRICS: Dict[str, Callable[[Optional[Dict]], Optional[float]]] = {
    "decode_step_ms.serve_tok_s": decode_step_ms,
    "prefill_chunk_ms.itl_p95_ms": prefill_chunk_ms,
    "idle_host.serve_tok_s": idle_host,
    "layer_loop_overhead.serve_tok_s": layer_loop_overhead,
}
