"""Device trace of a run's window, and its reduction to busy time,
per-program and per-operation device time, and idle gaps labelled by the
harness annotation the host was in.

The reduction reads the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData``: the device planes ``/device:TPU:<n>`` carry
an "XLA Modules" line (one event per call of a compiled program) and an
"XLA Ops" line (one event per operation); the host plane carries the
harness's ``bench.*`` annotations on the same clock.
"""

from __future__ import annotations

import contextlib
import gzip
import re
from dataclasses import dataclass, field
from pathlib import Path

PREFIX = "bench."
DEVICE = re.compile(r"^/device:TPU:\d+$")


@dataclass
class Reduced:
    window_s: float                  # first to last harness annotation
    busy_s: float                    # union of device operations, per chip
    chips: int
    programs: dict = field(default_factory=dict)   # name -> [seconds]
    kernels: dict = field(default_factory=dict)    # name -> [seconds]
    ops: dict = field(default_factory=dict)        # label -> self seconds
    gaps: list = field(default_factory=list)       # [(seconds, label)]


@contextlib.contextmanager
def record(log_dir):
    """Trace what runs inside the block into ``log_dir``, without the
    Python tracer (it would time every Python call)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(PREFIX + name)


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path):
    """A trace file, plain or gzipped, as ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(
            gzip.decompress(path.read_bytes()))
    return ProfileData.from_file(str(path))


def program_name(event_name: str) -> str:
    """"jit_serve_step(1029...)" -> "jit_serve_step"."""
    return re.sub(r"\(\d+\)$", "", event_name)


def op_label(text: str) -> str:
    """An operation event is named by its HLO text; keep the instruction,
    its kind and its result type without layouts:
    "%convert.69 = bf16[24,2048,5632]{...} convert(f32..." ->
    "convert.69 convert bf16[24,2048,5632]"."""
    m = re.match(r"%(\S+) = (.*)", text, re.S)
    if not m:
        return text[:100]
    rest = re.sub(r"\{[^{}]*\}", "", m.group(2))
    depth, end = 0, 0
    for end, ch in enumerate(rest):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0 and ch in " )":
            break
    rtype = rest[:end + 1].strip()
    kind = re.match(r"\s*([\w-]+)", rest[end + 1:])
    return f"{m.group(1)} {kind.group(1) if kind else '?'} {rtype}"[:100]


def kernel_name(text: str):
    """Instruction name of a Pallas kernel call ("%ssd.3 = ...
    custom_call_target="tpu_custom_call"" -> "ssd"), else None."""
    if 'custom_call_target="tpu_custom_call"' not in text:
        return None
    m = re.match(r"%([A-Za-z_][\w-]*?)(\.\d+)? = ", text)
    return m.group(1) if m else None


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(spans, t):
    """The innermost harness annotation open at time t."""
    inside = [(e - s, name) for s, e, name in spans if s <= t < e]
    return min(inside)[1][len(PREFIX):] if inside else "outside"


def _self_times(events):
    """Each operation's duration less that of the operations nested in it
    (a while loop holds its body's operations)."""
    out, stack = [], []            # stack: [end, index into out]
    for e in sorted(events, key=lambda e: (e.start_ns, -e.duration_ns)):
        while stack and stack[-1][0] <= e.start_ns:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= e.duration_ns
        out.append([e.name, e.duration_ns])
        stack.append((e.end_ns, len(out) - 1))
    return out


def reduce(data) -> Reduced:
    """Busy time, programs, kernels, operations and idle gaps of the
    window that the harness annotations span."""
    spans, devices = [], []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            spans += [(e.start_ns, e.end_ns, e.name) for line in plane.lines
                      for e in line.events if e.name.startswith(PREFIX)]
    if not spans or not devices:
        raise ValueError("the trace holds no harness annotation or no TPU")
    t0 = min(s for s, _, _ in spans)
    t1 = max(e for _, e, _ in spans)
    out = Reduced(window_s=(t1 - t0) * 1e-9, busy_s=0.0, chips=len(devices))
    for plane in devices:
        busy = []
        for line in plane.lines:
            events = list(line.events)
            if line.name == "XLA Modules":
                for e in events:
                    out.programs.setdefault(program_name(e.name), []).append(
                        e.duration_ns * 1e-9)
            elif line.name == "XLA Ops":
                for e in events:
                    s, t = max(e.start_ns, t0), min(e.end_ns, t1)
                    if t > s:
                        busy.append((s, t))
                    k = kernel_name(e.name)
                    if k:
                        out.kernels.setdefault(k, []).append(
                            e.duration_ns * 1e-9)
                for name, ns in _self_times(events):
                    label = op_label(name)
                    out.ops[label] = out.ops.get(label, 0.0) + ns * 1e-9
        merged = _union(busy)
        out.busy_s += sum(e - s for s, e in merged) * 1e-9 / len(devices)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                out.gaps.append(((e - s) * 1e-9, _label(spans, (s + e) / 2)))
    out.gaps.sort(reverse=True)
    return out
