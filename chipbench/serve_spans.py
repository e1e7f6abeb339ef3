"""The serving loop's own spans in a traced window, and the device's idle
time put down to what the host was doing.

``repro/serve/loop.py`` marks each step of ``ServeLoop.generate`` with a
profiler span (``serve.*``); they land in the trace's host plane beside
the harness's ``bench.*`` annotations, JAX's compile and
garbage-collection events and the runtime's issue of each program call.
``reduce`` reads them from the same ``jax.profiler.ProfileData`` that
``tracing.reduce`` reads, over the same window, and puts each idle
instant of each chip down to one label (``idle_by``):

1. ``compile``, where JAX's ``backend_compile_and_load`` is open;
2. ``gc``, where ``PythonRefManager::CollectGarbage`` is open;
3. the innermost open ``serve.*`` span;
4. the innermost open ``bench.*`` annotation;
5. ``outside``.

The host and device timestamps share a clock only to about a
millisecond, and drift apart over a window: a v5e trace shows programs
starting on the device 1.2 ms before the host issued them. So the host's
events are moved onto the device's clock, batch by batch (``skews_ns``),
before idle time is put down to them; where the batches cannot be paired
with their prefills, nothing is moved and the readings below give None.

The harness hands its per-layer readers ``tracing.reduce``'s result
alone, so these readings are not among the benchmark's metrics;
``record_trace.py`` prints them for a traced window.
"""

from __future__ import annotations

import bisect
import statistics
from dataclasses import dataclass, field

from chipbench import tracing

# the serving loop's spans, by the names repro/serve/loop.py gives them
SERVE = "serve."
GENERATE, UPLOAD, PREFILL, CACHE, STEP, CONCAT = (
    SERVE + n for n in ("generate", "upload", "prefill", "cache", "step",
                        "concat"))
# host events that take idle time before any span does: event -> (rank,
# label); the serve.* spans rank 2, the bench.* annotations 3
STALLS = {"backend_compile_and_load": (0, "compile"),
          "PythonRefManager::CollectGarbage": (1, "gc")}
# the runtime's host event for a program call issued to the device, and
# the program each batch starts with
LAUNCH = "tpu::System::Execute=>IssueSequencedEvent"
PREFILL_PROGRAM = "jit_prefill_step"
# an idle interval this long is listed with the label that held most of it
LONG_GAP_NS = 30e6


@dataclass(frozen=True)
class Span:
    """A ``serve.*`` host span: times in the trace's nanoseconds, and the
    integer stats the program gave it."""
    start_ns: float
    end_ns: float
    name: str
    stats: dict


@dataclass
class Attributed:
    window_s: float                  # as tracing.reduce's
    busy_s: float                    # as tracing.reduce's
    chips: int
    spans: list = field(default_factory=list)      # [Span], host clock
    program_starts: dict = field(default_factory=dict)  # name -> [ns]
    idle_by: dict = field(default_factory=dict)    # label -> seconds
    skews_ns: list = field(default_factory=list)   # host - device, a batch
    prefill_issues_ns: list = field(default_factory=list)  # host, a batch
    long_gaps: list = field(default_factory=list)  # [(seconds, label)]


def _pieces(marks, t0, t1):
    """[t0, t1] cut wherever a mark (start, end, rank, label) opens or
    closes; each piece (start, end, label) takes the label of the open
    mark of least rank, the latest opened (the innermost) among equals,
    or "outside"."""
    cuts = sorted({t0, t1} | {x for s, e, _, _ in marks for x in (s, e)
                              if t0 < x < t1})
    marks = sorted(marks)
    out, open_, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(marks) and marks[i][0] <= a:
            open_.append(marks[i])
            i += 1
        open_ = [m for m in open_ if m[1] > a]
        top = min(open_, key=lambda m: (m[2], -m[0], m[1]), default=None)
        out.append((a, b, top[3] if top else "outside"))
    return out


def _skews(serve, issued, prefills):
    """Per batch, how far the host's clock runs ahead of the device's, in
    ns: the host's issue of the batch's prefill (the first runtime issue
    once its ``serve.prefill`` span opens) less the device start of that
    prefill, which starts when issued, the device being idle between the
    batches of a closed loop. [(serve.generate start, prefill issue,
    skew)], or [] where the spans and the prefill calls do not pair up."""
    gens = [x.start_ns for x in serve if x.name == GENERATE]
    opens = [x.start_ns for x in serve if x.name == PREFILL]
    if not opens or not len(gens) == len(opens) == len(prefills):
        return []
    out = []
    for g, p, start in zip(gens, opens, sorted(prefills)):
        i = bisect.bisect_left(issued, p)
        if i == len(issued):
            return []
        out.append((g, issued[i], issued[i] - start))
    return out


def _attribute(idle, pieces, into, scale):
    """Add each idle interval's overlap with each labelled piece to
    ``into[label]``, times ``scale``; both lists are sorted and disjoint."""
    j = 0
    for s, e in idle:
        while pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b, label = pieces[k]
            overlap = (min(b, e) - max(a, s)) * scale
            into[label] = into.get(label, 0.0) + overlap
            k += 1


def reduce(data) -> Attributed:
    """The serving loop's spans, each program call's device start and the
    idle time by host label, in the window that the harness annotations
    span."""
    window, devices, serve, marks, issued = [], [], [], [], []
    for plane in data.planes:
        if tracing.DEVICE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(tracing.PREFIX):
                        window.append((e.start_ns, e.end_ns))
                        marks.append((e.start_ns, e.end_ns, 3, e.name))
                    elif e.name.startswith(SERVE):
                        serve.append(Span(e.start_ns, e.end_ns, e.name,
                                          dict(e.stats)))
                        marks.append((e.start_ns, e.end_ns, 2, e.name))
                    elif e.name in STALLS:
                        marks.append((e.start_ns, e.end_ns,
                                      *STALLS[e.name]))
                    elif e.name == LAUNCH:
                        issued.append(e.start_ns)
    if not window or not devices:
        raise ValueError("the trace holds no harness annotation or no TPU")
    t0 = min(s for s, _ in window)
    t1 = max(e for _, e in window)
    out = Attributed(window_s=(t1 - t0) * 1e-9, busy_s=0.0,
                     chips=len(devices))
    out.spans = sorted((x for x in serve if x.end_ns > t0 and x.start_ns < t1),
                       key=lambda x: x.start_ns)
    idle = []
    for plane in devices:
        busy = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for e in line.events:
                    out.program_starts.setdefault(
                        tracing.program_name(e.name), []).append(e.start_ns)
            elif line.name == "XLA Ops":
                for e in line.events:
                    s, t = max(e.start_ns, t0), min(e.end_ns, t1)
                    if t > s:
                        busy.append((s, t))
        merged = tracing._union(busy)
        out.busy_s += sum(e - s for s, e in merged) * 1e-9 / len(devices)
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle.append([(s, e) for s, e in zip(edges[::2], edges[1::2])
                     if e > s])

    skews = _skews(out.spans, sorted(issued),
                   out.program_starts.get(PREFILL_PROGRAM, []))
    out.skews_ns = [k for _, _, k in skews]
    out.prefill_issues_ns = [i for _, i, _ in skews]
    opened = [g for g, _, _ in skews]

    def shift(s, e):
        """Host times on the device's clock, by the skew of the batch open
        at s (before the first batch, the first's)."""
        if not skews:
            return s, e
        d = skews[max(bisect.bisect_right(opened, s) - 1, 0)][2]
        return s - d, e - d
    pieces = _pieces([(*shift(s, e), rank, label)
                      for s, e, rank, label in marks], t0, t1)
    for chip in idle:
        _attribute(chip, pieces, out.idle_by, 1e-9 / len(devices))
        for s, e in chip:
            if e - s >= LONG_GAP_NS:
                held = {}
                _attribute([(s, e)], pieces, held, 1.0)
                out.long_gaps.append(((e - s) * 1e-9, max(held, key=held.get)))
    out.long_gaps.sort(reverse=True)
    return out


def serve_batches(trace, batches):
    """The window's ``serve.generate`` spans, in order, if the serving
    loop's spans agree with its own counters and with the harness's
    batches [(requests, prompt_len, new_tokens)]: one upload, prefill,
    cache and concat per batch, ``new_tokens - 1`` steps, each of them
    carrying its batch's id. Otherwise (no spans, or a mismatch) None."""
    gens = [x for x in trace.spans if x.name == GENERATE]
    shape = [(g.stats.get("requests"), g.stats.get("prompt_len"),
              g.stats.get("new_tokens")) for g in gens]
    if not gens or shape != [tuple(b) for b in batches]:
        return None
    want = {g.stats.get("batch"): {UPLOAD: 1, PREFILL: 1, CACHE: 1,
                                   CONCAT: 1, STEP: n - 1}
            for g, (_, _, n) in zip(gens, shape)}
    if len(want) != len(gens):
        return None
    for x in trace.spans:
        counts = want.get(x.stats.get("batch"))
        if x.name != GENERATE:
            if counts is None or x.name not in counts:
                return None
            counts[x.name] -= 1
    if any(n for counts in want.values() for n in counts.values()):
        return None
    return gens


def aligned_batches(trace, batches):
    """``serve_batches``, and only where each batch's host events could be
    moved onto the device's clock (``skews_ns``); else None, since
    ``idle_by`` would then rest on the raw host clock."""
    gens = serve_batches(trace, batches)
    if gens is None or len(trace.skews_ns) != len(gens):
        return None
    return gens


def serve_idle_share(trace, batches):
    """Share of the window, in %, in which the device was idle while the
    serving loop's host path held it: idle time put down to a ``serve.*``
    span (compile and garbage collection are counted apart)."""
    if aligned_batches(trace, batches) is None:
        return None
    idle = sum(s for label, s in trace.idle_by.items()
               if label.startswith(SERVE))
    return 100.0 * idle / trace.window_s


def prefill_wait_ms(trace, batches):
    """Host time from the call into ``generate`` until the runtime issues
    the batch's prefill to the chip (the upload and the prefill's
    dispatch), in ms, the mean over the window's batches. Both ends are
    on the host's clock."""
    gens = aligned_batches(trace, batches)
    if gens is None:
        return None
    waits = [i - g.start_ns for g, i in zip(gens, trace.prefill_issues_ns)]
    return sum(waits) / len(waits) * 1e-6


def summary(trace, batches) -> dict:
    """What a traced window shows of the serving loop's host path."""
    skews = trace.skews_ns
    return {
        "window_s": trace.window_s, "busy_s": trace.busy_s,
        "idle_by_span": sorted(([k, s] for k, s in trace.idle_by.items()),
                               key=lambda kv: -kv[1]),
        "long_gaps": [[label, s] for s, label in trace.long_gaps],
        "serve_idle_share": serve_idle_share(trace, batches),
        "prefill_wait_ms": prefill_wait_ms(trace, batches),
        "host_clock_ahead_us": [f(skews) * 1e-3 for f in (
            min, statistics.median, max)] if skews else None,
    }
