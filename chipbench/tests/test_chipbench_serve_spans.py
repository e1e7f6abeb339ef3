"""Idle time put down to the serving loop's spans (``serve_spans``), on
synthetic traces and on two traces recorded on one TPU v5e: the
harness's closed loop over mamba2-130m at batch 32, 256-token prompts and
8 new tokens, two batches, before and after the loop had spans."""

import dataclasses

import pytest

from chipbench import serve_spans, tracing
from chipbench.run import Context
from chipbench.spec import HERE, load_json, load_module
from chipbench.work import ssm

DATA = HERE / "tests" / "data"
TRACE = DATA / "mamba2-130m.gen-b32.xplane.pb.gz"
# recorded by chipbench/record_trace.py, with the serve.* spans
TRACE_SPANS = DATA / "mamba2-130m.gen-b32.spans.xplane.pb.gz"
BATCHES = [(32, 256, 8)] * 2
READINGS = [serve_spans.serve_idle_share, serve_spans.prefill_wait_ms]
LABELS = {"compile", "gc", "outside", "bench.prepare", "bench.generate",
          "bench.fetch"}


@pytest.fixture(scope="module", params=[TRACE, TRACE_SPANS],
                ids=["no-spans", "spans"])
def both(request):
    data = tracing.load(request.param)
    return tracing.reduce(data), serve_spans.reduce(data)


@pytest.fixture(scope="module")
def old():
    return serve_spans.reduce(tracing.load(TRACE))


@pytest.fixture(scope="module")
def spans():
    return serve_spans.reduce(tracing.load(TRACE_SPANS))


def test_window_and_busy_are_those_of_the_trace_reduction(both):
    reduced, attributed = both
    assert attributed.window_s == reduced.window_s
    assert attributed.busy_s == pytest.approx(reduced.busy_s, rel=1e-12)
    assert attributed.chips == reduced.chips
    assert {k: len(v) for k, v in attributed.program_starts.items()} == \
        {k: len(v) for k, v in reduced.programs.items()}


def test_idle_by_fills_the_idle_time(both):
    _, trace = both
    assert abs(sum(trace.idle_by.values()) -
               (trace.window_s - trace.busy_s)) < 1e-9
    assert set(trace.idle_by) <= LABELS | {
        serve_spans.GENERATE, serve_spans.UPLOAD, serve_spans.PREFILL,
        serve_spans.CACHE, serve_spans.STEP, serve_spans.CONCAT}


@pytest.mark.parametrize("reading", READINGS, ids=lambda f: f.__name__)
def test_readings_stay_silent_without_the_programs_spans(reading, old):
    # recorded before the serving loop had spans
    assert old.spans == [] and set(old.idle_by) <= LABELS
    assert reading(old, BATCHES) is None


def test_recorded_spans_follow_the_batches(spans):
    gens = serve_spans.serve_batches(spans, BATCHES)
    assert gens is not None and len(gens) == 2
    assert len(spans.program_starts["jit_serve_step"]) == 14
    assert len(spans.program_starts[serve_spans.PREFILL_PROGRAM]) == 2
    # each batch's host events were moved onto the device's clock
    assert len(spans.skews_ns) == len(spans.prefill_issues_ns) == 2


def test_serve_idle_share_is_part_of_the_idle_share(spans):
    ctx = Context(conf=load_json(HERE / "configs" / "mamba2-130m.json"),
                  work=ssm, trace=tracing.reduce(tracing.load(TRACE_SPANS)),
                  peaks=load_json(HERE / "peaks.json")["TPU v5 lite"],
                  batches=BATCHES)
    share = serve_spans.serve_idle_share(spans, BATCHES)
    assert 0 < share <= load_module("metrics", "idle_share.gen").read(ctx)


def test_prefill_wait_ms_is_positive(spans):
    gens = [x for x in spans.spans if x.name == serve_spans.GENERATE]
    prefills = [x for x in spans.spans if x.name == serve_spans.PREFILL]
    # each batch's prefill is issued inside its serve.prefill span
    for g, p, i in zip(gens, prefills, spans.prefill_issues_ns):
        assert g.start_ns < p.start_ns <= i < g.end_ns
    waits = [i - g.start_ns for g, i in zip(gens, spans.prefill_issues_ns)]
    value = serve_spans.prefill_wait_ms(spans, BATCHES)
    assert value > 0 and value == pytest.approx(sum(waits) / 2 * 1e-6)


@pytest.mark.parametrize("reading", READINGS, ids=lambda f: f.__name__)
def test_readings_stay_silent_without_clock_alignment(reading, spans):
    # as where the runtime's issue event is renamed or the prefills do not
    # pair up with the spans: idle_by would rest on the raw host clock
    trace = dataclasses.replace(spans, skews_ns=[], prefill_issues_ns=[])
    assert reading(trace, BATCHES) is None
    assert serve_spans.summary(trace, BATCHES)["host_clock_ahead_us"] is None


@pytest.mark.parametrize("reading", READINGS, ids=lambda f: f.__name__)
def test_readings_stay_silent_when_the_batches_do_not_match(reading, spans):
    assert reading(spans, BATCHES * 2) is None


def test_summary_of_the_recorded_window(spans):
    s = serve_spans.summary(spans, BATCHES)
    seconds = [x for _, x in s["idle_by_span"]]
    assert seconds == sorted(seconds, reverse=True)
    assert sum(seconds) == pytest.approx(spans.window_s - spans.busy_s)
    least, median, most = s["host_clock_ahead_us"]
    assert least <= median <= most
    assert s["prefill_wait_ms"] == serve_spans.prefill_wait_ms(spans,
                                                               BATCHES)


class _Event:
    def __init__(self, name, start, end, **stats):
        self.name, self.start_ns, self.end_ns = name, float(start), float(end)
        self.duration_ns = self.end_ns - self.start_ns
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, *events):
        self.name, self.events = name, list(events)


class _Plane:
    def __init__(self, name, *lines):
        self.name, self.lines = name, list(lines)


def _data(*planes):
    return type("Data", (), {"planes": list(planes)})


def test_idle_time_goes_to_the_label_that_takes_precedence():
    """Ops run at [0,10), [50,60) and [90,100) ns; the host's annotations
    and spans around them decide where each idle nanosecond goes."""
    device = _Plane("/device:TPU:0",
                    _Line("XLA Modules", _Event("jit_prefill_step(1)", 0, 10),
                          _Event("jit_serve_step(2)", 50, 60),
                          _Event("jit_serve_step(2)", 90, 100)),
                    _Line("XLA Ops", _Event("%a = f32[1] add()", 0, 10),
                          _Event("%b = f32[1] add()", 50, 60),
                          _Event("%c = f32[1] add()", 90, 100)))
    host = _Plane("/host:CPU", _Line(
        "python3",
        _Event("bench.generate", 0, 100),
        _Event("serve.generate", 5, 95, batch=1, requests=1, prompt_len=4,
               new_tokens=3),
        _Event("serve.step", 20, 40, batch=1, pos=4),
        _Event("backend_compile_and_load", 30, 45),
        _Event("PythonRefManager::CollectGarbage", 60, 70),
        _Event("serve.step", 70, 80, batch=1, pos=5),
        _Event("bench.fetch", 100, 120),
        _Event("bench.prepare", 130, 140)))
    data = _data(device, host)
    r = serve_spans.reduce(data)
    want = {"serve.generate": 10 + 5 + 10, "serve.step": 10 + 10,
            "compile": 15, "gc": 10, "bench.fetch": 20, "outside": 10,
            "bench.prepare": 10}
    assert r.idle_by == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(r.idle_by.values()) == pytest.approx(r.window_s - r.busy_s)
    assert [x.name for x in r.spans] == ["serve.generate", "serve.step",
                                         "serve.step"]
    assert r.spans[0].stats["new_tokens"] == 3
    assert r.program_starts == {"jit_prefill_step": [0.0],
                                "jit_serve_step": [50.0, 90.0]}
    assert r.long_gaps == []
    # the trace reduction keeps its harness labels
    assert sorted(label for _, label in tracing.reduce(data).gaps) == [
        "generate", "generate", "outside"]


def test_long_gaps_carry_the_label_that_held_most_of_them():
    ms = 1e6
    device = _Plane("/device:TPU:0", _Line(
        "XLA Ops", _Event("%a = f32[1] add()", 0, 1 * ms),
        _Event("%b = f32[1] add()", 41 * ms, 42 * ms),
        _Event("%c = f32[1] add()", 52 * ms, 53 * ms)))
    host = _Plane("/host:CPU", _Line(
        "python3",
        _Event("bench.fetch", 0, 53 * ms),
        _Event("PythonRefManager::CollectGarbage", 5 * ms, 30 * ms)))
    r = serve_spans.reduce(_data(device, host))
    assert r.long_gaps == [(pytest.approx(0.040), "gc")]
    assert r.idle_by == pytest.approx({"gc": 0.025, "bench.fetch": 0.025})


def _skewed(issues, chips=1):
    """Two batches whose prefills run at [0,10) and [50,60) ns on each
    device; the host issues them at ``issues`` on its own clock."""
    devices = [_Plane(f"/device:TPU:{n}",
                      _Line("XLA Modules",
                            _Event("jit_prefill_step(1)", 0, 10),
                            _Event("jit_prefill_step(1)", 50, 60)),
                      _Line("XLA Ops", _Event("%a = f32[1] add()", 0, 10),
                            _Event("%b = f32[1] add()", 50, 60)))
               for n in range(chips)]
    host = _Plane("/host:CPU", _Line(
        "python3",
        _Event("bench.generate", 1, 63),
        _Event("serve.generate", 1, 20, batch=1),
        _Event("serve.prefill", 2, 3, batch=1),
        _Event("serve.generate", 40, 62, batch=2),
        _Event("serve.upload", 41, 52, batch=2),
        _Event("serve.prefill", 52, 53, batch=2),
        *(_Event(serve_spans.LAUNCH, t, t + 1) for t in issues)))
    return serve_spans.reduce(_data(*devices, host))


def test_host_labels_move_onto_the_device_clock_batch_by_batch():
    # the host's clock runs 3 ns ahead at the first batch, 4 at the second
    r = _skewed(issues=[3, 54])
    assert r.skews_ns == [3, 4]
    want = {"serve.generate": 7 + 1 + 1, "bench.generate": 19,
            "serve.upload": 11, "serve.prefill": 1, "outside": 3}
    assert r.idle_by == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert sum(r.idle_by.values()) == pytest.approx(r.window_s - r.busy_s)
    # the spans themselves stay on the host's clock
    assert [x.start_ns for x in r.spans] == [1, 2, 40, 41, 52]
    assert r.prefill_issues_ns == [3, 54]


def test_no_shift_where_the_prefills_cannot_be_paired_with_issues():
    r = _skewed(issues=[])
    assert r.skews_ns == [] and r.prefill_issues_ns == []
    want = {"serve.generate": 10 + 1 + 2, "bench.generate": 20 + 1,
            "serve.upload": 9}
    assert r.idle_by == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    assert [x.start_ns for x in r.spans] == [1, 2, 40, 41, 52]


def test_no_shift_where_each_chip_runs_the_prefills():
    # two device planes hold each prefill twice: the batches cannot be
    # paired with their issues, so nothing is aligned
    r = _skewed(issues=[3, 54], chips=2)
    assert r.chips == 2
    assert r.skews_ns == [] and r.prefill_issues_ns == []
    assert sum(r.idle_by.values()) == pytest.approx(r.window_s - r.busy_s)
