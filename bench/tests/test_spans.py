"""The program spans' reduction: on hand-made traces, and on a small trace of
a real `generate` recorded on the chip (`record_spans.py`)."""

from pathlib import Path

import pytest

from bench import spans, spec, trace
from bench.trace import Event, Trace

FIXTURE = Path(__file__).resolve().parent / "data" / "small_spans.xplane.pb"
CONTROL = ("serve.account", "serve.sync", "serve.control")
DISPATCH = ("serve.decode", "serve.prefill")


def _serve():
    """One decode step: the model program, then the accounting's program
    and pull, then its control round, then the sample's argmax. Each
    program is dispatched inside its span; the model program's enqueue
    runs later, on a runtime thread, while the host is in the accounting."""
    calls = [(12, 13), (35, 36), (85, 86), (105, 106), (150, 151)]
    enqueues = [(31, 32), (40, 41), (86, 87), (106, 107), (151, 152)]
    host = [Event("bench.generate", 0, 200), Event("serve.generate", 0, 200),
            Event("serve.decode", 10, 30),
            Event("serve.account", 30, 100), Event("serve.sync", 50, 70),
            Event("serve.control", 80, 100),
            Event("serve.sample", 100, 120)]
    host += [Event(spans.DISPATCH, a, b) for a, b in calls]
    host += [Event(trace.ENQUEUE, a, b) for a, b in enqueues]
    host.sort(key=lambda e: e.start)
    modules = [Event("jit_decode_step", 14, 50),
               Event("jit_multiply", 50, 55),
               Event("jit_control_round", 90, 95),
               Event("jit_argmax", 110, 112),
               Event("jit_concatenate", 160, 170)]
    ops = [Event("fusion", 14, 50), Event("multiply", 50, 55),
           Event("sor_fit", 90, 95), Event("argmax", 110, 112),
           Event("concatenate", 160, 170)]
    return Trace({0: ops}, {0: modules}, host)


def test_innermost_span_over_nested_spans():
    tr = _serve()
    assert spans.innermost(tr.host) == [
        (0, 10, "serve.generate"), (10, 30, "serve.decode"),
        (30, 50, "serve.account"), (50, 70, "serve.sync"),
        (70, 80, "serve.account"), (80, 100, "serve.control"),
        (100, 120, "serve.sample"), (120, 200, "serve.generate")]


def test_each_program_is_paired_with_the_span_that_enqueued_it():
    tr = _serve()
    pairs = spans.enqueued_by(tr, 0, 200)
    assert [(m.name, s) for m, s in pairs] == [
        ("jit_decode_step", "serve.decode"),
        ("jit_multiply", "serve.account"),
        ("jit_control_round", "serve.control"),
        ("jit_argmax", "serve.sample"),
        ("jit_concatenate", "serve.generate")]
    # the programs that overlap the window, no others
    assert [m.name for m, _ in spans.enqueued_by(tr, 52, 100)] == \
        ["jit_multiply", "jit_control_round"]


def test_a_dispatch_outside_every_program_span_has_none():
    tr = _serve()
    tr.host += [Event(spans.DISPATCH, 250, 251),
                Event(trace.ENQUEUE, 251, 252)]
    tr.modules[0].append(Event("jit_other", 255, 260))
    assert spans.enqueued_by(tr, 0, 300)[-1][1] is None


@pytest.mark.parametrize("change", ["extra_dispatch", "missing_enqueue",
                                    "extra_execution", "two_devices"])
def test_unpaired_traces_give_none(change):
    tr = _serve()
    if change == "extra_dispatch":
        tr.host.append(Event(spans.DISPATCH, 180, 181))
    elif change == "missing_enqueue":
        tr.host.remove(Event(trace.ENQUEUE, 151, 152))
    elif change == "extra_execution":
        tr.modules[0].append(Event("jit_other", 180, 190))
    else:
        tr.modules[1] = list(tr.modules[0])
    assert spans.enqueued_by(tr, 0, 200) is None
    assert spans.busy_within(tr, 0, 200, CONTROL) is None


def test_idle_within_cuts_a_gap_at_span_boundaries():
    tr = _serve()
    # idle: 0-14 in generate then decode, 55-90 in sync, account, control,
    # 95-110 in control then sample, 112-160 in sample then generate,
    # 170-200 in generate
    assert spans.idle_within(tr, 0, 200, ("serve.decode",)) == 14 - 10
    # the 55-90 gap straddles three spans, each gets its own share
    assert spans.idle_within(tr, 0, 200, ("serve.sync",)) == 70 - 55
    assert spans.idle_within(tr, 0, 200, ("serve.account",)) == 80 - 70
    assert spans.idle_within(tr, 0, 200, ("serve.control",)) == \
        (90 - 80) + (100 - 95)
    # an outer span holds only the time no inner span covers
    assert spans.idle_within(tr, 0, 200, ("serve.generate",)) == \
        10 + (160 - 120) + (200 - 170)
    every = ("serve.generate", "serve.decode", "serve.sample") + CONTROL
    idle = 200 - trace.busy_ns(tr, 0, 200)
    assert spans.idle_within(tr, 0, 200, every) == idle
    assert spans.idle_within(tr, 60, 85, CONTROL) == 85 - 60


def test_busy_within_counts_op_time_inside_the_span_programs():
    tr = _serve()
    assert spans.busy_within(tr, 0, 200, CONTROL) == (55 - 50) + (95 - 90)
    assert spans.busy_within(tr, 0, 200, DISPATCH) == 50 - 14
    assert spans.busy_within(tr, 20, 52, DISPATCH) == 50 - 20


def _ctx(tr, kind, lo=0, hi=200):
    return {"trace": tr, "lo": lo, "hi": hi,
            "busy_ns": trace.busy_ns(tr, lo, hi), "window_ns": hi - lo,
            "work": {"kind": kind}, "peaks": {}}


@pytest.mark.parametrize("metric,kind,want", [
    # programs 10 + idle (15 + 10 + 15)
    ("control_share.decode", "serve", 100.0 * (10 + 40) / 200),
    # idle in serve.decode alone (10-14); no prefill here
    ("dispatch_idle_share.decode", "serve", 100.0 * 4 / 200),
    ("control_share.train", "serve", None),
    ("control_share.decode", "train", None),
    ("dispatch_idle_share.decode", "train", None)])
def test_readers(metric, kind, want):
    got = spec.reader(metric)(_ctx(_serve(), kind))
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", ["control_share.decode",
                                    "dispatch_idle_share.decode"])
def test_readers_find_nothing_in_a_program_without_spans(metric):
    tr = _serve()
    tr.host = [e for e in tr.host if not e.name.startswith("serve.")]
    assert spec.reader(metric)(_ctx(tr, "serve")) is None


def test_train_reader():
    host = [Event("bench.step", 0, 100), Event("train.step", 0, 100),
            Event("train.dispatch", 2, 5), Event(spans.DISPATCH, 3, 4),
            Event(trace.ENQUEUE, 5, 6), Event("train.wait", 5, 80),
            Event("train.telemetry", 80, 95), Event(spans.DISPATCH, 82, 83),
            Event(trace.ENQUEUE, 83, 84)]
    tr = Trace({0: [Event("while", 4, 79), Event("copy", 84, 86)]},
               {0: [Event("jit_train_step", 4, 79),
                    Event("jit_convert", 84, 86)]}, host)
    # program 2 + idle in telemetry (80-84, 86-95)
    assert spec.reader("control_share.train")(_ctx(tr, "train", 0, 100)) == \
        pytest.approx(2 + 4 + 9)
    tr.host.remove(Event(spans.DISPATCH, 82, 83))
    assert spec.reader("control_share.train")(_ctx(tr, "train", 0, 100)) \
        is None


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def test_recorded_generate_pairs_every_program(recorded):
    lo, hi = trace.window(recorded)
    pairs = spans.enqueued_by(recorded, lo, hi)
    assert pairs is not None
    by = {}
    for m, s in pairs:
        by.setdefault(s, set()).add(m.name.split("(")[0])
    assert by["serve.decode"] == {"jit_decode_step"}
    assert "jit_prefill" in by["serve.prefill"]
    assert by["serve.control"] == {"jit_control_round"}
    assert len([m for m, s in pairs if s == "serve.decode"]) == 3


def test_recorded_generate_reduces_into_range(recorded):
    lo, hi = trace.window(recorded)
    busy = trace.busy_ns(recorded, lo, hi)
    ctx = {"trace": recorded, "lo": lo, "hi": hi, "busy_ns": busy,
           "window_ns": hi - lo, "work": {"kind": "serve"}, "peaks": {}}
    got = {m: spec.reader(m)(ctx) for m in ("control_share.decode",
                                            "dispatch_idle_share.decode")}
    assert all(0 < v < 100 for v in got.values()), got
    idle = spans.idle_within(recorded, lo, hi, CONTROL) + \
        spans.idle_within(recorded, lo, hi, DISPATCH)
    assert idle <= (hi - lo) - busy
    # the engine's spans hold nearly all of the call's device idle
    names = {e.name for e in recorded.host if e.name.startswith("serve.")}
    assert spans.idle_within(recorded, lo, hi, names) >= \
        0.9 * ((hi - lo) - busy)
