"""The reduction from a device trace to numbers: on hand-made events, and
on a small trace recorded on the chip (`record_trace.py`)."""

from pathlib import Path

import pytest

from bench import readers, trace
from bench.trace import Event, Trace

FIXTURE = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"


def _hand_made():
    # while.1 is a loop whose body's ops are listed as well
    ops = [Event("while.1", 10, 30), Event("fusion.1", 10, 15),
           Event("decode_attention", 15, 30),
           Event("fusion.1", 50, 60),
           Event("transpose_jvp_decode_attention__.7", 70, 75)]
    modules = [Event("jit_a", 10, 30), Event("jit_b", 50, 60),
               Event("jit_a", 70, 80)]
    host = [Event("bench.generate", 0, 100), Event("PjitFunction(a)", 30, 45)]
    return Trace({0: ops}, {0: modules}, host)


def test_busy_is_the_union_of_op_intervals():
    tr = _hand_made()
    assert trace.busy_ns(tr, 0, 100) == (30 - 10) + (60 - 50) + (75 - 70)
    assert trace.busy_ns(tr, 16, 55) == (30 - 16) + (55 - 50)


def test_kernel_time_and_programs_that_ran_it():
    tr = _hand_made()
    assert trace.kernel_ns(tr, "decode_attention", 0, 100) == (15 + 5, 2)
    progs = trace.programs_with(tr, "decode_attention", 0, 100)
    assert [(p.start, p.end) for p in progs] == [(10, 30), (70, 80)]
    assert trace.window(tr) == (0, 100)


def test_idle_gaps_are_attributed_to_the_host_span_running():
    tr = _hand_made()
    gaps = dict(trace.idle_gaps(tr, 0, 100))
    # 0-10, 60-70 and 75-100 fall in plain host python; 30-50 is centred
    # in the dispatch of PjitFunction(a)
    assert gaps["bench.generate > PjitFunction(a)"] == pytest.approx(20e-9)
    assert gaps["bench.generate > host python"] == pytest.approx(45e-9)
    ops = dict(trace.device_ops(tr, 0, 100))
    assert ops == {"decode_attention": pytest.approx(15e-9),
                   "transpose_jvp_decode_attention__.7": pytest.approx(5e-9),
                   "fusion.1": pytest.approx(15e-9)}


@pytest.mark.parametrize("op,kernel", [
    ("decode_attention", "decode_attention"),
    ("decode_attention.12", "decode_attention"),
    ("transpose_jvp_mamba2_ssd__.3", "mamba2_ssd"),
    ("flash_attention_bwd_dkv.1", "flash_attention_bwd_dkv"),
    ("fusion.12", "fusion")])
def test_kernel_names(op, kernel):
    assert trace.base_name(op) == kernel


def test_idle_share_reader():
    ctx = {"work": {"kind": "serve"}, "busy_ns": 25.0, "window_ns": 100}
    assert readers.idle_share(ctx, "serve") == 75.0
    assert readers.idle_share(ctx, "train") is None


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE))


def test_recorded_trace_planes_and_names(recorded):
    assert set(recorded.ops) == {0} and len(recorded.modules[0]) == 3
    names = {e.name for e in recorded.ops[0]}
    assert "decode_attention.1" in names and "fusion" in names
    spans = [e.name for e in recorded.host if e.name.startswith("bench.")]
    assert spans == ["bench.call", "bench.host_gap", "bench.call"]


def test_recorded_device_clock_is_aligned_to_the_host(recorded):
    enq = [e for e in recorded.host if e.name == trace.ENQUEUE]
    gaps = [m.start - q.end for m, q in zip(recorded.modules[0], enq)]
    assert min(gaps) == 0 and all(g >= 0 for g in gaps)
    lo, hi = trace.window(recorded)
    assert all(lo <= m.start and m.end <= hi for m in recorded.modules[0])


def test_recorded_trace_reduces(recorded):
    lo, hi = trace.window(recorded)
    busy = trace.busy_ns(recorded, lo, hi)
    # two 2048^3 bf16 matmuls (~0.09 ms each) and one decode attention
    # (~0.12 ms) against a 50 ms sleep: the device is idle almost always
    assert 0.2e6 < busy < 1e6 and busy < 0.02 * (hi - lo)
    ns, calls = trace.kernel_ns(recorded, "decode_attention", lo, hi)
    assert calls == 1 and ns == 123032
    progs = trace.programs_with(recorded, "decode_attention", lo, hi)
    assert len(progs) == 1 and progs[0].name.startswith("jit__lambda")
    gaps = trace.idle_gaps(recorded, lo, hi)
    assert gaps[0][0] == "bench.host_gap > host python"
    assert 0.049 < gaps[0][1] < 0.052
    top = trace.device_ops(recorded, lo, hi)
    assert top[0][0] in ("fusion", "decode_attention.1")
