"""The reduction from a profiler trace to busy time, module and op times and
labeled idle gaps, on a small trace recorded on a TPU v5e
(``data/probe.xplane.pb``: a ``bench.window`` annotation around five
``bench.call`` annotations, each running two jitted programs, with 6 ms
sleeps between them)."""

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import xtrace  # noqa: E402

PROBE = os.path.join(BENCH, "tests", "data", "probe.xplane.pb")
WINDOW_START_NS = 48403268.0
CALLS = [(48413658.0, 587400.0), (54849367.0, 632400.0),
         (61341857.0, 507010.0), (67699877.0, 398800.0),
         (74214026.0, 514140.0)]


@pytest.fixture(scope="module")
def summary():
    spans = [(s, s + d, "bench.call") for s, d in CALLS]
    return xtrace.reduce(PROBE, host_spans=spans,
                         host_origin_ns=WINDOW_START_NS)


def test_window_modules_and_busy(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(0.032318258)
    assert summary.module_runs == {"jit_small_step": 5, "jit_other_prog": 5}
    assert summary.modules["jit_small_step"] == pytest.approx(1.1308e-4)
    assert summary.modules["jit_other_prog"] == pytest.approx(2.0772e-5)
    # the ops lie inside their modules, and the union counts each once
    assert summary.busy_s == pytest.approx(1.33775e-4)
    assert summary.busy_s <= sum(summary.modules.values()) + 1e-12


def test_ops_sum_to_busy_time(summary):
    assert set(summary.ops) == {
        "convolution_tanh_fusion bf16[512,1024]", "fusion f32[512,1024]",
        "copy-done f32[1024,1024]", "multiply_reduce_fusion f32[]",
        "copy-start (f32[1024,1024],"}
    assert sum(summary.ops.values()) == pytest.approx(summary.busy_s)


def test_gaps_labeled_by_host_spans(summary):
    # the five sleeps between calls lie outside the calls; the short gaps
    # between ops and between a call's two programs lie inside one
    assert summary.gaps == [
        ("outside program spans (5 gaps, longest 0.006450 s)",
         pytest.approx(0.0311627)),
        ("bench.call (21 gaps, longest 0.000289 s)",
         pytest.approx(0.001021783))]
    assert sum(g[1] for g in summary.gaps) == pytest.approx(
        summary.window_s - summary.busy_s)
    assert summary.idle_by_span == {
        "outside program spans": pytest.approx(0.0311627),
        "bench.call": pytest.approx(0.001021783)}


@pytest.mark.parametrize("events,expected", [
    ([(0, 10, "loop"), (2, 4, "a"), (5, 9, "b")],
     {"loop": 4, "a": 2, "b": 4}),
    ([(0, 4, "a"), (4, 6, "a"), (8, 9, "b")], {"a": 6, "b": 1}),
    ([(0, 10, "loop"), (8, 12, "a")], {"loop": 8, "a": 4}),
])
def test_exclusive_time(events, expected):
    assert xtrace._exclusive(events) == expected


@pytest.mark.parametrize("text,name", [
    ("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop",
     "fusion f32[8]"),
    ("%convolution_tanh_fusion = bf16[512,64]{1,0:T(8,128)(2,1)} fusion()",
     "convolution_tanh_fusion bf16[512,64]"),
    ("%reshape.3.1 = s32[4,2]{1,0} reshape(s32[8] %w)", "reshape s32[4,2]"),
])
def test_op_names(text, name):
    assert xtrace.op_name(text) == name


def test_layer_readers_on_the_probe(summary):
    sys.path.insert(0, BENCH)
    import run as R
    run = types.SimpleNamespace(trace=summary, window_rounds=5,
                                client_steps_per_round=2)
    idle = R.load_module(os.path.join(BENCH, "metrics",
                                      "device_idle_frac.py"), "m_idle")
    assert idle.read(run) == pytest.approx(
        100 * (1 - 1.33775e-4 / 0.032318258))
    shard = R.load_module(os.path.join(BENCH, "metrics",
                                       "shard_program_ms.py"), "m_shard")
    assert shard.read(run) is None        # no shard program in the probe
    assert idle.read(types.SimpleNamespace(trace=None)) is None


def test_dispatch_idle_reader_on_the_probe():
    sys.path.insert(0, BENCH)
    import run as R
    spans = [(s, s + d, "train.dispatch") for s, d in CALLS]
    summ = xtrace.reduce(PROBE, host_spans=spans,
                         host_origin_ns=WINDOW_START_NS)
    reader = R.load_module(os.path.join(BENCH, "metrics",
                                        "dispatch_idle_ms.py"), "m_disp")
    run = types.SimpleNamespace(trace=summ, window_rounds=5,
                                spans=[(s / 1e9, e / 1e9, n)
                                       for s, e, n in spans])
    assert reader.read(run) == pytest.approx(1000 * 0.001021783 / 5)
    # a run without the engine's spans has nothing to read
    assert reader.read(types.SimpleNamespace(
        trace=summ, window_rounds=5, spans=[])) is None
