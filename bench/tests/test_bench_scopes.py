"""Op metadata from the XPlane wire format, device time by named scope, and
the ``host_stack_ms`` and ``dispatch_tree_idle_ms`` readers.

Two traces recorded on a TPU v5e: ``data/probe.xplane.pb`` (two jitted
programs without scopes, see ``test_bench_trace.py``) and
``data/scopes.xplane.pb`` (``record_scope_trace.py``: a jitted step under
the scopes ``attn`` and ``loss``, called three times inside ``repro.obs``
spans that the tracer mirrored as profiler annotations; ``scopes.json``
holds the ring's spans in ns from the runner's origin reading).  On the
chip the annotations started 0.37-1.12 us from where that reading puts
the ring's spans."""

import json
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import scopes, xplane, xtrace  # noqa: E402
from harness.load import load_module  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
PROBE = os.path.join(DATA, "probe.xplane.pb")
SCOPED = os.path.join(DATA, "scopes.xplane.pb")
WINDOW_START_NS = 48403268.0
CALLS = [(48413658.0, 587400.0), (54849367.0, 632400.0),
         (61341857.0, 507010.0), (67699877.0, 398800.0),
         (74214026.0, 514140.0)]


def test_wire_decoder_reads_op_metadata():
    (plane,) = xplane.read(PROBE,
                           want=lambda n: n.startswith(xtrace.DEVICE_PREFIX))
    assert plane.name == "/device:TPU:0"
    tf_ops = {stats["tf_op"] for _, stats in plane.event_metadata.values()
              if "tf_op" in stats}
    assert tf_ops == {"jit(small_step)/dot_general:",
                      "jit(other_prog)/reduce_sum:"}
    fusion = [stats for name, stats in plane.event_metadata.values()
              if name.startswith("%multiply_reduce_fusion")]
    assert fusion[0]["flops"] == 1048575
    assert fusion[0]["program_id"] == 8838517998316270254
    ops = [ln for ln in plane.lines if ln.name == "XLA Ops"][0]
    assert len(ops.events) == 25
    # every op event names metadata the plane holds
    assert all(mid in plane.event_metadata for mid, _, _ in ops.events)


def test_probe_without_scopes():
    by_module = scopes.scope_seconds(PROBE)
    assert by_module == {
        "jit_small_step": {scopes.NO_SCOPE: pytest.approx(1.13021e-4)},
        "jit_other_prog": {scopes.NO_SCOPE: pytest.approx(2.0754e-5)}}
    # the same op times as the reduction's: the scopes split busy time
    busy = xtrace.reduce(PROBE, host_origin_ns=WINDOW_START_NS).busy_s
    assert sum(scopes.totals(by_module).values()) == pytest.approx(busy)


@pytest.mark.parametrize("tf_op,scope", [
    ("jit(_fed_shard)/while/body/closed_call/vmap(jvp(loss))/mul:", "loss"),
    ("jit(f)/vmap(transpose(jvp()))/while/body/checkpoint/"
     "rematted_computation/attn/dot_general:", "attn"),
    ("jit(f)/transpose(jvp(lm_head))/dot_general:", "lm_head"),
    ("jit(_fed_shard)/vmap(optimizer)/sub:", "optimizer"),
    ("jit(_fed_combine)/fold/convert_element_type:", "fold"),
    ("jit(f)/attn/mlp/add:", "mlp"),                 # the innermost wins
    ("jit(loss)/reduce_sum:", scopes.NO_SCOPE),      # a function, no scope
    ("jit(small_step)/dot_general:", scopes.NO_SCOPE),
    ("", scopes.NO_SCOPE),
])
def test_scope_of(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


def _span_list(name):
    return [(s, s + d, name) for s, d in CALLS]


def test_host_stack_reader():
    reader = load_module(os.path.join(BENCH, "metrics", "host_stack_ms.py"),
                         "m_host_stack")
    run = types.SimpleNamespace(window_rounds=2, spans=[
        (1.0, 1.5, "train.round"), (1.0, 1.25, "train.dispatch"),
        (1.0, 1.2, "train.stack"), (2.0, 2.1, "train.stack")])
    assert reader.read(run) == pytest.approx(1000 * 0.3 / 2)
    # the parent's program records no train.stack spans
    assert reader.read(types.SimpleNamespace(
        window_rounds=2, spans=[(1.0, 1.5, "train.dispatch")])) is None
    assert reader.read(types.SimpleNamespace(window_rounds=0,
                                             spans=run.spans)) is None


def _dispatch_run(parent, child):
    spans = parent + child
    return types.SimpleNamespace(
        window_rounds=len(parent), spans=spans,
        trace=xtrace.reduce(PROBE, host_spans=spans,
                            host_origin_ns=WINDOW_START_NS))


CHILDREN = {
    "none": [],
    "stack": [(0.0, 0.6, "train.stack")],
    "stack+launch": [(0.0, 0.4, "train.stack"), (0.5, 1.0, "train.launch")],
}


@pytest.mark.parametrize("children", sorted(CHILDREN))
def test_dispatch_tree_idle_reader(children):
    """``train.stack`` and ``train.launch`` children take the gaps they
    hold from ``train.dispatch`` in ``idle_by_span``, so
    ``dispatch_idle_ms`` drops; ``dispatch_tree_idle_ms`` counts them and
    reads what ``dispatch_idle_ms`` reads with no children."""
    tree = load_module(os.path.join(BENCH, "metrics",
                                    "dispatch_tree_idle_ms.py"), "m_tree")
    inner = load_module(os.path.join(BENCH, "metrics",
                                     "dispatch_idle_ms.py"), "m_inner")
    parent = _span_list("train.dispatch")
    child = [(s + a * (e - s), s + b * (e - s), name) for s, e, _ in parent
             for a, b, name in CHILDREN[children]]
    alone = _dispatch_run(parent, [])
    nested = _dispatch_run(parent, child)
    assert inner.read(alone) > 0
    assert tree.read(nested) == pytest.approx(inner.read(alone))
    if child:
        assert inner.read(nested) < 0.5 * inner.read(alone)
    else:
        assert tree.read(nested) == inner.read(nested)
    # nothing to read: no trace, no rounds, no dispatch spans
    assert tree.read(types.SimpleNamespace(
        window_rounds=5, spans=parent, trace=None)) is None
    assert tree.read(types.SimpleNamespace(
        window_rounds=0, spans=parent, trace=alone.trace)) is None
    assert tree.read(types.SimpleNamespace(
        window_rounds=5, spans=child, trace=nested.trace)) is None


@pytest.fixture(scope="module")
def recorded_spans():
    with open(os.path.join(DATA, "scopes.json")) as f:
        return [tuple(sp) for sp in json.load(f)["spans"]]


def test_scopes_on_the_recorded_chip_trace():
    (plane,) = xplane.read(SCOPED,
                           want=lambda n: n.startswith(xtrace.DEVICE_PREFIX))
    tf_ops = {stats["tf_op"] for _, stats in plane.event_metadata.values()
              if "tf_op" in stats}
    # the forward and the gradient forms of the scope
    assert tf_ops == {"jit(scoped_step)/jvp(attn)/dot_general:",
                      "jit(scoped_step)/transpose(jvp(attn))/dot_general:"}
    split = scopes.scope_seconds(SCOPED)["jit_scoped_step"]
    # the loss's few ops fused into the two matmul fusions, and a fusion
    # takes the scope of its root
    assert "loss" not in split
    assert split["attn"] > 0.99 * sum(split.values())
    busy = xtrace.reduce(SCOPED, host_origin_ns=0.0).busy_s
    assert sum(split.values()) == pytest.approx(busy)


def test_mirrored_spans_on_the_recorded_chip_trace(recorded_spans):
    """The tracer's spans reached the trace's host plane as annotations,
    where the runner's origin rule places them, and label the gaps."""
    from jax.profiler import ProfileData
    host, window = [], None
    for plane in ProfileData.from_file(SCOPED).planes:
        if plane.name == xtrace.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "bench.call":
                        host.append(ev.start_ns)
                    elif ev.name == "bench.window":
                        window = ev.start_ns
    assert len(host) == len(recorded_spans) == 3
    for start, _, _ in recorded_spans:
        placed = window + start
        assert min(abs(h - placed) for h in host) < 50_000
    summ = xtrace.reduce(SCOPED, host_spans=recorded_spans,
                         host_origin_ns=0.0)
    assert summ.idle_by_span["bench.call"] > 0
