"""repro.obs: span tracer invariants (nesting, ring overflow, the
disabled zero-cost fast path, Chrome trace schema round-trip), pinned
exact quantiles, metrics registry semantics, the measured-vs-predicted
drift monitor, sim-span parity with ``ClientTiming`` totals, and the
end-to-end join over a tiny traced ``FedSession``."""

import json
import re
import threading
import time

import jax
import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry, quantile, summary_stats
from repro.obs.trace import NULL_SPAN, PID_MEASURED, PID_SIM, Tracer


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test gets a quiet process-wide tracer and registry, and
    leaves them that way (other test modules share these singletons)."""
    obs.disable()
    obs.get_tracer().clear()
    obs.registry().clear()
    yield
    obs.disable()
    obs.get_tracer().clear()
    obs.registry().clear()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    tr = Tracer(capacity=16)
    with tr.span("outer", cat="t", round=0):
        time.sleep(0.001)
        with tr.span("inner", cat="t"):
            time.sleep(0.001)
    evs = tr.events()
    # children close before parents: inner is appended first
    assert [e.name for e in evs] == ["inner", "outer"]
    inner, outer = evs
    assert outer.ts_us <= inner.ts_us
    assert outer.ts_us + outer.dur_us >= inner.ts_us + inner.dur_us
    assert outer.args == {"round": 0}
    assert all(e.phase == "X" and e.pid == PID_MEASURED for e in evs)


def test_ring_buffer_overflow_keeps_newest():
    tr = Tracer(capacity=4)
    for i in range(10):
        with tr.span(f"s{i}"):
            pass
    assert tr.dropped == 6
    assert len(tr) == 4
    assert [e.name for e in tr.events()] == ["s6", "s7", "s8", "s9"]
    assert tr.chrome_trace()["otherData"]["dropped_events"] == 6


def test_disabled_tracer_is_shared_singleton():
    tr = Tracer(enabled=False)
    s1, s2 = tr.span("a", x=1), tr.span("b")
    assert s1 is s2 is NULL_SPAN       # no allocation on the fast path
    tr.instant("i")
    tr.add_span("syn", ts_s=0.0, dur_s=1.0)
    assert tr.events() == []
    # module-level convenience hits the same singleton while disabled
    assert obs.span("c", y=2) is NULL_SPAN


def test_disabled_overhead_below_measurement_noise():
    """The acceptance bar: instrumenting a hot path with a disabled
    tracer must cost well under measurement noise.  5us/call is ~100x the
    observed cost of the attribute check + singleton return; a real
    allocation-per-call regression lands far above it."""
    tr = Tracer(enabled=False)
    n = 50_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tr.span("hot", round=1, client=2):
            pass
    per_call = (time.perf_counter() - t0) / n
    assert per_call < 5e-6, f"disabled span costs {per_call*1e6:.2f}us/call"


def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = Tracer(capacity=64)
    with tr.span("work", cat="train", round=3):
        pass
    tr.instant("mark", cat="compile")
    tr.add_span("sim.round", ts_s=1.0, dur_s=0.5, cat="sim", round=3)
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"measured", "simulated"}
    by_name = {e["name"]: e for e in evs if e["ph"] != "M"}
    x = by_name["work"]
    assert x["ph"] == "X" and x["dur"] >= 0 and x["pid"] == PID_MEASURED
    assert x["args"] == {"round": 3}
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    syn = by_name["sim.round"]
    assert syn["pid"] == PID_SIM
    assert syn["ts"] == pytest.approx(1.0e6)
    assert syn["dur"] == pytest.approx(0.5e6)


def test_traced_decorator_and_thread_tracks():
    """Spans from several threads land on one track per thread, and each
    thread's span stack is its own: a span opened in a worker never takes
    another thread's open span as its parent."""
    tr = obs.enable(capacity=128)

    def work():
        with obs.span("worker", cat="t"):
            time.sleep(0.001)

    threads = [threading.Thread(target=work) for _ in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with obs.span("main", cat="t", round=7):
        work()
    evs = [e for e in tr.events() if e.name == "worker"]
    assert len(evs) == 4
    assert len({e.tid for e in evs}) >= 2   # one track per thread
    # only the main thread's worker span opened inside "main"
    assert sorted((e.parent, e.round) for e in evs
                  if e.parent is not None) == [("main", 7)]
    assert sum(e.parent is None for e in evs) == 3


def test_span_records_parent_and_round():
    tr = Tracer(capacity=16)
    with tr.span("train.round", round=4):
        with tr.span("train.dispatch", shard=0):
            with tr.span("train.stack") as sp:
                sp.set(bytes=12)
        with tr.span("train.wait", round=5):
            pass
    with tr.span("train.account"):
        pass
    got = {e.name: (e.parent, e.round) for e in tr.events()}
    assert got == {"train.round": (None, 4),
                   "train.dispatch": ("train.round", 4),
                   "train.stack": ("train.dispatch", 4),
                   "train.wait": ("train.round", 5),
                   "train.account": (None, None)}
    exported = {e["name"]: e.get("args") for e in
                tr.chrome_trace()["traceEvents"] if e["ph"] == "X"}
    assert exported["train.stack"] == {"bytes": 12,
                                       "parent": "train.dispatch",
                                       "round": 4}
    assert exported["train.account"] is None
    assert NULL_SPAN.set(bytes=1) is None       # disabled: dropped


def test_spans_mirror_into_profiler_trace(tmp_path):
    """Each enabled span enters a profiler annotation of its name: on a CPU
    profile the annotation starts within 50 us of where the benchmark's
    origin rule (a clock reading taken as the window annotation opens)
    puts the ring's span."""
    import glob
    from jax.profiler import ProfileData
    tr = obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    window = jax.profiler.TraceAnnotation("bench.window")
    origin_ns = time.perf_counter_ns()
    window.__enter__()
    for r in range(3):
        with obs.span("t.outer", round=r):
            time.sleep(0.002)
            with obs.span("t.inner"):
                time.sleep(0.001)
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    obs.disable()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    host = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append(ev.start_ns)
    (win_ns,) = host["bench.window"]
    ring = [e for e in tr.events() if e.name.startswith("t.")]
    assert len(ring) == 6
    for e in ring:
        placed = tr._epoch_ns + e.ts_us * 1e3 - origin_ns + win_ns
        assert len(host[e.name]) == 3
        assert min(abs(placed - h) for h in host[e.name]) < 50_000


def test_compile_cache_keys_on_op_metadata(monkeypatch, tmp_path):
    """The persistent cache's keys leave op metadata out unless told: a
    program is never served from an entry compiled without its named
    scopes.  Source paths in that metadata are relative to the checkout,
    so a tree keys alike wherever it is unpacked; tracing leaves the
    keying alone."""
    from repro.launch import cache

    keys = ("jax_compilation_cache_include_metadata_in_key",
            "jax_hlo_source_file_canonicalization_regex")
    before = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert cache.use_compile_cache() == str(tmp_path)
        assert getattr(jax.config, keys[0]) is True
        obs.enable()
        obs.disable()
        assert getattr(jax.config, keys[0]) is True

        def scoped(x):
            with jax.named_scope("probe"):
                return x * 2.0

        text = jax.jit(scoped).lower(1.0).as_text(debug_info=True)
        assert "probe" in text
        assert "tests/test_obs.py" in text
        assert str(cache.ROOT) + "/" not in text
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def test_enable_resets_and_keeps_identity():
    before = obs.get_tracer()
    tr = obs.enable(capacity=8)
    assert tr is before                     # call sites keep their reference
    with tr.span("x"):
        pass
    obs.disable()
    assert len(tr.events()) == 1            # kept for export after disable


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_quantile_pinned_values():
    # linear interpolation between closest ranks, h = (n-1)q — these exact
    # values must never drift with a numpy upgrade (they don't use numpy)
    assert quantile([1.0, 2.0, 3.0, 4.0], 0.5) == 2.5
    assert quantile([1.0, 2.0, 3.0, 4.0, 5.0], 0.25) == 2.0
    assert quantile([1.0, 2.0], 0.75) == 1.75
    assert quantile([7.0], 0.99) == 7.0
    assert quantile([], 0.5) == 0.0
    assert quantile(list(range(1, 101)), 0.99) == pytest.approx(99.01)
    with pytest.raises(ValueError):
        quantile([1.0], 1.5)
    s = summary_stats([3.0, 1.0, 2.0])
    assert s == {"mean": 2.0, "p50": 2.0, "p99": pytest.approx(2.98)}


def test_serve_percentiles_delegate_to_pinned_rule():
    from repro.serve.metrics import percentiles
    xs = [0.1, 0.5, 0.2, 0.9, 0.3]
    assert percentiles(xs) == summary_stats(xs)
    assert percentiles([]) == {"mean": 0.0, "p50": 0.0, "p99": 0.0}


def test_registry_semantics(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)               # get-or-create: same object
    assert reg.counter("c").value == 3.5
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(7)
    reg.gauge("g").set(-2)                  # gauges go down
    assert reg.gauge("g").value == -2.0
    h = reg.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["sum"] == 10.0 and s["p50"] == 2.5
    with pytest.raises(TypeError):
        reg.gauge("c")                      # kind conflict never shadows
    assert reg.names() == ["c", "g", "h"]

    path = reg.export_jsonl(str(tmp_path / "m.jsonl"))
    rows = obs.load_jsonl(path)
    assert [r["name"] for r in rows] == ["c", "g", "h"]   # sorted, stable
    assert rows[0] == {"name": "c", "type": "counter", "value": 3.5}
    assert rows[2]["p99"] == pytest.approx(3.97)


def test_compile_listener_counts_compile_phases_only():
    """A persistent-cache hit reports the seconds it saved under a name
    that holds "compile"; those were not spent compiling."""
    from repro.obs.profile import _on_duration_event
    _on_duration_event("/jax/core/compile/jaxpr_trace_duration", 0.25)
    _on_duration_event("/jax/core/compile/backend_compile_duration", 1.5)
    _on_duration_event("/jax/compilation_cache/compile_time_saved_sec", 30.0)
    _on_duration_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.1)
    reg = obs.registry()
    assert reg.counter("compile.events").value == 2
    # phases nest, so no counter sums them
    assert "compile.total_s" not in reg.snapshot()
    assert reg.counter("compile.backend_compile_s").value == 1.5
    assert reg.counter("compile.jaxpr_trace_s").value == 0.25


def test_jax_profile_raises_when_the_profiler_cannot_start(monkeypatch,
                                                            tmp_path):
    import jax.profiler

    def broken(*a, **kw):
        raise RuntimeError("no profiler backend")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    with obs.jax_profile(None):             # off: never touches the profiler
        pass
    with pytest.raises(RuntimeError, match="no profiler backend"):
        with obs.jax_profile(str(tmp_path)):
            pass


# ---------------------------------------------------------------------------
# Drift
# ---------------------------------------------------------------------------

def test_drift_ratio_pinned_and_warn_rule():
    mon = obs.DriftMonitor(warn_ratio=2.0, metrics=MetricsRegistry())
    r = mon.observe(0, "round", measured_s=1.25, predicted_s=1.0)
    assert r.ratio == pytest.approx(1.25) and not r.warn
    assert mon.observe(1, "round", 2.5, 1.0).warn          # > 2x
    assert mon.observe(2, "round", 0.4, 1.0).warn          # < 1/2x
    assert not mon.observe(3, "round", 0.5, 1.0).warn      # boundary holds
    bad = mon.observe(4, "round", 1.0, 0.0)
    assert bad.ratio is None and bad.warn   # unpriceable round always warns
    assert len(mon.warnings()) == 3
    with pytest.raises(ValueError):
        obs.DriftMonitor(warn_ratio=0.5)


def test_drift_banks_metrics_and_exports(tmp_path):
    reg = MetricsRegistry()
    mon = obs.DriftMonitor(warn_ratio=4.0, metrics=reg)
    mon.observe(0, "round", 1.25, 1.0)
    mon.observe(1, "round", 8.0, 1.0)
    assert reg.counter("drift.rows").value == 2
    assert reg.counter("drift.warnings").value == 1
    assert reg.histogram("drift.round.ratio").count == 2
    path = mon.export(str(tmp_path / "drift.json"))
    doc = json.loads(open(path).read())
    assert doc["n_rows"] == 2 and doc["n_warnings"] == 1
    assert doc["rows"][0]["ratio"] == pytest.approx(1.25)


def test_drift_from_dict_history_with_fleet():
    from repro.sim import make_fleet
    from repro.sim.clock import sync_round_s
    hist = [{"round": t, "clients": [0, 1], "round_time_s": 1.0,
             "client_steps": [2, 2], "client_step_flops": [1e12] * 2,
             "client_step_hbm": [1e9] * 2,
             "client_upload_bytes": [1e6] * 2} for t in range(3)]
    fleet = make_fleet("uniform-a100", 2, seed=0)
    mon = obs.from_history(hist, fleet=fleet, warn_ratio=1e9,
                           metrics=MetricsRegistry())
    assert len(mon.records) == 3
    for t, rec in enumerate(mon.records):
        assert rec.source == "fleet"
        pred = sync_round_s(hist[t], fleet, overlap=False)
        assert rec.ratio == pytest.approx(1.0 / pred)


def test_drift_prediction_precedence():
    rr = {"round": 0, "round_time_s": 2.0, "sim_round_s": 4.0,
          "flops_estimate": 1e12, "hbm_bytes_estimate": 1e9,
          "comm_bytes": 0}
    # recorded sim_round_s beats the device roofline...
    s, src = obs.predicted_round_s(rr, device="a100")
    assert (s, src) == (4.0, "sim_round_s")
    # ...and the roofline prices it when there's no recording
    rr2 = dict(rr, sim_round_s=0.0)
    s2, src2 = obs.predicted_round_s(rr2, device="a100")
    assert s2 > 0 and src2 == "device:a100"
    with pytest.raises(ValueError):
        obs.predicted_round_s(rr2, device="not-a-device")
    assert obs.predicted_round_s(dict(rr2, sim_round_s=0.0)) == (0.0, "none")


# ---------------------------------------------------------------------------
# Sim-span parity
# ---------------------------------------------------------------------------

def _tiny_history(rounds=2, clients=3):
    return [{"round": t, "clients": list(range(clients)),
             "client_steps": [2] * clients,
             "client_step_flops": [1e12] * clients,
             "client_step_hbm": [1e9] * clients,
             "client_upload_bytes": [1e6] * clients}
            for t in range(rounds)]


@pytest.mark.parametrize("overlap", [False, True])
def test_sim_spans_match_client_timing_totals(overlap):
    from repro.sim import emit_spans, make_fleet, simulate
    fleet = make_fleet("edge-mixed", 3, seed=0)
    report = simulate(_tiny_history(), fleet, mode="sync", overlap=overlap)
    tr = obs.enable(capacity=4096)
    n = emit_spans(report, tr)
    evs = tr.events()
    assert n == len(evs)
    rounds = [e for e in evs if e.name == "sim.round"]
    assert len(rounds) == len(report.rounds)
    assert all(e.pid == PID_SIM and e.tid == 0 for e in rounds)
    for rs, ev in zip(report.rounds, rounds):
        assert ev.dur_us / 1e6 == pytest.approx(rs.round_s)
    # every client span's duration is EXACTLY its timing total under the
    # report's clock mode, on its own track
    for rs in report.rounds:
        for tm in rs.timings:
            [ev] = [e for e in evs if e.name == "sim.client"
                    and e.args["round"] == rs.round
                    and e.args["client"] == tm.client]
            assert ev.dur_us / 1e6 == pytest.approx(tm.total(overlap))
            assert ev.tid == tm.client + 1
            phases = [e for e in evs if e.tid == ev.tid
                      and e.args and e.args.get("round") == rs.round
                      and e.name in ("sim.down", "sim.compute", "sim.up")]
            assert len(phases) == 3
            total = sum(e.dur_us for e in phases) / 1e6
            assert total == pytest.approx(tm.down_s + tm.compute_s + tm.up_s)


def test_sim_spans_disabled_tracer_is_noop():
    from repro.sim import emit_spans, make_fleet, simulate
    report = simulate(_tiny_history(), make_fleet("uniform-a100", 3, seed=0),
                      mode="sync")
    assert emit_spans(report, Tracer(enabled=False)) == 0


# ---------------------------------------------------------------------------
# End-to-end: a tiny traced FedSession
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced_session():
    from repro import optim
    from repro.configs import get_config
    from repro.core.noniid import make_client_datasets
    from repro.core.rounds import FedSession, RoundPlan
    from repro.data.corpus import generate_corpus
    from repro.models.model import init_model
    from repro.nn import param as P
    from repro.sim import make_fleet

    cfg = get_config("distilbert-mlm").reduced()
    params0 = P.unbox(init_model(jax.random.PRNGKey(0), cfg))
    ds = make_client_datasets(generate_corpus(40, seed=0), cfg, k=3,
                              skew="quantity", batch=2, seq=32)
    batches = [b[:2] for b in ds["batches"]]
    fleet = make_fleet("paper-2080ti", 3, seed=0)
    tr = obs.enable(capacity=65536)
    obs.registry().clear()
    try:
        plan = RoundPlan(n_rounds=2, client_sizes=ds["sizes"],
                         simulate=fleet)
        _, hist = FedSession(cfg, optim.adam(1e-3), plan).run(params0,
                                                              batches)
        events = tr.events()
        reg_snapshot = obs.registry().snapshot()
    finally:
        obs.disable()
    return {"hist": hist, "events": events, "reg": reg_snapshot,
            "fleet": fleet, "tracer_events": events}


def test_session_emits_expected_spans(traced_session):
    names = {e.name for e in traced_session["events"]}
    assert {"train.round", "train.prepare", "train.dispatch", "train.stack",
            "train.launch", "train.combine", "train.wait",
            "train.account"} <= names
    assert "train.aggregate" not in names
    rounds = [e for e in traced_session["events"]
              if e.name == "train.round"]
    assert [e.args["round"] for e in rounds] == [0, 1]
    parents = {e.name: e.parent for e in traced_session["events"]
               if e.name.startswith("train.")}
    assert parents == {"train.round": None, "train.prepare": "train.round",
                       "train.dispatch": "train.round",
                       "train.stack": "train.dispatch",
                       "train.launch": "train.dispatch",
                       "train.combine": "train.round",
                       "train.wait": "train.round", "train.account": None}
    # children carry their round without being told it
    stacks = [e for e in traced_session["events"] if e.name == "train.stack"]
    assert sorted({e.round for e in stacks}) == [0, 1]
    assert all(e.args["arrays"] > 0 and e.args["bytes"] > 0 for e in stacks)
    reg = traced_session["reg"]
    assert reg["train.rounds"]["value"] == 2
    assert reg["train.round_s"]["count"] == 2
    assert reg["train.tokens"]["value"] > 0


SCOPES = ("embed", "attn", "mlp", "lm_head", "loss", "optimizer", "fold")


@pytest.fixture(scope="module")
def round_programs():
    """One traced round of the cohort-scan engine at a small width, and the
    compiled text of its shard and combine programs.  FedAvgM, so the
    combine computes (FedAvg's only copies the mean out)."""
    from repro import optim
    from repro.configs import get_config
    from repro.core.noniid import make_client_datasets
    from repro.core.rounds import FedSession, RoundPlan
    from repro.core.strategy import FedAvgM
    from repro.data.corpus import generate_corpus
    from repro.models.model import init_model
    from repro.nn import param as P

    cfg = get_config("distilbert-mlm").reduced()
    params0 = P.unbox(init_model(jax.random.PRNGKey(0), cfg))
    ds = make_client_datasets(generate_corpus(30, seed=1), cfg, k=2,
                              skew="iid", batch=2, seq=16)
    batches = [b[:1] for b in ds["batches"]]
    tr = obs.enable()
    try:
        sess = FedSession(cfg, optim.adam(1e-3),
                          RoundPlan(n_rounds=1, engine="parallel",
                                    strategy=FedAvgM(), telemetry=False))
        sess.run(params0, batches)
        events = tr.events()
    finally:
        obs.disable()
    strategy = sess.plan.strategy
    shard = sess.shard_program.lower(*sess.shard_args).compile().as_text()
    combine = sess.combine_program.lower(
        params0, strategy.aggregate_init(params0),
        strategy.init_state(params0)).compile().as_text()
    return {"shard": shard, "combine": combine, "events": events}


def _has_scope(hlo_text: str, scope: str) -> bool:
    return re.search(r'op_name="[^"]*[/(]%s[/)][^"]*"' % scope,
                     hlo_text) is not None


@pytest.mark.parametrize("scope", SCOPES)
def test_named_scopes_in_round_programs(round_programs, scope):
    """Named scopes change only op metadata: each one reaches the compiled
    per-round programs (``fold`` both of them), whose jit names the device
    trace's module names come from."""
    assert "HloModule jit__fed_shard" in round_programs["shard"]
    assert "HloModule jit__fed_combine" in round_programs["combine"]
    assert _has_scope(round_programs["shard"], scope)
    assert _has_scope(round_programs["combine"], scope) == (scope == "fold")


def test_parallel_engine_span_tree(round_programs):
    got = {e.name: (e.parent, e.round) for e in round_programs["events"]
           if e.name.startswith("train.")}
    assert got == {"train.round": (None, 0),
                   "train.prepare": ("train.round", 0),
                   "train.dispatch": ("train.round", 0),
                   "train.stack": ("train.dispatch", 0),
                   "train.launch": ("train.dispatch", 0),
                   "train.combine": ("train.round", 0),
                   "train.wait": ("train.round", 0),
                   "train.account": (None, 0)}


def test_session_drift_ratios_within_tolerance(traced_session):
    """The measured-vs-predicted join over a real session: the span the
    tracer recorded and the engine's own perf_counter delta bound the
    same interval, so the two measured paths must agree to a few percent
    — and the fleet predictor prices every round (finite ratio)."""
    hist = traced_session["hist"]

    class _Replay:
        def events(self):
            return traced_session["tracer_events"]

    mon = obs.DriftMonitor(warn_ratio=1e9, metrics=MetricsRegistry())
    for rr in hist:
        mon.observe_round(rr, fleet=traced_session["fleet"],
                          tracer=_Replay())
    assert len(mon.records) == len(hist)
    for rec, rr in zip(mon.records, hist):
        assert rec.source == "fleet" and rec.ratio is not None
        assert rec.predicted_s == pytest.approx(rr.sim_round_s)
        # span-measured vs engine-measured: same interval, <5% apart
        assert rec.measured_s == pytest.approx(rr.round_time_s, rel=0.05)


def test_measured_round_s_falls_back_without_tracer(traced_session):
    rr = traced_session["hist"][0]
    assert obs.measured_round_s(rr) == rr.round_time_s
