"""The ``fed_pool`` runner on the CPU at a tiny size: a virtual client's
batches are those ``harness.data`` gives that client, only the clients a
round samples are ever made, the session records each round's cohort as
the engine sampled it, the window is sized from the engine's round time,
whole runs of the ``dbert-fdapt-xdev`` traffic (4 local steps a client),
sound and broken, against a reference that repeats those cohorts, and the
``cohort_host_ms`` reader on recorded spans."""

import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from harness import data  # noqa: E402

NAME, CONFIG = "dbert-fdapt-xdev", "distilbert-mlm"
SEED = 3000001521
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
            d_ff=128, vocab_size=512, max_seq_len=64,
            param_dtype="float32", compute_dtype="float32")


def _cell():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {"name": NAME, "config": CONFIG, "traffic": NAME, "chips": 1}
    config = json.load(open(os.path.join(BENCH, "configs",
                                         CONFIG + ".json")))
    traffic = json.load(open(os.path.join(BENCH, "traffic", NAME + ".json")))
    traffic = dict(traffic, batch=4, seq=32, population=300,
                   participation=0.02, clients=6, cohort_shard=2)
    return spec, cell, dict(config, **TINY), traffic


def _runner():
    return R.load_module(os.path.join(BENCH, "runners", "fed_pool.py"),
                         "runner_fed_pool")


@pytest.fixture
def quiet_cache(monkeypatch):
    """Keep the run's persistent-cache settings out of this process's
    other tests."""
    import repro.launch.cache as cache
    monkeypatch.setattr(cache, "use_compile_cache", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def test_virtual_client_is_the_generators_client():
    _, _, config, traffic = _cell()
    assert traffic["local_steps"] == 4
    pool = _runner()
    k = 4
    want = data.client_batches(dict(traffic, clients=k + 1), config,
                               SEED)[k]
    got = pool.client_batches(traffic, config, SEED, k)
    assert len(got) == len(want) == traffic["local_steps"]
    for g, w in zip(got, want):
        for name in ("tokens", "targets", "loss_mask"):
            np.testing.assert_array_equal(g[name], w[name])


def test_only_sampled_clients_exist_and_cohorts_are_recorded():
    _, _, config, traffic = _cell()
    pool = _runner()
    job = pool.build(config, traffic, SEED)
    assert job.batches.max_steps == traffic["local_steps"]
    assert job.batches.materialized == []
    tap = pool.Tap()
    sess = pool.session(job, tap)
    base, _ = job.weights()
    _, hist = sess.run(base, job.batches)
    cohorts = [list(h.clients) for h in hist]
    assert job.cohorts == cohorts and len(cohorts) == 2
    assert all(len(c) == traffic["clients"] for c in cohorts)
    assert cohorts[0] != cohorts[1]
    assert sess.plan.seed == SEED
    # client 0 is read once by the telemetry's cost analysis
    assert set(job.batches.materialized) == set(sum(cohorts, [0]))
    assert all(len(job.batches.batches_for(k)) == 4
               for k in job.batches.materialized)
    assert job.tokens_per_round == (traffic["clients"] * 4
                                    * traffic["batch"] * traffic["seq"])
    assert sess.round_times == [h.round_time_s for h in hist]


def test_later_calls_sample_cohorts_of_their_own(quiet_cache):
    """Each ``run`` call samples from a seed of its own, so a second call
    (the window's) draws cohorts the first did not and builds its clients'
    data anew; the same run seed gives the same cohorts."""
    _, _, config, traffic = _cell()
    pool = _runner()
    got = []
    for _ in range(2):
        job = pool.build(config, traffic, SEED)
        sess = pool.session(job, pool.Tap())
        base, _ = job.weights()
        sess.run(base, job.batches)
        first = len(job.batches.materialized)
        sess.run(base, job.batches)
        got.append(job.cohorts)
        assert len(job.cohorts) == 4
        assert len({frozenset(c) for c in job.cohorts}) == 4
        assert sess.plan.seed == pool.call_seed(SEED, 1) != SEED
        new = set(sum(job.cohorts[2:], [])) - set(sum(job.cohorts[:2], [0]))
        assert len(job.batches.materialized) == first + len(new) > first
    assert got[0] == got[1]


def test_call_seed_is_fixed_and_distinct():
    seed = _runner().call_seed
    big = 2**31 + 12345
    assert seed(big, 0) == big
    assert seed(big, 1) == seed(big, 1) != seed(big, 2)
    assert len({seed(s, c) for s in (SEED, big) for c in (1, 2, 3)}) == 6


def test_window_is_sized_from_the_engines_round_time(monkeypatch):
    """The warm round is the first call's last round as the engine timed
    it, not the gap between two taps (which holds the tap's own work)."""
    pool = _runner()
    monkeypatch.setattr(pool, "_first_rounds", lambda *a: ("r", "g", 9.0))
    sess = types.SimpleNamespace(round_times=[4.0, 0.75])
    assert pool.first_rounds(None, sess, None, None, None) == ("r", "g",
                                                               0.75)


def _run():
    spec, cell, config, traffic = _cell()
    return R.run_cell(spec, cell, config, traffic, seed=SEED, seconds=0.3,
                      trace=False, device=("cpu", "TPU v5 lite", 1))


def test_sound_pool_run_is_correct(quiet_cache):
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["fold_left_out", "other_cohort"])
def test_broken_pool_run_is_not_correct(quiet_cache, monkeypatch, fault):
    """A fold that keeps each shard's first client; an engine whose third
    round trains other clients than it reports."""
    from repro.core import rounds
    from repro.core.fedavg import fedavg_fold
    from repro.core.strategy import FederatedStrategy
    if fault == "fold_left_out":
        def broken(self, global_params, stacked, norm_weights, partial):
            first = jax.tree.map(lambda x: x[:1], stacked)
            return fedavg_fold(partial, first, jnp.sum(norm_weights)[None])
        monkeypatch.setattr(FederatedStrategy, "aggregate_partial", broken)
    else:
        stack = rounds._stack_shard
        monkeypatch.setattr(rounds, "_stack_shard",
                            lambda d, ids, steps: stack(
                                d, [k + 1 for k in ids], steps))
    res = _run()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cohorts, match", [
    ([[0, 1], [2, 3]], None),
    ([[0, 0], [2, 3]], "distinct"),
    ([[0], [2, 3]], "distinct"),
    ([[0, 5], [2, 3]], "outside"),
    ([[0, 1], [1, 0]], "same cohort"),
    ([[0, 1], [2, 3], [4, 0], [3, 2]], "same cohort"),
])
def test_check_cohorts(cohorts, match):
    traffic = {"population": 5, "clients": 2}
    check = _runner().check_cohorts
    if match is None:
        check(cohorts, traffic)
    else:
        with pytest.raises(RuntimeError, match=match):
            check(cohorts, traffic)


@pytest.mark.parametrize("fault", ["same_every_round", "repeated_client"])
def test_sampling_fault_stops_the_run(quiet_cache, monkeypatch, fault):
    """An engine that draws one cohort every round, or a client twice in a
    round, stops the run: the reference repeats the cohorts the engine
    recorded, so the comparison alone would pass it."""
    from repro.core import rounds
    draw = rounds._participants
    if fault == "same_every_round":
        def broken(rng, k, participation):
            draw(rng, k, participation)
            return list(range(max(1, int(round(participation * k)))))
    else:
        def broken(rng, k, participation):
            got = draw(rng, k, participation)
            return got[:-1] + got[:1]
    monkeypatch.setattr(rounds, "_participants", broken)
    match = "same cohort" if fault == "same_every_round" else "distinct"
    with pytest.raises(RuntimeError, match=match):
        _run()


def _cohort_reader():
    return R.load_module(os.path.join(BENCH, "metrics", "cohort_host_ms.py"),
                         "metric_cohort_host_ms")


@pytest.mark.parametrize("spans, rounds, want", [
    ([(1.0, 1.25, "train.cohort"), (2.0, 2.5, "train.cohort"),
      (1.0, 3.0, "train.prepare"), (2.5, 2.75, "train.stack")], 2, 375.0),
    ([(1.0, 1.2, "train.cohort")], 4, 50.0),
    ([(1.0, 1.25, "train.prepare"), (2.0, 2.5, "train.stack")], 2, None),
    ([(1.0, 1.25, "train.cohort")], 0, None),
])
def test_cohort_host_ms_reads_the_cohort_spans(spans, rounds, want):
    """Host ms a round in ``train.cohort`` alone; nothing to read where the
    program has no such span (the parent of the change that added it) or
    the window has no rounds."""
    got = _cohort_reader().read(types.SimpleNamespace(
        spans=spans, window_rounds=rounds))
    assert got == (None if want is None else pytest.approx(want))
