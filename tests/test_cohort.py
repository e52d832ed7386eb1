"""Cohort-scan engine: shard-schedule invariants, bitwise parity with the
full-width stacked-vmap round for every registered strategy (+ FFDAPT
masking), compile-count independence from cohort size, resume across a
DIFFERENT shard size, O(m) Floyd sampling, lazy ``ClientPool`` parity, the
vectorized mega-cohort clock, and the shard-program cost multiplicity."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core.ffdapt import FFDAPTConfig
from repro.core.noniid import make_client_datasets, make_client_pool
from repro.core.rounds import (FedSession, RoundPlan, _participants,
                               _shard_widths, _stack_shard)
from repro.core.strategies import AsyncFedAvg
from repro.core.strategy import Compressed, FedAvg, FedAvgM, FedProx
from repro.data.corpus import generate_corpus
from repro.data.partition import ClientPool
from repro.models.model import init_model
from repro.nn import param as P
from repro.sim import clock
from repro.sim.fleet import make_fleet
from repro.telemetry import batch_struct, client_step_cost, shard_epoch_cost

CFG = get_config("distilbert-mlm").reduced()
KEY = jax.random.PRNGKey(0)
DOCS = generate_corpus(120, seed=0)
OPT = optim.adam(1e-3)          # ONE instance: sessions share the step cache


@pytest.fixture(scope="module")
def params0():
    return P.unbox(init_model(KEY, CFG))


@pytest.fixture(scope="module")
def clients():
    ds = make_client_datasets(DOCS, CFG, k=5, skew="quantity", batch=2,
                              seq=32)
    return [b[:2] for b in ds["batches"]], ds["sizes"]


def _assert_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _run(params0, batches, sizes, *, shard, **plan_kw):
    plan = RoundPlan(client_sizes=sizes, engine="parallel",
                     cohort_shard=shard, telemetry=False, **plan_kw)
    session = FedSession(CFG, OPT, plan)
    p, h = session.run(params0, batches)
    return p, h, session


# ---------------------------------------------------------------- schedule

def test_shard_widths_schedule():
    assert _shard_widths(5, None) == [5]        # full-width stacked vmap
    assert _shard_widths(6, 2) == [2, 2, 2]
    assert _shard_widths(5, 2) == [2, 3]        # lone remainder absorbed
    assert _shard_widths(7, 3) == [3, 4]
    assert _shard_widths(8, 3) == [3, 3, 2]
    assert _shard_widths(5, 1) == [2, 3]        # width-1 clamped to 2
    assert _shard_widths(2, 1) == [2]
    assert _shard_widths(1, 1) == [1]           # single client: no choice
    assert _shard_widths(4, 100) == [4]         # shard >= m: one shard
    for m in range(1, 40):
        for s in (1, 2, 3, 5, 8, None):
            widths = _shard_widths(m, s)
            assert sum(widths) == m
            # never a width-1 shard unless the whole cohort is 1 client
            # (width-1 vmaps lower differently and break bitwise parity)
            assert m == 1 or all(w >= 2 for w in widths)
            # at most two distinct widths -> at most two shard compiles
            assert len(set(widths)) <= 2


# ------------------------------------------------------------------ parity

STRATEGIES = [
    FedAvg(),
    FedAvgM(beta=0.9, lr=1.0),
    FedProx(mu=0.01),
    AsyncFedAvg(alpha=0.5, staleness=(1, 0)),
    Compressed(inner=FedAvg(), kind="topk", frac=0.3),
]


@pytest.mark.parametrize("strategy", STRATEGIES, ids=lambda s: s.name)
def test_cohort_scan_bitwise_parity(params0, clients, strategy):
    """shard=3 over a 5-client cohort (widths [3, 2] — both shard program
    variants) must reproduce the full-width vmapped round bit for bit:
    the streaming fold is the SAME left fold the stacked path runs."""
    batches, sizes = clients
    kw = dict(n_rounds=2, strategy=strategy, seed=3)
    p_full, h_full, _ = _run(params0, batches, sizes, shard=None, **kw)
    p_scan, h_scan, _ = _run(params0, batches, sizes, shard=3, **kw)
    _assert_bitwise(p_full, p_scan)
    assert [h.loss for h in h_full] == [h.loss for h in h_scan]
    assert [h.tokens for h in h_full] == [h.tokens for h in h_scan]


def test_cohort_scan_ffdapt_masked_parity(params0, clients):
    """Per-client freeze masks ride the shard slices: masked FFDAPT rounds
    stay bitwise shard-invariant too."""
    batches, sizes = clients
    kw = dict(n_rounds=2, ffdapt=FFDAPTConfig(), seed=5)
    p_full, _, _ = _run(params0, batches, sizes, shard=None, **kw)
    p_scan, _, _ = _run(params0, batches, sizes, shard=2, **kw)
    _assert_bitwise(p_full, p_scan)


def test_cohort_scan_participation_parity(params0, clients):
    """Sampled cohorts (participation < 1) pick the same clients under any
    shard size (Floyd draw happens before sharding) and fold to the same
    bits."""
    batches, sizes = clients
    kw = dict(n_rounds=3, participation=0.8, seed=11)
    p_full, h_full, _ = _run(params0, batches, sizes, shard=None, **kw)
    p_scan, h_scan, _ = _run(params0, batches, sizes, shard=2, **kw)
    assert [h.clients for h in h_full] == [h.clients for h in h_scan]
    _assert_bitwise(p_full, p_scan)


@pytest.fixture(scope="module")
def wide_clients():
    """4 clients x 2 steps of 4 x 128: B*S = 512 positions, so the LM head
    runs gathered at the ladder's floor of 128 rows (15% masks, about 77
    masked positions a batch)."""
    ds = make_client_datasets(DOCS, CFG, k=4, skew="iid", batch=4, seq=128)
    return [b[:2] for b in ds["batches"]], ds["sizes"]


def test_cohort_scan_gathered_head_parity(params0, wide_clients):
    """With the head at the masked positions, every shard schedule stays
    bitwise the full-width round, each width still compiles one program,
    and the round's capacity (128 < 512) is what the registry reads."""
    from repro import obs
    batches, sizes = wide_clients
    kw = dict(n_rounds=2, seed=3)
    p_full, h_full, s_full = _run(params0, batches, sizes, shard=None, **kw)
    p_scan, h_scan, s_scan = _run(params0, batches, sizes, shard=2, **kw)
    _assert_bitwise(p_full, p_scan)
    assert [h.loss for h in h_full] == [h.loss for h in h_scan]
    assert s_full.shard_compiles == 1             # widths [4]
    assert s_scan.shard_compiles == 1             # widths [2, 2]
    assert obs.registry().gauge("train.head_capacity").value == 128
    # the shard program holds the gathered head: no (B, S, vocab) logits
    text = s_scan.shard_program.lower(*s_scan.shard_args).as_text()
    assert f"4x128x{CFG.vocab_size}x" not in text
    assert f"128x{CFG.vocab_size}x" in text


@pytest.mark.parametrize("engine", ["parallel", "sequential"])
@pytest.mark.parametrize("masks", ["mlm", "all_ones"])
def test_head_counters_after_a_session(params0, wide_clients, engine, masks):
    """``train.head_capacity``, ``train.head_fill`` and ``train.head_full``
    after a short run: MLM masks run gathered at 128 rows; all-ones masks
    (a CLM objective's) run the head at every position, once per round."""
    from repro import obs
    batches, sizes = wide_clients
    if masks == "all_ones":
        batches = [[dict(b, loss_mask=np.ones_like(b["loss_mask"]))
                    for b in bs] for bs in batches]
    most = max(int(np.count_nonzero(b["loss_mask"]))
               for bs in batches for b in bs)
    reg = obs.registry()
    full0 = reg.counter("train.head_full").value
    plan = RoundPlan(client_sizes=sizes, engine=engine, cohort_shard=2,
                     telemetry=False, n_rounds=2, seed=0)
    FedSession(CFG, OPT, plan).run(params0, batches)
    rows = 128 if masks == "mlm" else 512
    assert reg.gauge("train.head_capacity").value == rows
    assert reg.gauge("train.head_fill").value == most / rows
    assert reg.counter("train.head_full").value - full0 == (
        0 if masks == "mlm" else 2)


# ----------------------------------------------------------- compile count

def test_compile_count_independent_of_cohort(params0, clients):
    """One uniform shard width -> ONE compiled shard program, reused across
    shards AND rounds; a remainder adds at most one more.  Cohort size
    never shows up in the compile count."""
    batches, sizes = clients
    _, _, s_uniform = _run(params0, batches[:4], sizes[:4], shard=2,
                           n_rounds=2, seed=0)
    assert s_uniform.shard_compiles == 1          # widths [2, 2]
    _, _, s_remainder = _run(params0, batches, sizes, shard=2,
                             n_rounds=2, seed=0)
    assert s_remainder.shard_compiles == 2        # widths [2, 3]
    _, _, s_full = _run(params0, batches, sizes, shard=None,
                        n_rounds=2, seed=0)
    assert s_full.shard_compiles == 1             # widths [5]


# ------------------------------------------------------------------ resume

def test_resume_across_different_shard_size(params0, clients, tmp_path):
    """cohort_shard is a memory knob, not part of the run's identity: a
    checkpoint written under shard=2 resumes under shard=3 (and under the
    full-width engine) bitwise identical to the uninterrupted run."""
    batches, sizes = clients
    kw = dict(n_rounds=3, participation=0.8, seed=7)
    p_full, h_full, _ = _run(params0, batches, sizes, shard=2, **kw)

    plan = RoundPlan(client_sizes=sizes, engine="parallel", cohort_shard=2,
                     telemetry=False, checkpoint_dir=str(tmp_path),
                     stop_after_round=1, **kw)
    FedSession(CFG, OPT, plan).run(params0, batches)

    plan_b = dataclasses.replace(plan, cohort_shard=3, stop_after_round=None)
    p_b, h_b = FedSession(CFG, OPT, plan_b).run(params0, batches,
                                                resume=True)
    _assert_bitwise(p_full, p_b)
    assert [h.clients for h in h_b] == [h.clients for h in h_full]
    assert [h.loss for h in h_b] == [h.loss for h in h_full]


# ------------------------------------------------------- shard stacking

def test_stack_shard_keeps_device_leaves_on_the_device(monkeypatch):
    """A shard's block pads short clients by cycling their batches; host
    leaves are stacked in numpy, and a leaf the provider holds on the
    device never goes through numpy (which would copy it to the host)."""
    class Provider:
        def __init__(self, on_device):
            self.on_device = on_device

        def batches_for(self, k):
            return [{"tokens": (jnp.full if self.on_device else np.full)(
                         (2, 3), 10 * k + i, np.int32),
                     "loss_mask": np.full((2, 3), i % 2, np.float32)}
                    for i in range(k + 1)]

    real, seen = np.stack, []

    def spy(xs, *a, **kw):
        seen.append(any(isinstance(x, jax.Array) for x in xs))
        return real(xs, *a, **kw)

    monkeypatch.setattr(np, "stack", spy)
    host = _stack_shard(Provider(False), [0, 2], 3)
    assert seen and not any(seen)
    seen.clear()
    mixed = _stack_shard(Provider(True), [0, 2], 3)
    assert seen and not any(seen)
    for got in (host, mixed):
        assert all(isinstance(v, jax.Array) for v in got.values())
        np.testing.assert_array_equal(got["tokens"][:, :, 0, 0],
                                      [[0, 0, 0], [20, 21, 22]])
        np.testing.assert_array_equal(got["loss_mask"][:, :, 0, 0],
                                      [[0, 0, 0], [0, 1, 0]])


# ------------------------------------------------------- Floyd sampling

def test_participants_floyd_uniform_subset():
    rng = np.random.default_rng(0)
    got = _participants(rng, 100, 0.2)
    assert len(got) == 20 and got == sorted(set(got))
    assert all(0 <= c < 100 for c in got)


def test_participants_deterministic_same_bitstate():
    a = _participants(np.random.default_rng(42), 1000, 0.016)
    b = _participants(np.random.default_rng(42), 1000, 0.016)
    assert a == b and len(a) == 16


def test_participants_consumes_one_vectorized_draw():
    """The draw is ONE ``integers`` call over the Floyd ranges — the exact
    generator advance the resume contract checkpoints.  A reference
    generator making the same call lands in the same bit-state."""
    k, m = 1000, 16
    rng_a, rng_b = np.random.default_rng(9), np.random.default_rng(9)
    _participants(rng_a, k, m / k)
    rng_b.integers(0, np.arange(k - m + 1, k + 1))
    assert rng_a.integers(0, 2**63) == rng_b.integers(0, 2**63)


def test_participants_billion_clients_o_of_m():
    """k = 10^9 must not materialize a k-length permutation (rng.choice
    would); Floyd touches O(m) memory and returns instantly."""
    got = _participants(np.random.default_rng(1), 10**9, 100 / 10**9)
    assert len(got) == 100
    assert all(0 <= c < 10**9 for c in got)


def test_participants_edges():
    assert _participants(np.random.default_rng(0), 5, 1.0) == [0, 1, 2, 3, 4]
    got = _participants(np.random.default_rng(0), 5, 0.8)   # m = k - 1
    assert len(got) == 4 and len(set(got)) == 4
    assert len(_participants(np.random.default_rng(0), 7, 1e-9)) == 1


# ------------------------------------------------------------- ClientPool

def test_client_pool_lazy_materialization():
    pool = ClientPool(6, [lambda: ["a", "b"], lambda: ["c"]], sizes=[2, 1])
    assert pool.materialized == []               # nothing built yet
    assert pool.batches_for(3) == ["c"]          # virtual 3 -> shard 1
    assert pool.materialized == [1]
    assert len(pool) == 6
    assert pool.sizes == [2, 1, 2, 1, 2, 1]


def test_client_pool_session_parity(params0):
    """A FedSession fed the lazy pool matches the same session fed the
    pre-materialized batch lists bitwise, and builds only the sampled
    cohort's data shards."""
    pool = make_client_pool(DOCS, CFG, n_clients=4, pool=2, batch=2,
                            seq=32, seed=0, limit=2)
    batches = [pool.batches_for(k) for k in range(4)]
    kw = dict(n_rounds=2, seed=3)
    p_list, _, _ = _run(params0, batches, list(pool.sizes), shard=2, **kw)
    fresh = make_client_pool(DOCS, CFG, n_clients=4, pool=2, batch=2,
                             seq=32, seed=0, limit=2)
    plan = RoundPlan(engine="parallel", cohort_shard=2, telemetry=False,
                     **kw)
    p_pool, _ = FedSession(CFG, OPT, plan).run(params0, fresh)
    _assert_bitwise(p_list, p_pool)
    assert fresh.materialized == [0, 1]


# ------------------------------------------------------- vectorized clock

def _ledger_round(m, seed=0):
    rng = np.random.default_rng(seed)
    from repro.core.rounds import RoundResult
    return RoundResult(
        round=0, loss=0.0, round_time_s=0.0,
        clients=[int(c) for c in rng.choice(4096, size=m, replace=False)],
        client_steps=[int(s) for s in rng.integers(1, 5, m)],
        client_step_flops=[float(f) for f in rng.uniform(1e9, 1e12, m)],
        client_step_hbm=[float(h) for h in rng.uniform(1e8, 1e10, m)],
        client_upload_bytes=[int(b) for b in rng.integers(10**6, 10**8, m)],
        upload_bytes=0, download_bytes=m * 7_627_776)


@pytest.mark.parametrize("overlap", [False, True])
def test_sync_round_s_vec_bitwise_matches_loop(monkeypatch, overlap):
    """The numpy fast path is op-for-op the ClientTiming loop: same float64
    numbers, not merely close."""
    rr = _ledger_round(64)
    fleet = make_fleet("crossdevice", 4096, seed=0)
    monkeypatch.setattr(clock, "VECTOR_MIN_CLIENTS", 10**9)
    want = clock.sync_round_s(rr, fleet, overlap=overlap)
    monkeypatch.setattr(clock, "VECTOR_MIN_CLIENTS", 1)
    got = clock.sync_round_s(rr, fleet, overlap=overlap)
    assert got == want                            # bitwise, not approx


# ------------------------------------------------- shard program costing

def test_shard_epoch_cost_multiplicity(clients):
    """The scan-aware analyzer prices the shard program at exactly
    shard x steps x per-step compute (the fold adds no dot FLOPs), which
    is why the round ledger can stay rectangular under any shard size."""
    batches, _ = clients
    sds = batch_struct(batches[0][0])
    one = client_step_cost(CFG, OPT, FedAvg(), sds)
    shard = shard_epoch_cost(CFG, OPT, FedAvg(), sds, shard=3, steps=2)
    assert shard.flops == pytest.approx(3 * 2 * one.flops, rel=1e-6)
    assert shard.hbm_bytes >= 3 * 2 * one.hbm_bytes * 0.5
