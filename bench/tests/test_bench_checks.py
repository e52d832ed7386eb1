"""A whole benchmark run of each cell at a tiny size on the CPU, past the
harness's look for a chip: sound, its comparison says ``correct``; with the
timed path broken underneath in each way the cell can be broken, it does
not.  Then the control: the plain reference one precision below what the
cell's configuration states, put in the program's place, fails the cell's
limits at the same size.  ``qwen2-lora-fdapt`` is not in ``BENCHMARK.json``
(PERF.md, section 7), but its files stay, and so do its checks.

The tiny models compute in float32: the limits were set at the cells' own
sizes on the chip, where bfloat16 rounds relatively less than at a width
of 64 on the CPU."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import calibrate  # noqa: E402
import run as R  # noqa: E402

SEED = 3000000011
TINY = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128,
            vocab_size=512, param_dtype="float32", compute_dtype="float32")
CELLS = {"dbert-fdapt": "distilbert-mlm",
         "qwen2-lora-fdapt": "qwen2-7b-share"}


def _cell(name):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {"name": name, "config": CELLS[name], "traffic": name, "chips": 1}
    config = json.load(open(os.path.join(BENCH, "configs",
                                         CELLS[name] + ".json")))
    traffic = json.load(open(os.path.join(BENCH, "traffic", name + ".json")))
    config = dict(config, **TINY, control=calibrate.control_mode(config))
    if config["objective"] == "mlm":
        config.update(n_kv_heads=4, max_seq_len=64)
        traffic = dict(traffic, clients=4, local_steps=2, batch=4, seq=32,
                       cohort_shard=2)
    else:
        config.update(n_kv_heads=2, peft=dict(config["peft"], rank=4))
        traffic = dict(traffic, clients=4, local_steps=2, batch=1, seq=64,
                       cohort_shard=2)
    return spec, cell, config, traffic


@pytest.fixture
def quiet_cache(monkeypatch):
    """Keep the run's persistent-cache settings out of this process's
    other tests."""
    import repro.launch.cache as cache
    monkeypatch.setattr(cache, "use_compile_cache", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def _run(name):
    spec, cell, config, traffic = _cell(name)
    return R.run_cell(spec, cell, config, traffic, seed=SEED, seconds=0.3,
                      trace=False, device=("cpu", "TPU v5 lite", 1))


def _unchanged_state(step):
    def broken(p, o, *rest):
        _, o2, m = step(p, o, *rest)
        return p, o2, m
    return broken


def _half_batch(step):
    def broken(p, o, *rest):
        b = dict(rest[-1])
        m = b["loss_mask"]
        b["loss_mask"] = (m.at[m.shape[0] // 2:].set(0.0) if m.shape[0] > 1
                          else m.at[:, m.shape[1] // 2:].set(0.0))
        return step(p, o, *rest[:-1], b)
    return broken


def _break_step(monkeypatch, wrap):
    from repro.core.strategy import FederatedStrategy
    make = FederatedStrategy.make_client_step

    def broken(self, *a, **kw):
        return wrap(make(self, *a, **kw))

    monkeypatch.setattr(FederatedStrategy, "make_client_step", broken)


def _skip_fold(monkeypatch):
    """Each shard's fold keeps its first client, weighted by the shard."""
    from repro.core.fedavg import fedavg_fold
    from repro.core.strategy import FederatedStrategy

    def broken(self, global_params, stacked, norm_weights, partial):
        first = jax.tree.map(lambda x: x[:1], stacked)
        return fedavg_fold(partial, first, jnp.sum(norm_weights)[None])

    monkeypatch.setattr(FederatedStrategy, "aggregate_partial", broken)


def _reverse_round(monkeypatch):
    """Each round's change is applied with its sign turned."""
    from repro.core.strategy import FederatedStrategy
    combine = FederatedStrategy.aggregate_combine

    def broken(self, global_params, partial, state, *, k):
        new, state = combine(self, global_params, partial, state, k=k)
        return jax.tree.map(lambda g, n: (2 * g - n).astype(n.dtype),
                            global_params, new), state

    monkeypatch.setattr(FederatedStrategy, "aggregate_combine", broken)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(quiet_cache, name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-2:] == ["checks", "_notes"]


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "fold_left_out", "reversed_update"])
def test_broken_run_is_not_correct(quiet_cache, monkeypatch, name, fault):
    if fault == "unchanged_state":
        _break_step(monkeypatch, _unchanged_state)
    elif fault == "half_batch":
        _break_step(monkeypatch, _half_batch)
    elif fault == "fold_left_out":
        _skip_fold(monkeypatch)
    else:
        _reverse_round(monkeypatch)
    res = _run(name)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails_the_limits(quiet_cache, name):
    spec, cell, config, traffic = _cell(name)
    run_mod = R.load_module(os.path.join(BENCH, "runners", "fed_round.py"),
                            "runner_fed_round")
    job = run_mod.build(config, traffic, SEED)
    ref = run_mod.reference(job, BENCH, "f32")
    control = run_mod.reference(job, BENCH, config["control"])
    from harness import compare
    nums = compare.numbers(control, ref)
    limits = traffic["limits"]
    assert any(nums[k][0] > limit for k, limit in limits.items()), nums
