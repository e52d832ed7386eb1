"""Whole runs of the ``granite-lora-fdapt`` cell at a tiny size on the
CPU, past the harness's look for a chip, sound and broken, in float32
(the limits were set at full width on the chip, where bfloat16 rounds
relatively less than at a width of 64), and the fp8 control against the
limits."""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
import run as R  # noqa: E402
from harness import compare  # noqa: E402
from test_bench_granite import SEED, _cell, _config  # noqa: E402


@pytest.fixture
def quiet_cache(monkeypatch):
    """Keep the run's persistent-cache settings out of this process's
    other tests."""
    import repro.launch.cache as cache
    monkeypatch.setattr(cache, "use_compile_cache", lambda: "")
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


def _unchanged_state(step):
    def broken(p, o, *rest):
        _, o2, m = step(p, o, *rest)
        return p, o2, m
    return broken


def _half_batch(step):
    def broken(p, o, *rest):
        b = dict(rest[-1])
        m = b["loss_mask"]
        b["loss_mask"] = m.at[:, m.shape[1] // 2:].set(0.0)
        return step(p, o, *rest[:-1], b)
    return broken


def _run(**kw):
    spec, cell, config, traffic = _cell(**kw)
    return R.run_cell(spec, cell, config, traffic, seed=SEED, seconds=0.3,
                      trace=False, device=("cpu", "TPU v5 lite", 1))


def test_sound_granite_run_is_correct(quiet_cache):
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "fold_left_out"])
def test_broken_granite_run_is_not_correct(quiet_cache, monkeypatch, fault):
    from repro.core.fedavg import fedavg_fold
    from repro.core.strategy import FederatedStrategy
    if fault == "fold_left_out":
        def broken(self, global_params, stacked, norm_weights, partial):
            first = jax.tree.map(lambda x: x[:1], stacked)
            return fedavg_fold(partial, first, jnp.sum(norm_weights)[None])
        monkeypatch.setattr(FederatedStrategy, "aggregate_partial", broken)
    else:
        wrap = _unchanged_state if fault == "unchanged_state" else _half_batch
        make = FederatedStrategy.make_client_step
        monkeypatch.setattr(FederatedStrategy, "make_client_step",
                            lambda self, *a, **k: wrap(make(self, *a, **k)))
    res = _run()
    assert not res["correct"], res["checks"]


def test_granite_control_fails_the_limits():
    spec, cell, config, traffic = _cell()
    runner = R.load_module(os.path.join(BENCH, "runners", "fed_round.py"),
                           "runner_fed_round")
    job = runner.build(config, traffic, SEED)
    ref = runner.reference(job, BENCH, "f32")
    control = runner.reference(job, BENCH, calibrate.control_mode(
        _config()))
    nums = compare.numbers(control, ref)
    assert any(nums[k][0] > v for k, v in traffic["limits"].items()), nums
