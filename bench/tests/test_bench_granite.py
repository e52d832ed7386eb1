"""The granite-4.0-h-micro-share configuration at tiny sizes on the CPU:
the plain reference against the program (loss and the bank's gradients,
from the benchmark's own seeded weights and batches), and the FLOP count
against the compiled unmerged LoRA step.  Whole runs of the cell are in
``test_bench_granite_runs.py``."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from harness import data, weights  # noqa: E402
from harness.precision import Num  # noqa: E402

NAME, CONFIG = "granite-lora-fdapt", "granite-4.0-h-micro-share"
SEED = 3000001511
# one whole period (9 Mamba2, 1 attention); two Mamba2 heads of 64
TINY = dict(d_model=64, n_heads=2, n_kv_heads=1, head_dim=32, d_ff=128,
            vocab_size=512, ssm_state=16, ssm_chunk=16)


def _config(**kw):
    config = json.load(open(os.path.join(BENCH, "configs", CONFIG + ".json")))
    return dict(config, **TINY, **kw,
                peft=dict(config["peft"], rank=4, alpha=8.0))


def _cell(**kw):
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = {"name": NAME, "config": CONFIG, "traffic": NAME, "chips": 1}
    config = _config(param_dtype="float32", compute_dtype="float32", **kw)
    traffic = json.load(open(os.path.join(BENCH, "traffic", NAME + ".json")))
    traffic = dict(traffic, batch=1, seq=64)
    return spec, cell, config, traffic


def _program(config):
    runner = R.load_module(os.path.join(BENCH, "runners", "fed_round.py"),
                           "runner_fed_round")
    return runner.model_config(config)


def test_granite_reference_matches_program():
    """Float32 on both sides at ``highest``: 1e-5 on the loss, 1e-4 by
    worst leaf on the bank's gradients (summation order only)."""
    from repro.models.model import init_model
    from repro.models.steps import _objective
    from repro.nn import param as P
    from repro.peft import lora
    config = _config(param_dtype="float32", compute_dtype="float32")
    cfg = _program(config)
    pe = config["peft"]
    space = lora(pe["rank"], alpha=pe["alpha"], targets=tuple(pe["targets"]))
    template = P.unbox(jax.eval_shape(lambda k: init_model(k, cfg),
                                      jax.random.PRNGKey(0)))
    base = weights.maker(template)(weights.seed_key(SEED, 0))
    bank = weights.maker(jax.eval_shape(
        lambda p: space.inject(p, jax.random.PRNGKey(0)), template))(
            weights.seed_key(SEED, 1))
    bank = jax.tree.map(lambda x: x + 0.05 * jnp.cos(
        jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape), bank)
    traffic = {"clients": 1, "local_steps": 1, "batch": 2, "seq": 48,
               "documents": {"length_median": 40, "length_sigma": 1.0,
                             "length_min": 4, "length_max": 200,
                             "pool_min": 20, "pool_max": 200}}
    batch = jax.tree.map(jnp.asarray,
                         data.client_batches(traffic, config, SEED)[0][0])
    loss = R.load_module(os.path.join(BENCH, "reference", CONFIG + ".py"),
                         "reference_granite").make_loss(config, pe)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.jit(jax.value_and_grad(lambda b: _objective(
            space.unmerged(base, b), cfg, batch, None, "xla")[0]))(bank)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda b: loss(b, base, batch, Num("f32"))[0]))(bank)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    pairs = list(zip(jax.tree.leaves(gp), jax.tree.leaves(gr)))
    norms = [float(jnp.linalg.norm(y)) for _, y in pairs]
    med = sorted(norms)[len(norms) // 2]
    for (x, y), n in zip(pairs, norms):
        assert float(jnp.linalg.norm(x - y)) <= 1e-4 * max(n, med)


@pytest.mark.parametrize("remat", [False, True])
def test_granite_count_differs_by_named_terms(remat):
    """The compiled unmerged LoRA step against ``bench/flops``: without
    remat they differ by exactly the named terms; remat adds at most one
    forward of every layer."""
    from repro import optim
    from repro.core.strategy import FedAvg
    from repro.models.steps import abstract_train_state
    from repro.peft import lora
    from repro.telemetry.cost import analyze
    from repro.telemetry.step import train_batch_struct
    b, s = 2, 64
    config = _config(remat=remat)
    cfg = _program(config)
    pe = config["peft"]
    space = lora(pe["rank"], alpha=pe["alpha"], targets=tuple(pe["targets"]))
    opt = optim.adam(1e-3)
    params, _ = abstract_train_state(cfg, opt)
    bank = jax.eval_shape(lambda p: space.inject(p, jax.random.PRNGKey(0)),
                          params)
    step = FedAvg().make_client_step(cfg, opt, space=space)
    compiled = jax.jit(step).lower(bank, jax.eval_shape(opt.init, bank),
                                   params, train_batch_struct(cfg, b, s)
                                   ).compile()
    got = analyze(compiled.as_text()).dot_flops / (b * s)
    flops = R.load_module(os.path.join(BENCH, "flops", CONFIG + ".py"),
                          "flops_granite")
    ours = flops.train_flops_per_token(config, {"seq": s}, 1.0)
    d, ff, r = config["d_model"], config["d_ff"], pe["rank"]
    h, hd, n = config["n_heads"], config["head_dim"], config["ssm_state"]
    q, p = config["ssm_chunk"], config["mamba_d_head"]
    d_in = config["ssm_expand"] * d
    heads = d_in // p
    n_mamba = config["layer_pattern"].count("mamba")
    n_attn = config["layer_pattern"].count("attention")
    in_out = 2 * d_in + 2 * n + heads
    named = (
        # the masked halves of the attention scores and of the SSD's
        # chunk matmuls (C B^T and its weighted sum of x), in each pass
        n_attn * 3 * 2 * 2 * (s - (s + 1) / 2) * h * hd
        + n_mamba * 3 * (2 * n + 2 * heads * p) * (q - (q + 1) / 2)
        # the zero initial state's gradient, two state-sized matmuls per
        # sequence and Mamba2 layer
        + n_mamba * 2 * 2 * heads * p * n / s
        # the first layer reads the frozen embedding: no activation
        # gradient through its in-projection, nor through its LoRA A
        - 2 * d * in_out - 2 * d * r)
    if not remat:
        assert got == pytest.approx(ours + named, rel=1e-9)
    else:
        mlp = 3 * 2 * d * ff
        forward = (n_mamba * (2 * d * in_out + 2 * d_in * d + mlp)
                   + n_attn * (4 * d * h * hd * 2 + mlp)
                   + n_mamba * (2 * n + 2 * heads * p) * q
                   + n_attn * 2 * 2 * s * h * hd)
        assert ours + named < got <= ours + named + forward
