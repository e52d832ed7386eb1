"""The plain references against the program at tiny sizes on the CPU, in
float32: the loss of one batch and its gradient with respect to what a
client trains (all weights for distilbert-mlm, the LoRA bank for
qwen2-7b-share), from the benchmark's own seeded weights and batches."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402
from harness import data, weights  # noqa: E402
from harness.precision import Num  # noqa: E402

TINY = dict(n_layers=2, d_model=64, n_heads=4, head_dim=16, d_ff=128,
            vocab_size=512, param_dtype="float32", compute_dtype="float32")
PEFT = {"kind": "lora", "rank": 4, "alpha": 8.0, "targets": ["attn", "mlp"]}


def _config(arch, **kw):
    from repro.configs import get_config
    cfg = get_config(arch).replace(**TINY, **kw)
    d = {k: list(v) if isinstance(v, tuple) else v
         for k, v in dataclasses.asdict(cfg).items()}
    return cfg, d


def _batch(config, batch, seq):
    traffic = {"clients": 1, "local_steps": 1, "batch": batch, "seq": seq,
               "documents": {"length_median": 40, "length_sigma": 1.0,
                             "length_min": 4, "length_max": 200,
                             "pool_min": 20, "pool_max": 200}}
    return data.client_batches(traffic, config, 3000000007)[0][0]


def _reference(name):
    return R.load_module(os.path.join(BENCH, "reference", f"{name}.py"),
                         "reference_" + name.replace("-", "_"))


def _weights(template, stream):
    return weights.maker(template)(weights.seed_key(3000000007, stream))


def _close_trees(a, b, rtol):
    """Each leaf within ``rtol`` of the larger of its own norm and the
    median leaf's (a key's bias has a gradient of zero to rounding)."""
    pairs = [(np.asarray(x, np.float64), np.asarray(y, np.float64))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    med = np.median([np.linalg.norm(y) for _, y in pairs])
    for x, y in pairs:
        assert np.linalg.norm(x - y) <= rtol * max(np.linalg.norm(y), med)


def test_distilbert_reference_matches_program():
    from repro.models.model import init_model
    from repro.models.steps import _objective
    from repro.nn import param as P
    cfg, config = _config("distilbert-mlm", n_kv_heads=4, max_seq_len=64)
    params = _weights(P.unbox(jax.eval_shape(
        lambda k: init_model(k, cfg), jax.random.PRNGKey(0))), 0)
    batch = jax.tree.map(jnp.asarray, _batch(config, 4, 32))
    loss = _reference("distilbert-mlm").make_loss(config)

    def prog(p):
        return _objective(p, cfg, batch, None, "xla")[0]

    def ref(p):
        return loss(p, None, batch, Num("f32"))[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(params)
    lr, gr = jax.value_and_grad(ref)(params)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    _close_trees(gp, gr, 1e-4)


def test_qwen2_lora_reference_matches_program():
    from repro.models.model import init_model
    from repro.models.steps import _objective
    from repro.nn import param as P
    from repro.peft import lora
    cfg, config = _config("qwen2-7b", n_kv_heads=2)
    space = lora(PEFT["rank"], alpha=PEFT["alpha"], targets=("attn", "mlp"))
    template = P.unbox(jax.eval_shape(lambda k: init_model(k, cfg),
                                      jax.random.PRNGKey(0)))
    base = _weights(template, 0)
    bank = _weights(jax.eval_shape(
        lambda p: space.inject(p, jax.random.PRNGKey(0)), template), 1)
    # a bank that has trained: nonzero B factors
    bank = jax.tree.map(lambda x: x + 0.05 * jnp.cos(
        jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape), bank)
    batch = jax.tree.map(jnp.asarray, _batch(config, 2, 32))
    loss = _reference("qwen2-7b-share").make_loss(config, PEFT)

    def prog(b):
        return _objective(space.merge(base, b), cfg, batch, None, "xla")[0]

    def ref(b):
        return loss(b, base, batch, Num("f32"))[0]

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(prog)(bank)
    lr, gr = jax.value_and_grad(ref)(bank)
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    _close_trees(gp, gr, 1e-4)
