"""Granite-4.0-H (hybrid_mlp) against its plain float32 reference
(``bench/reference/granite-4.0-h-micro-share.py``) at one whole period of
a small Granite-shaped model on the CPU, with seeded random weights and a
trained-looking LoRA bank applied unmerged: logits, loss and the bank's
gradients.  Then the witness: the merged step (``W + ΔW`` rounded to a
bfloat16 base) misses a tolerance the unmerged step meets.  Then the
Mamba2 mixer's choice of the chunked SSD by sequence length, and a
federated round through ``FedSession`` that records the chunk."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core.rounds import FedSession, RoundPlan
from repro.models.model import apply_model, init_model
from repro.models.steps import _objective
from repro.nn import param as P
from repro.nn.layers import LowRank, project
from repro.nn.mamba import apply_mamba2, init_mamba2, ssd_chunk
from repro.nn.param import ParamCtx
from repro.obs.metrics import registry
from repro.peft import lora

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "bench"))

from harness.precision import Num  # noqa: E402

TARGETS = ("attn", "mamba", "mlp")
PEFT = {"kind": "lora", "rank": 4, "alpha": 8.0, "targets": list(TARGETS)}
B, S = 2, 48


def _reference():
    path = os.path.join(ROOT, "bench", "reference",
                        "granite-4.0-h-micro-share.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**kw):
    """One whole period (9 Mamba2 + 1 attention) at width 256, chunk 16."""
    return get_config("granite-4.0-h-micro").reduced().replace(ssm_chunk=16,
                                                               **kw)


def _config(cfg):
    d = dataclasses.asdict(cfg)
    d["mamba_d_head"] = 64
    return d


def _weights(cfg, key=1):
    """Seeded normal weights; norm gains near one, an embedding wide enough
    that the logits are not flat."""
    tmpl = P.unbox(jax.eval_shape(lambda k: init_model(k, cfg),
                                  jax.random.PRNGKey(0)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tmpl)
    k = jax.random.PRNGKey(key)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(jax.random.fold_in(k, i), leaf.shape,
                              jnp.float32)
        v = {"scale": 1.0 + 0.1 * z, "table": 0.3 * z,
             "conv_w": 0.3 * z}.get(name, 0.06 * z)
        out.append(v.astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _batch(cfg, seed=0):
    t = np.random.default_rng(seed).integers(5, cfg.vocab_size, (B, S + 1))
    return {"tokens": jnp.asarray(t[:, :-1], jnp.int32),
            "targets": jnp.asarray(t[:, 1:], jnp.int32),
            "loss_mask": jnp.ones((B, S), jnp.float32)}


def _trained_bank(space, base):
    """Nonzero B factors, as after training."""
    bank = space.inject(base, jax.random.PRNGKey(2))
    return jax.tree.map(lambda x: x + 0.05 * jnp.cos(
        jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape), bank)


def _close_trees(a, b, rtol):
    """Each leaf within ``rtol`` of the larger of its own norm and the
    median leaf's."""
    pairs = [(np.asarray(x, np.float64), np.asarray(y, np.float64))
             for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]
    med = np.median([np.linalg.norm(y) for _, y in pairs])
    worst = max(np.linalg.norm(x - y) / max(np.linalg.norm(y), med)
                for x, y in pairs)
    assert worst <= rtol, worst


@pytest.mark.parametrize("seq", [48, 12], ids=["chunked_ssd", "token_scan"])
def test_program_matches_reference(seq):
    """Float32 at ``highest`` on both sides, so the two differ by summation
    order alone: 1e-5 relative on logits and loss, 1e-4 by worst leaf on
    the bank's gradients (their sums run over every position)."""
    cfg = _cfg()
    assert (ssd_chunk(cfg, seq) > 0) == (seq > cfg.ssm_chunk)
    space = lora(PEFT["rank"], alpha=PEFT["alpha"], targets=TARGETS)
    base = _weights(cfg)
    bank = _trained_bank(space, base)
    batch = jax.tree.map(lambda x: x[:, :seq], _batch(cfg))
    ref = _reference()
    forward = ref.make_forward(_config(cfg), PEFT)
    loss = ref.make_loss(_config(cfg), PEFT)
    with jax.default_matmul_precision("highest"):
        got, _, _ = jax.jit(lambda b: apply_model(space.unmerged(base, b),
                                                  cfg, batch))(bank)
        want = jax.jit(lambda b: forward(b, base, batch["tokens"],
                                         Num("f32")))(bank)
        lp, gp = jax.jit(jax.value_and_grad(lambda b: _objective(
            space.unmerged(base, b), cfg, batch, None, "xla")[0]))(bank)
        lr, gr = jax.jit(jax.value_and_grad(
            lambda b: loss(b, base, batch, Num("f32"))[0]))(bank)
    want = np.asarray(want)
    assert float(np.std(want)) > 0.1          # the logits are not flat
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    assert float(lp) == pytest.approx(float(lr), rel=1e-5)
    _close_trees(gp, gr, 1e-4)


def test_merged_bf16_step_misses_what_unmerged_meets():
    """A bfloat16 base with float32 activations, so that the merge's
    rounding of ``W + ΔW`` is the only rounding that matters.  A bank one
    sign-descent step of 1e-4 from a fresh one (|ΔW| / |W| about 1.5e-4,
    under half an ulp of most bf16 weights, as after a few LoRA steps at
    lr 2e-4) lowers the reference's loss by some 7e-4.  The unmerged step
    sees that drop to within 1% (float32 summation order); the merged step
    rounds most of ΔW away and misses it by more than half."""
    cfg = _cfg(param_dtype="bfloat16", compute_dtype="float32")
    space = lora(4, alpha=4.0, targets=TARGETS)
    peft = dict(PEFT, alpha=4.0)
    base = _weights(cfg)
    batch = _batch(cfg)
    loss = _reference().make_loss(_config(cfg), peft)
    ref = jax.jit(lambda b: loss(b, base, batch, Num("f32"))[0])
    fresh = space.inject(base, jax.random.PRNGKey(2))
    g = jax.grad(ref)(fresh)
    bank = jax.tree_util.tree_map_with_path(
        lambda p, b, gb: b - 1e-4 * jnp.sign(gb)
        if str(p[-1].key) == "b" else b, fresh, g)
    want = float(ref(fresh) - ref(bank))

    def drop(view):
        f = jax.jit(lambda b: _objective(view(base, b), cfg, batch, None,
                                         "xla")[0])
        return float(f(fresh) - f(bank))

    assert want > 1e-4
    assert abs(drop(space.unmerged) - want) <= 0.01 * want
    assert abs(drop(space.merge) - want) > 0.5 * want


def test_small_bank_term_reaches_a_bf16_projection():
    """A bank whose term is about 1e-3 of a bfloat16 projection's output,
    under half the output's spacing almost everywhere: ``project`` adds it
    to the float32 projection and rounds once, so it reaches the output in
    expectation (round to nearest is unbiased over the outputs' positions
    on the grid): 95% or more of it, where a projection rounded before the
    term is added keeps about 60%."""
    k = jax.random.split(jax.random.PRNGKey(5), 4)
    d, f, r = 1024, 512, 8
    x = jax.random.normal(k[0], (64, d)).astype(jnp.bfloat16)
    w = (jax.random.normal(k[1], (d, f)) / d ** 0.5).astype(jnp.bfloat16)
    a = jax.random.normal(k[2], (d, r)) / d ** 0.5
    b = 4e-4 * jnp.sign(jax.random.normal(k[3], (r, f)))
    term = (x.astype(jnp.float32) @ a) @ b
    out = project("nd,df->nf", x, LowRank(w, a, b))
    assert out.dtype == jnp.bfloat16
    seen = (out.astype(jnp.float32)
            - project("nd,df->nf", x, w).astype(jnp.float32))
    assert float(jnp.sum(seen * term) / jnp.sum(term * term)) >= 0.95


def test_mixer_picks_chunked_ssd_by_length():
    """Longer than ``ssm_chunk``: the chunked SSD, a scan over T / chunk
    chunks; otherwise the token scan, T steps.  Both compute the same
    recurrence: 1e-4 relative in float32 (the chunked form sums in another
    order)."""
    cfg = _cfg()
    params = P.unbox(init_mamba2(ParamCtx(jax.random.PRNGKey(3),
                                          jnp.float32), cfg.d_model,
                                 cfg.ssm_state))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model))

    def scan_lengths(c):
        jaxpr = jax.make_jaxpr(lambda v: apply_mamba2(params, v, c)[0])(x)
        return sorted(e.params["length"] for e in jaxpr.eqns
                      if e.primitive.name == "scan")

    assert ssd_chunk(cfg, S) == 16 and ssd_chunk(cfg, 16) == 0
    assert scan_lengths(cfg) == [S // 16]
    long_chunk = cfg.replace(ssm_chunk=S)
    assert ssd_chunk(long_chunk, S) == 0
    assert scan_lengths(long_chunk) == [S]
    with jax.default_matmul_precision("highest"):
        got = apply_mamba2(params, x, cfg)[0]
        want = apply_mamba2(params, x, long_chunk)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-4 * float(jnp.abs(want).max()))


def test_lora_round_through_fed_session_records_the_chunk():
    """The normal path: the parallel engine, a LoRA space over all three
    target groups, two clients in one shard; the bank trains, the base
    stays, and the round records the SSD chunk its program ran."""
    cfg = _cfg()
    base = _weights(cfg)
    space = lora(4, alpha=8.0, targets=TARGETS)
    clients = [[_batch(cfg, seed=10 * k + i) for i in range(2)]
               for k in range(2)]
    sess = FedSession(cfg, optim.adam(1e-3), RoundPlan(
        n_rounds=2, engine="parallel", param_space=space, telemetry=False))
    merged, hist = sess.run(base, clients)
    assert len(hist) == 2 and all(np.isfinite(h.loss) for h in hist)
    assert hist[1].loss < hist[0].loss
    assert registry().gauge("train.ssd_chunk").value == 16
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), merged, base)
    assert moved["layers"]["mamba"]["mamba"]["in_proj"] > 0
    assert moved["layers"]["mamba"]["mamba"]["conv_w"] == 0
