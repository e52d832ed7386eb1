"""Training-step mechanics: microbatch equivalence, clipping, optimizers,
loss masking, the LM head at the loss-masked positions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro import optim
from repro.configs import get_config
from repro.models.model import apply_model, init_model
from repro.models.steps import (_objective, head_capacity, lm_loss,
                                make_eval_step, make_masked_train_step,
                                make_train_step)
from repro.nn import param as P

KEY = jax.random.PRNGKey(0)


def test_lm_loss_matches_naive():
    rng = np.random.default_rng(0)
    B, S, V = 2, 6, 11
    logits = jnp.asarray(rng.normal(0, 2, (B, S, V)), jnp.float32)
    tgt = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    mask = jnp.asarray(rng.integers(0, 2, (B, S)), jnp.float32)
    loss, n = lm_loss(logits, tgt, mask)
    lp = jax.nn.log_softmax(logits, -1)
    want = -np.sum(np.take_along_axis(np.asarray(lp), np.asarray(tgt)[..., None],
                                      -1)[..., 0] * np.asarray(mask))
    want /= max(float(mask.sum()), 1.0)
    assert float(loss) == pytest.approx(want, rel=1e-5)
    assert float(n) == float(mask.sum())


def test_lm_loss_ignores_masked_positions():
    rng = np.random.default_rng(1)
    B, S, V = 1, 4, 7
    logits = jnp.asarray(rng.normal(0, 1, (B, S, V)), jnp.float32)
    t1 = jnp.asarray(rng.integers(0, V, (B, S)), jnp.int32)
    t2 = t1.at[0, 0].set((int(t1[0, 0]) + 1) % V)       # differs only at masked
    mask = jnp.asarray([[0, 1, 1, 1]], jnp.float32)
    assert float(lm_loss(logits, t1, mask)[0]) == \
        float(lm_loss(logits, t2, mask)[0])


def _setup():
    cfg = get_config("phi4-mini-3.8b").reduced().replace(n_layers=2)
    params = P.unbox(init_model(KEY, cfg))
    rng = np.random.default_rng(0)
    B, S = 4, 8
    batch = {
        "tokens": jnp.asarray(rng.integers(5, cfg.vocab_size, (B, S)), jnp.int32),
        "targets": jnp.asarray(rng.integers(5, cfg.vocab_size, (B, S)), jnp.int32),
        "loss_mask": jnp.ones((B, S), jnp.float32),
    }
    return cfg, params, batch


def test_microbatch_equivalence():
    cfg, params, batch = _setup()
    opt = optim.sgd(1e-2)                    # linear in grads -> exact check
    o0 = P.unbox(opt.init(params))
    s1 = jax.jit(make_train_step(cfg, opt, microbatches=1, clip_norm=0.0))
    s4 = jax.jit(make_train_step(cfg, opt, microbatches=4, clip_norm=0.0))
    p1, _, m1 = s1(params, o0, batch)
    p4, _, m4 = s4(params, o0, batch)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-6)
    assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-4)


def test_grad_clipping_caps_update():
    cfg, params, batch = _setup()
    opt = optim.sgd(1.0)
    o0 = P.unbox(opt.init(params))
    step = jax.jit(make_train_step(cfg, opt, clip_norm=1e-6))
    p1, _, m = step(params, o0, batch)
    delta = optim.global_norm(jax.tree.map(lambda a, b: a - b, p1, params))
    assert float(delta) <= 1.2e-6
    assert float(m["grad_norm"]) > 0


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_adam_decreases_quadratic(seed):
    rng = np.random.default_rng(seed)
    target = jnp.asarray(rng.normal(0, 1, (8,)), jnp.float32)
    params = {"w": jnp.zeros((8,))}
    opt = optim.adam(0.1)
    state = opt.init(params)

    def loss(p):
        return jnp.sum((p["w"] - target) ** 2)

    init = float(loss(params))
    for _ in range(120):
        g = jax.grad(loss)(params)
        upd, state = opt.update(g, state, params)
        params = optim.apply_updates(params, upd)
    assert float(loss(params)) < 0.02 * max(init, 1.0)


def test_adamw_weight_decay_shrinks():
    params = {"w": jnp.ones((4,))}
    opt = optim.adamw(1e-2, weight_decay=0.1)
    state = opt.init(params)
    g = {"w": jnp.zeros((4,))}
    upd, state = opt.update(g, state, params)
    p2 = optim.apply_updates(params, upd)
    assert float(jnp.max(p2["w"])) < 1.0


def test_bf16_state_dtype():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    opt = optim.adam(1e-2, state_dtype=jnp.bfloat16)
    st_ = opt.init(params)
    assert st_["m"]["w"].dtype == jnp.bfloat16
    assert st_["v"]["w"].dtype == jnp.bfloat16


def test_eval_step_matches_train_loss():
    cfg, params, batch = _setup()
    ev = jax.jit(make_eval_step(cfg))
    opt = optim.sgd(0.0)
    step = jax.jit(make_train_step(cfg, opt, clip_norm=0.0))
    _, _, m = step(params, P.unbox(opt.init(params)), batch)
    assert float(ev(params, batch)["loss"]) == pytest.approx(
        float(m["loss"]), rel=1e-5)


# ---------------------------------------------------------------------------
# LM head at the loss-masked positions
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("most,positions,want", [
    (614, 4096, 768),      # DistilBERT 32 x 128 at 15%: the floor K0
    (0, 4096, 768),        # nothing masked: still the floor
    (768, 4096, 768),
    (769, 4096, 1536),     # one over the floor: the next rung
    (3073, 4096, None),    # the ladder reaches B*S: every position
    (4096, 4096, None),    # all-ones (CLM) masks
    (10, 64, None),        # the floor is already every position
    (37, 512, 128),
    (150, 512, 256),
])
def test_head_capacity_ladder(most, positions, want):
    assert head_capacity(most, positions, 0.15) == want


def _mlm_setup(B=4, S=128):
    cfg = get_config("distilbert-mlm").reduced()
    params = P.unbox(init_model(KEY, cfg))
    rng = np.random.default_rng(0)
    tokens = rng.integers(5, cfg.vocab_size, (B, S)).astype(np.int32)
    batch = {"tokens": tokens,
             "targets": rng.integers(5, cfg.vocab_size, (B, S)).astype(
                 np.int32)}
    return cfg, params, batch, rng


def _loss_and_grads(cfg, params, batch, capacity):
    fn = jax.jit(jax.value_and_grad(
        lambda p, b: _objective(p, cfg, b, None, "xla", capacity),
        has_aux=True))
    (_, metrics), grads = fn(params, batch)
    return metrics, grads


@pytest.mark.parametrize("rate,rung", [(0.15, 128), (0.3, 256), (0.0, 128)],
                         ids=["typical", "over_floor", "none_masked"])
def test_gathered_head_matches_full_head(rate, rung):
    """The head at the masked positions only gives the full head's loss,
    token count and every gradient (B*S = 512: the floor K0 is 128, so the
    gather runs; 30% masks need the next rung, 256)."""
    cfg, params, batch, rng = _mlm_setup()
    mask = (rng.random(batch["tokens"].shape) < rate).astype(np.float32)
    batch["loss_mask"] = mask
    cap = head_capacity(int(mask.sum()), mask.size, cfg.mlm_mask_rate)
    assert cap == rung
    m_full, g_full = _loss_and_grads(cfg, params, batch, None)
    m_rows, g_rows = _loss_and_grads(cfg, params, batch, cap)
    assert float(m_rows["tokens"]) == float(m_full["tokens"]) \
        == max(float(mask.sum()), 1.0)
    np.testing.assert_allclose(float(m_rows["loss"]), float(m_full["loss"]),
                               rtol=1e-5)
    if rate == 0.0:
        assert float(m_rows["loss"]) == 0.0
    for a, b in zip(jax.tree.leaves(g_rows), jax.tree.leaves(g_full)):
        a, b = np.asarray(a), np.asarray(b)
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * float(np.max(np.abs(b))))


def test_full_head_paths_bitwise_equal_the_plain_objective():
    """All-ones (CLM) masks take the head at every position, and that path
    (capacity None, or a capacity of every position) is bitwise the plain
    apply_model + lm_loss objective."""
    cfg, params, batch, _ = _mlm_setup()
    batch["loss_mask"] = np.ones(batch["tokens"].shape, np.float32)
    assert head_capacity(batch["loss_mask"].size, batch["loss_mask"].size,
                         cfg.mlm_mask_rate) is None

    def plain(p, b):
        logits, _, aux = apply_model(p, cfg, b, mode="train")
        loss, _ = lm_loss(logits, b["targets"],
                          b["loss_mask"].astype(jnp.float32))
        return loss + cfg.router_aux_coef * aux

    want, g_want = jax.jit(jax.value_and_grad(plain))(params, batch)
    for cap in (None, batch["loss_mask"].size):
        m, grads = _loss_and_grads(cfg, params, batch, cap)
        assert float(m["loss"]) == float(want)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(g_want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_masked_step_with_head_capacity_tracks_full_head():
    """The engine's step (masked FFDAPT form, Adam) with the gathered head
    lands where the full-head step lands."""
    cfg, params, batch, rng = _mlm_setup()
    batch["loss_mask"] = (rng.random(batch["tokens"].shape)
                          < 0.15).astype(np.float32)
    opt = optim.adam(1e-3)
    o0 = P.unbox(opt.init(params))
    fmask = jnp.zeros((cfg.n_layers,), jnp.float32)
    full = jax.jit(make_masked_train_step(cfg, opt))
    rows = jax.jit(make_masked_train_step(cfg, opt, head_capacity=128))
    p_full, _, m_full = full(params, o0, batch, fmask)
    p_rows, _, m_rows = rows(params, o0, batch, fmask)
    assert float(m_rows["loss"]) == pytest.approx(float(m_full["loss"]),
                                                  rel=1e-5)
    for a, b in zip(jax.tree.leaves(p_rows), jax.tree.leaves(p_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
