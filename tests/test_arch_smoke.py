"""Per-architecture smoke tests: REDUCED variant of each assigned family runs
one forward + one train step on CPU; output shapes + no NaNs.  A reduced
config keeps 2 layers, or one whole period of a layer pattern.  The FULL
configs are exercised only via the dry-run (ShapeDtypeStruct, no alloc)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import all_configs, get_config
from repro.models.model import apply_model, init_cache, init_model
from repro.models.steps import make_train_step
from repro.nn import param as P

ARCHS = list(all_configs())
KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=16, train=True, rng_seed=1):
    rng = np.random.default_rng(rng_seed)
    b = {"tokens": jnp.asarray(rng.integers(5, cfg.vocab_size, (B, S)),
                               jnp.int32)}
    if train:
        b["targets"] = jnp.asarray(rng.integers(5, cfg.vocab_size, (B, S)),
                                   jnp.int32)
        b["loss_mask"] = jnp.ones((B, S), jnp.float32)
    if cfg.arch_type == "vlm":
        b["image_embeds"] = jnp.asarray(
            rng.normal(0, 0.1, (B, cfg.n_image_tokens, cfg.d_model)),
            jnp.float32)
    if cfg.arch_type == "audio":
        b["frames"] = jnp.asarray(
            rng.normal(0, 0.1, (B, cfg.n_audio_frames, cfg.d_model)),
            jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_forward(arch):
    cfg = get_config(arch).reduced()
    assert cfg.n_layers == (len(cfg.layer_pattern) or 2)
    assert cfg.d_model <= 512
    if cfg.n_experts:
        assert cfg.n_experts <= 4
    params = P.unbox(init_model(KEY, cfg))
    B, S = 2, 16
    logits, cache, aux = apply_model(params, cfg, _batch(cfg, B, S, False),
                                     mode="train")
    assert logits.shape == (B, S, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert cache is None
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    params = P.unbox(init_model(KEY, cfg))
    opt = optim.adam(1e-4)
    opt_state = P.unbox(opt.init(params))
    step = jax.jit(make_train_step(cfg, opt))
    b = _batch(cfg)
    p1, o1, m1 = step(params, opt_state, b)
    p2, o2, m2 = step(p1, o1, b)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])        # same batch: must improve
    for l in jax.tree.leaves(p2):
        assert bool(jnp.all(jnp.isfinite(l)))


DECODE_ARCHS = [a for a in ARCHS if get_config(a).arch_type != "mlm"]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_prefill_decode_matches_train(arch):
    """prefill(S-1) + decode(1) logits == full train-mode forward at pos S-1."""
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        cfg = cfg.replace(capacity_factor=float(cfg.n_experts))  # no drops
    params = P.unbox(init_model(KEY, cfg))
    B, S = 2, 12
    batch = _batch(cfg, B, S, train=False)
    full, _, _ = apply_model(params, cfg, batch, mode="train")
    cache = init_cache(cfg, B, S)
    pre = dict(batch, tokens=batch["tokens"][:, :S - 1])
    _, cache, _ = apply_model(params, cfg, pre, mode="prefill", cache=cache)
    dec = {k: v for k, v in batch.items() if k != "tokens"}
    dec["tokens"] = batch["tokens"][:, S - 1:]
    lg, cache, _ = apply_model(params, cfg, dec, mode="decode", cache=cache)
    assert int(cache["index"]) == S
    ref = np.asarray(full[:, S - 1], np.float32)
    got = np.asarray(lg[:, 0], np.float32)
    np.testing.assert_allclose(got, ref, rtol=2e-3,
                               atol=2e-3 * np.abs(ref).max())


def test_sliding_window_ring_decode():
    """Window variant: decoding past the window with the ring cache matches
    train-mode sliding-window attention."""
    cfg = get_config("phi4-mini-3.8b").reduced().replace(sliding_window=8)
    params = P.unbox(init_model(KEY, cfg))
    B, S = 1, 14
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(5, cfg.vocab_size, (B, S)), jnp.int32)
    full, _, _ = apply_model(params, cfg, {"tokens": toks}, mode="train")
    cache = init_cache(cfg, B, cfg.sliding_window)       # ring-sized cache
    _, cache, _ = apply_model(params, cfg, {"tokens": toks[:, :8]},
                              mode="prefill", cache=cache)
    outs = []
    for t in range(8, S):
        lg, cache, _ = apply_model(params, cfg, {"tokens": toks[:, t:t + 1]},
                                   mode="decode", cache=cache)
        outs.append(lg[:, 0])
    got = np.asarray(jnp.stack(outs, 1), np.float32)
    ref = np.asarray(full[:, 8:], np.float32)
    np.testing.assert_allclose(got, ref, rtol=2e-3, atol=2e-3 * np.abs(ref).max())


def test_full_configs_validate_and_count():
    """Full configs build abstract params with the published scale."""
    expected_min = {"qwen2-7b": 7e9, "qwen3-14b": 13e9, "nemotron-4-340b": 3e11,
                    "phi4-mini-3.8b": 3.5e9, "llama-3.2-vision-90b": 8e10}
    for arch, lo in expected_min.items():
        cfg = get_config(arch)
        cfg.validate()
        boxed = jax.eval_shape(lambda k: init_model(k, cfg),
                               jax.random.PRNGKey(0))
        n = P.count_params(boxed)
        assert n >= lo, f"{arch}: {n:.3e} < {lo:.1e}"
        assert n < lo * 2.2, f"{arch}: {n:.3e} implausibly large"
