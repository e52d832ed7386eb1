"""Beyond-paper federated strategies: FedAvgM, FedProx, upload compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.configs import get_config
from repro.core import strategies as S
from repro.core.fedavg import fedavg
from repro.models.model import init_model
from repro.nn import param as P

KEY = jax.random.PRNGKey(0)


def _trees(k=3, seed=0):
    rng = np.random.default_rng(seed)
    return [{"w": jnp.asarray(rng.normal(0, 1, (6,)), jnp.float32)}
            for _ in range(k)]


def test_fedavgm_zero_beta_is_fedavg():
    g = _trees(1)[0]
    clients = _trees(3, 1)
    new, st = S.fedavgm_update(g, clients, [1, 1, 1], S.ServerState(),
                               beta=0.0, lr=1.0)
    want = fedavg(clients, [1, 1, 1])
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(want["w"]),
                               rtol=1e-6)


def test_fedavgm_momentum_accumulates():
    g = {"w": jnp.zeros((4,))}
    clients = [{"w": jnp.ones((4,))}]
    st = S.ServerState()
    new1, st = S.fedavgm_update(g, clients, [1], st, beta=0.9)
    new2, st = S.fedavgm_update(new1, [{"w": new1["w"] + 1.0}], [1], st,
                                beta=0.9)
    # second step's momentum includes 0.9 * first delta
    assert float(new2["w"][0] - new1["w"][0]) > 1.0


def test_quantize8_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    d = {"w": jnp.asarray(rng.normal(0, 1, (256,)), jnp.float32)}
    dq, nbytes = S.quantize8(d)
    err = float(jnp.max(jnp.abs(dq["w"] - d["w"])))
    scale = float(jnp.max(jnp.abs(d["w"]))) / 127
    assert err <= scale * 0.51 + 1e-7
    assert nbytes == 256 + 4                     # 1B/entry + scale
    assert nbytes < S.dense_bytes(d)


def test_topk_zero_delta_counts_minimum():
    # all-zero leaf (e.g. a frozen layer's delta): threshold is 0, which
    # "keeps" everything — accounting must not bill the whole leaf
    d = {"w": jnp.zeros((100,), jnp.float32)}
    _, nbytes = S.topk_sparsify(d, frac=0.1)
    assert nbytes == 8


def test_topk_keeps_largest():
    d = {"w": jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 0.0], jnp.float32)}
    # k = ceil(0.34 * 6) = 3: the third survivor is 0.2
    sp, nbytes = S.topk_sparsify(d, frac=0.34)
    w = np.asarray(sp["w"])
    assert w[1] == -5.0 and w[3] == 3.0 and w[2] == 0.2
    assert np.count_nonzero(w) == 3
    assert nbytes == 3 * 8


def test_compressed_fedavg_identity_compressor():
    rng = np.random.default_rng(2)
    g = {"w": jnp.asarray(rng.normal(0, 1, (512,)), jnp.float32)}
    clients = [{"w": jnp.asarray(rng.normal(0, 1, (512,)), jnp.float32)}
               for _ in range(2)]
    a, b_dense = S.compressed_fedavg(g, clients, [1, 2])
    want = fedavg(clients, [1, 2])
    np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(want["w"]),
                               rtol=1e-5, atol=1e-6)
    a8, b_q = S.compressed_fedavg(g, clients, [1, 2], compressor=S.quantize8)
    assert b_q < b_dense / 3                      # ~4x smaller upload
    np.testing.assert_allclose(np.asarray(a8["w"]), np.asarray(want["w"]),
                               atol=0.06)


def test_fedprox_step_pulls_toward_anchor():
    cfg = get_config("distilbert-mlm").reduced().replace(n_layers=2)
    params = P.unbox(init_model(KEY, cfg))
    anchor = params
    opt = optim.sgd(1e-2)
    # huge mu and a zero-information batch: the prox term dominates, so a
    # step from a perturbed point must move BACK toward the anchor
    step = jax.jit(S.make_fedprox_step(cfg, opt, mu=100.0, clip_norm=0.0))
    rng = np.random.default_rng(0)
    B, Sq = 2, 8
    batch = {
        "tokens": jnp.asarray(rng.integers(5, cfg.vocab_size, (B, Sq)), jnp.int32),
        "targets": jnp.asarray(rng.integers(5, cfg.vocab_size, (B, Sq)), jnp.int32),
        "loss_mask": jnp.ones((B, Sq), jnp.float32),
    }
    perturbed = jax.tree.map(lambda p: p + 0.1, params)
    o = P.unbox(opt.init(perturbed))
    d_before = float(S.proximal_penalty(perturbed, anchor))
    p1, _, m = step(perturbed, o, anchor, batch)
    d_after = float(S.proximal_penalty(p1, anchor))
    assert d_after < d_before
    assert float(m["prox"]) > 0


# ---------------------------------------------------------------------------
# Property tests: the compression laws (hypothesis; deterministic shim
# fallback in tests/_hyp.py when the real library is absent)
# ---------------------------------------------------------------------------

from _hyp import given, settings, st  # noqa: E402

from repro.core import strategy as ST  # noqa: E402


def _distinct_magnitudes(seed, n):
    """Values with pairwise-distinct |.| so the exact-count law has no
    threshold ties (tie behavior is pinned separately below)."""
    rng = np.random.default_rng(seed)
    mags = np.cumsum(rng.uniform(0.1, 1.0, n))     # strictly increasing > 0
    signs = rng.choice([-1.0, 1.0], n)
    return jnp.asarray(rng.permutation(mags * signs), jnp.float32)


@settings(max_examples=25, deadline=None)
@given(n=st.integers(min_value=1, max_value=97),
       frac=st.floats(min_value=0.01, max_value=1.0),
       seed=st.integers(min_value=0, max_value=2**31))
def test_topk_exact_count_law(n, frac, seed):
    """topk_sparsify keeps EXACTLY topk_count(n, frac) = ceil(frac*n)
    entries when magnitudes are distinct — and the eager compressor, the
    trace-safe compressor, and the static byte accounting all agree."""
    d = {"w": _distinct_magnitudes(seed, n)}
    k = ST.topk_count(n, frac)
    assert k == min(n, max(1, int(np.ceil(frac * n))))

    sp, nbytes = S.topk_sparsify(d, frac=frac)
    w = np.asarray(sp["w"])
    assert np.count_nonzero(w) == k
    # survivors are exactly the k largest magnitudes
    keep = np.argsort(-np.abs(np.asarray(d["w"])))[:k]
    assert set(np.flatnonzero(w)) == set(keep.tolist())
    np.testing.assert_array_equal(w[keep], np.asarray(d["w"])[keep])
    # engine parity: jit/trace-safe compressor selects the same entries
    np.testing.assert_array_equal(
        np.asarray(ST.topk_compress(d, frac)["w"]), w)
    # byte-accounting parity: eager exact count == static k-based count
    assert nbytes == ST.topk_bytes(d, frac) == k * 8
    assert nbytes == ST.exact_kept_bytes(sp)


def test_topk_tie_stability():
    """The >= threshold rule keeps ALL entries tied at the k-th magnitude
    (may exceed k), identically in both compressors, and the exact-count
    accounting bills the survivors, not k."""
    d = {"w": jnp.asarray([2.0, -2.0, 2.0, 1.0, -0.5, 0.25], jnp.float32)}
    sp, nbytes = S.topk_sparsify(d, frac=0.34)     # k = 3; |2.0| tied x3
    w = np.asarray(sp["w"])
    np.testing.assert_array_equal(w, [2.0, -2.0, 2.0, 0.0, 0.0, 0.0])
    assert nbytes == 3 * 8
    np.testing.assert_array_equal(
        np.asarray(ST.topk_compress(d, 0.34)["w"]), w)
    # tie straddling the cut: k = 2 but all three tied entries survive
    sp2, nbytes2 = S.topk_sparsify(d, frac=0.3)
    w2 = np.asarray(sp2["w"])
    np.testing.assert_array_equal(w2, [2.0, -2.0, 2.0, 0.0, 0.0, 0.0])
    assert nbytes2 == 3 * 8 == ST.exact_kept_bytes(sp2)
    assert ST.topk_bytes(d, 0.3) == 2 * 8          # static law stays at k


@settings(max_examples=25, deadline=None)
@given(n=st.integers(min_value=1, max_value=257),
       scale_exp=st.integers(min_value=-6, max_value=4),
       seed=st.integers(min_value=0, max_value=2**31))
def test_quantize8_roundtrip_law(n, scale_exp, seed):
    """dequantize(quantize8(d)) is within scale/2 of d elementwise, where
    scale = max|d| / 127 — at every magnitude order."""
    rng = np.random.default_rng(seed)
    d = {"w": jnp.asarray(rng.normal(0, 10.0 ** scale_exp, n), jnp.float32)}
    dq, nbytes = S.quantize8(d)
    scale = max(float(jnp.max(jnp.abs(d["w"]))), 1e-12) / 127.0
    err = float(jnp.max(jnp.abs(dq["w"] - d["w"])))
    assert err <= scale * 0.5 * (1 + 1e-5) + 1e-12
    assert nbytes == n + 4                         # 1 B/entry + fp32 scale
    # trace-safe engine round trip is identical
    np.testing.assert_array_equal(np.asarray(ST.int8_compress(d)["w"]),
                                  np.asarray(dq["w"]))
    assert ST.int8_bytes(d) == nbytes


def test_quantize8_zero_delta():
    d = {"w": jnp.zeros((32,), jnp.float32)}
    dq, nbytes = S.quantize8(d)
    np.testing.assert_array_equal(np.asarray(dq["w"]), np.zeros(32))
    assert nbytes == 32 + 4


def test_topk_single_entry_leaf():
    # n = 1: every frac keeps the single entry (k clamped to [1, n])
    for frac in (0.01, 0.5, 1.0):
        d = {"w": jnp.asarray([3.5], jnp.float32)}
        sp, nbytes = S.topk_sparsify(d, frac=frac)
        assert float(sp["w"][0]) == 3.5
        assert nbytes == 8 == ST.topk_bytes(d, frac)


def test_topk_multi_leaf_tree_accounting():
    # per-leaf k: ceil is applied leaf-wise, not over the concatenation
    d = {"a": _distinct_magnitudes(0, 10), "b": _distinct_magnitudes(1, 3)}
    sp, nbytes = S.topk_sparsify(d, frac=0.5)
    assert np.count_nonzero(np.asarray(sp["a"])) == 5
    assert np.count_nonzero(np.asarray(sp["b"])) == 2
    assert nbytes == (5 + 2) * 8 == ST.topk_bytes(d, 0.5)
