"""The benchmark's FLOP counts against the program's compiled-HLO count
(``repro.telemetry.client_step_cost``) at reduced sizes on the CPU: the two
differ by exactly the terms each ``bench/flops/<config>.py`` names, and
the table of chip peaks refuses a device it does not list."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run as R  # noqa: E402

B, S, RANK = 4, 64, 4


def _shapes(cfg):
    return {k: getattr(cfg, k) for k in (
        "d_model", "d_ff", "n_layers", "vocab_size", "n_heads",
        "n_kv_heads", "head_dim")}


def _telemetry(cfg, space=None):
    from repro import optim
    from repro.core.strategy import FedAvg
    from repro.telemetry import client_step_cost
    from repro.telemetry.step import train_batch_struct
    cost = client_step_cost(cfg, optim.adam(1e-3), FedAvg(),
                            train_batch_struct(cfg, B, S), space=space)
    return cost.flops / (B * S)


@pytest.mark.parametrize("remat", [False, True])
def test_distilbert_count_differs_by_named_terms(remat):
    from repro.configs import get_config
    cfg = get_config("distilbert-mlm").reduced().replace(remat=remat)
    flops = R.load_module(os.path.join(BENCH, "flops", "distilbert-mlm.py"),
                          "flops_distilbert_mlm")
    r = 0.15
    ours = flops.train_flops_per_token(_shapes(cfg), {"seq": S}, r)
    d, ff, v, n = cfg.d_model, cfg.d_ff, cfg.vocab_size, cfg.n_layers
    unread_head = 3 * (1 - r) * (2 * d * d + 2 * d * v)
    recompute = n * (8 * d * d + 4 * d * ff + 4 * S * d) if remat else 0
    assert _telemetry(cfg) == pytest.approx(ours + unread_head + recompute,
                                            rel=1e-9)


@pytest.mark.parametrize("remat", [False, True])
def test_qwen2_lora_count_differs_by_named_terms(remat):
    from repro.configs import get_config
    from repro.peft import lora
    cfg = get_config("qwen2-7b").reduced().replace(remat=remat)
    flops = R.load_module(os.path.join(BENCH, "flops", "qwen2-7b-share.py"),
                          "flops_qwen2_7b_share")
    shapes = _shapes(cfg)
    shapes["peft"] = {"rank": RANK, "targets": ["attn", "mlp"]}
    ours = flops.train_flops_per_token(shapes, {"seq": S}, 1.0)
    d, ff, n = cfg.d_model, cfg.d_ff, cfg.n_layers
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dims = [(d, h * hd), (d, kv * hd), (d, kv * hd), (h * hd, d),
            (d, ff), (d, ff), (ff, d)]
    linear = sum(2 * a * b for a, b in dims)
    lora_per_token = sum(2 * RANK * (a + b) for a, b in dims)
    full_attention, causal = 2 * 2 * S * h * hd, 2 * 2 * (S + 1) / 2 * h * hd
    # per step: the merge (A @ B) and the factor gradients taken from the
    # merged weight's full gradient (dA = dW B^T, dB = A^T dW)
    per_step = sum(2 * a * RANK * b * 3 for a, b in dims)
    named = n * (linear                      # full dW through the merge
                 + 3 * (full_attention - causal)
                 - 3 * lora_per_token        # the program merges instead
                 + per_step / (B * S))
    got = _telemetry(cfg, lora(RANK, targets=("attn", "mlp")))
    if not remat:
        assert got == pytest.approx(ours + named, rel=1e-9)
    else:
        # remat recomputes part of each layer's forward, at most all of it
        assert ours + named < got <= ours + named + n * (linear
                                                          + full_attention)


def test_peaks_table_refuses_unknown_device():
    assert R.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no peaks for device_kind"):
        R.peaks_for("TPU v9 imaginary")
