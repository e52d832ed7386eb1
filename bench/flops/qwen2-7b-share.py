"""Training FLOPs that one input token requires in the qwen2-7b-share
configuration under its LoRA bank, counted from shapes (multiply and add
are two FLOPs).

Forward, per layer and token: the q and output projections (2 x 2 d
H hd), k and v (2 x 2 d Kv hd), the SwiGLU MLP (3 x 2 d ff), causal
attention over the (S + 1) / 2 positions a token sees on average (scores
and the weighted sum, 2 x 2 x (S + 1) / 2 x H hd) and each adapted
projection's two factors (2 r (d_in + d_out)); then the head (2 d V) at
the positions the loss reads (every position here).

Backward: the base weights are frozen, so a linear layer needs only its
activation gradient (one forward's worth); attention needs two forwards'
worth (the gradients of the scores and of q, k and v); the LoRA factors
need their weight and activation gradients (two forwards' worth).

Not counted, though the program runs them: the full weight gradients of
every adapted projection that come from differentiating through the
merged weights, the merge itself, the recompute under remat, and the
masked half of the attention scores.
"""

_ADAPTED = {"attn": ("q", "k", "v", "o"), "mlp": ("gate", "up", "down")}


def train_flops_per_token(config: dict, traffic: dict,
                          loss_fraction: float) -> float:
    d, ff, vocab = config["d_model"], config["d_ff"], config["vocab_size"]
    h, kv, hd = config["n_heads"], config["n_kv_heads"], config["head_dim"]
    layers, seq = config["n_layers"], traffic["seq"]
    dims = {"q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd),
            "o": (h * hd, d), "gate": (d, ff), "up": (d, ff), "down": (ff, d)}
    linear = 2 * d * h * hd * 2 + 2 * d * kv * hd * 2 + 3 * 2 * d * ff
    attention = 2 * 2 * (seq + 1) / 2 * h * hd
    peft = config["peft"]
    lora = sum(2 * peft["rank"] * sum(dims[p])
               for group in peft["targets"] for p in _ADAPTED[group])
    head = 2 * d * vocab
    return float(layers * (2 * linear + 3 * attention + 3 * lora)
                 + 2 * head * loss_fraction)
