"""Training FLOPs that one input token requires in the
granite-4.0-h-micro-share configuration under its LoRA bank, counted from
shapes (multiply and add are two FLOPs).

Forward, per token:

* every layer's SwiGLU MLP (3 x 2 d ff);
* an attention layer: the q and output projections (2 x 2 d H hd), k and v
  (2 x 2 d Kv hd), and causal attention over the (S + 1) / 2 positions a
  token sees on average (scores and the weighted sum, 2 x 2 x (S + 1) / 2
  x H hd);
* a Mamba-2 layer: the in-projection (2 d (2 d_in + 2 N + H)) and the
  out-projection (2 d_in d), and the chunked SSD's matmuls at chunk Q: the
  chunk's C B^T over the (Q + 1) / 2 positions a token sees in its chunk
  (2 N (Q + 1) / 2, shared by the heads), their weighted sum of x
  (2 H P (Q + 1) / 2), the carried state read out (2 H P N) and the state
  hand-off (2 H P N);
* each adapted projection's two factors (2 r (d_in + d_out));

then the tied head (2 d V) at the positions the loss reads (every position
here).

Backward: the base weights are frozen, so a linear layer needs only its
activation gradient (one forward's worth), and so does the head; attention
and the SSD need two forwards' worth (the gradients of both operands of
each of their matmuls); the LoRA factors need their weight and activation
gradients (two forwards' worth).

Not counted, though the program runs them: the recompute under remat, the
masked half of the attention scores and of the SSD's chunk matmuls, the
depthwise convolution, norms and gates (not matmul work).
"""

_ADAPTED = {"attn": ("q", "k", "v", "o"), "mlp": ("gate", "up", "down"),
            "mamba": ("in", "out")}


def train_flops_per_token(config: dict, traffic: dict,
                          loss_fraction: float) -> float:
    d, ff, vocab = config["d_model"], config["d_ff"], config["vocab_size"]
    h, kv, hd = config["n_heads"], config["n_kv_heads"], config["head_dim"]
    n_state, q = config["ssm_state"], config["ssm_chunk"]
    d_in = config["ssm_expand"] * d
    p = config["mamba_d_head"]
    heads = d_in // p
    seq = traffic["seq"]
    pattern = config["layer_pattern"]
    periods = config["n_layers"] // len(pattern)
    n_mamba = periods * pattern.count("mamba")
    n_attn = periods * pattern.count("attention")
    dims = {"q": (d, h * hd), "k": (d, kv * hd), "v": (d, kv * hd),
            "o": (h * hd, d), "gate": (d, ff), "up": (d, ff), "down": (ff, d),
            "in": (d, 2 * d_in + 2 * n_state + heads), "out": (d_in, d)}
    rank, targets = config["peft"]["rank"], config["peft"]["targets"]

    def lora(groups):
        return sum(2 * rank * sum(dims[x]) for g in groups if g in targets
                   for x in _ADAPTED[g])

    mlp = 3 * 2 * d * ff
    attn_linear = 2 * d * h * hd * 2 + 2 * d * kv * hd * 2
    attention = 2 * 2 * (seq + 1) / 2 * h * hd
    mamba_linear = 2 * d * dims["in"][1] + 2 * d_in * d
    chunk = min(q, seq) if seq > q else 0
    if chunk:
        ssd = (2 * n_state * (chunk + 1) / 2 + 2 * heads * p * (chunk + 1) / 2
               + 2 * 2 * heads * p * n_state)
    else:       # the token scan: the state update and its read-out
        ssd = 2 * 2 * heads * p * n_state
    per_attn = 2 * (attn_linear + mlp) + 3 * attention \
        + 3 * lora(("attn", "mlp"))
    per_mamba = 2 * (mamba_linear + mlp) + 3 * ssd \
        + 3 * lora(("mamba", "mlp"))
    head = 2 * d * vocab
    return float(n_attn * per_attn + n_mamba * per_mamba
                 + 2 * head * loss_fraction)
