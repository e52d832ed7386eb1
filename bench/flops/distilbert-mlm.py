"""Training FLOPs that one input token requires in the distilbert-mlm
configuration, counted from shapes (multiply and add are two FLOPs).

Forward, per layer and token: q, k, v and the output projection
(4 x 2 d^2), the MLP (2 x 2 d ff) and bidirectional attention over all S
positions (scores and the weighted sum, 2 x 2 S d).  The masked-LM head
(the d x d transform and the tied d x V projection) runs only at the
positions the loss reads, a share ``loss_fraction`` of the input.  The
backward pass takes twice the forward (activation and weight gradients),
so training takes three times the forward.

Not counted, though the program runs them: the recompute of each layer's
forward under remat, and the head at the positions the loss does not read.
Embedding lookups, norms, softmax and the optimizer are not matmul work
and are left out.
"""


def train_flops_per_token(config: dict, traffic: dict,
                          loss_fraction: float) -> float:
    d, ff = config["d_model"], config["d_ff"]
    layers, vocab, seq = config["n_layers"], config["vocab_size"], traffic["seq"]
    per_layer = 8 * d * d + 4 * d * ff + 4 * seq * d
    head = loss_fraction * (2 * d * d + 2 * d * vocab)
    return 3.0 * (layers * per_layer + head)
