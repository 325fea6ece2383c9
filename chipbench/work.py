"""Operations and bytes a kernel's algorithm needs, from the cell's shapes.

A roofline share divides the least time the chip could take (operations
over peak FLOP/s, or bytes over peak bytes/s, whichever is larger) by the
time the kernel took in the trace. Recomputation an implementation chooses
to do is not counted: a share says how far the kernel is from what the
algorithm needs, not from what this code happens to execute.

Every function takes the cell's `shapes` (the dict the kind's driver puts
into its result) and returns (flops, bytes) for ONE step on ONE chip.
"""


def flash_attention_train(shapes):
    """Self-attention forward and backward over every layer of one training
    step, per chip. Forward: QK^T and PV, 2 matmuls. Backward: dV, dP, dQ,
    dK and one recomputation of QK^T (the flash algorithm stores no L x L
    matrix, so one recomputation belongs to it), 5 matmuls. Each matmul is
    2*B*H*L*L*D operations. The dq/dkv split in this repo recomputes QK^T
    and dP twice; the second time is the implementation's, not counted.
    Bytes: q, k, v, o read or written once forward; q, k, v, o, do read and
    dq, dk, dv written backward (bf16 = `itemsize`)."""
    b, h, l, d = (shapes["batch_per_chip"], shapes["heads"],
                  shapes["seq_len"], shapes["head_dim"])
    one = 2 * b * h * l * l * d
    flops = shapes["layers"] * 7 * one
    nbytes = shapes["layers"] * (4 + 8) * b * h * l * d * shapes["itemsize"]
    return flops, nbytes


def encoder_train_step(shapes):
    """The matrix products of one training step of a BERT-style encoder,
    per chip, for the whole step's share of the chip's peak (`step_mfu`).
    Forward, per token and layer: the four attention projections (4 U^2
    multiply-adds) and the two feed-forward products (2 U I); per layer the
    two attention products, 2 B H L^2 D multiply-adds each; the masked-LM
    head on the `masked` positions of a sequence alone (a transform U^2 and
    the decoder U V). Backward is twice the forward. Embedding lookups,
    norms, softmax and the optimizer are not products and not counted, and
    neither is anything an implementation recomputes. Bytes: none counted;
    a training step of this size is compute-bound."""
    b, l, u = shapes["batch_per_chip"], shapes["seq_len"], shapes["units"]
    layer = b * l * (4 * u * u + 2 * u * shapes["hidden"]) \
        + 2 * b * shapes["heads"] * l * l * shapes["head_dim"]
    head = b * shapes["masked"] * (u * u + u * shapes["vocab"])
    return 3 * 2 * (shapes["layers"] * layer + head), 0


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound) the chip needs at its published peaks."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
