"""fused_xent_roofline (%): the fused cross-entropy forward kernel's least
time over its summed device time on chip 0.  One call is the LM head and
the log-softmax of every position of the per-chip batch: 2 N d V FLOPs
(V the vocabulary, padding not counted), reading h (bf16), the head (bf16)
and the labels, writing one f32 loss per position."""
from bench.trace import kernel_calls, roofline_share

PATTERN = r"fused_xent"


def call_cost(m: dict, batch: int, seq: int):
    n, d, V = batch * seq, m["d_model"], m["vocab_size"]
    Vp = -(-V // 256) * 256
    flops = 2 * n * d * V
    nbytes = 2 * n * d + 2 * d * Vp + 4 * n + 4 * n
    return flops, nbytes


def read(ctx):
    flops, nbytes = call_cost(ctx.model, ctx.traffic["batch_per_chip"],
                              ctx.traffic["seq_len"])
    share, _ = roofline_share(ctx.trace, ctx.peaks,
                              kernel_calls(ctx.trace, PATTERN), flops, nbytes)
    return share
