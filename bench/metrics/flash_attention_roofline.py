"""flash_attention_roofline (%): the flash attention forward kernel's least
time (the larger of its FLOPs over the bf16 peak and its bytes over HBM
bandwidth) over its summed device time on chip 0.  One call is one layer
of causal GQA attention on the per-chip batch; the work counted is the
operation's own: causal QK^T and PV, reading q, the kv heads' k and v
(bf16) and writing o.  The backward is not a kernel and is not counted."""
from bench.trace import kernel_calls, roofline_share

PATTERN = r"^flash_attention\b"


def call_cost(m: dict, batch: int, seq: int):
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 2 * 2 * batch * H * hd * seq * (seq + 1) / 2
    nbytes = 2 * batch * seq * hd * (2 * H + 2 * K)
    return flops, nbytes


def read(ctx):
    flops, nbytes = call_cost(ctx.model, ctx.traffic["batch_per_chip"],
                              ctx.traffic["seq_len"])
    share, _ = roofline_share(ctx.trace, ctx.peaks,
                              kernel_calls(ctx.trace, PATTERN), flops, nbytes)
    return share
