"""flash_attention_bwd_roofline (%): the flash attention backward kernel's
least time over its summed device time on chip 0.  One call is one layer's
attention backward on the per-chip batch: the five causal matmuls of the
recompute (q·kᵀ, dO·vᵀ, Pᵀ·dO, dS·K, dSᵀ·Q), reading q, o, dO, k and v
and writing dq, dk and dv (bf16, k and v at the kv heads), and reading the
f32 log-sum-exp.  The calls are counted from the window's passes times the
layers, not from the events, so a kernel split in two counts once; None
where no event matches, as in a program without the kernel."""
from bench.trace import kernel_calls

PATTERN = r"^flash_attention_bwd"


def call_cost(m: dict, batch: int, seq: int):
    H, K, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 5 * 2 * batch * H * hd * seq * (seq + 1) / 2
    nbytes = 2 * batch * seq * hd * (4 * H + 4 * K) + 4 * batch * H * seq
    return flops, nbytes


def read(ctx):
    calls = kernel_calls(ctx.trace, PATTERN)
    if not calls:
        return None
    flops, nbytes = call_cost(ctx.model, ctx.traffic["batch_per_chip"],
                              ctx.traffic["seq_len"])
    least = max(flops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    n = ctx.passes * ctx.model["num_layers"]
    return 100.0 * n * least / sum(e.dur for e in calls)
