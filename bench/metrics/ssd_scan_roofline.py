"""ssd_scan_roofline (%): the SSD intra-chunk kernel's least time over its
summed device time on chip 0.  One call is one layer's chunks of the
per-chip batch: causal C.B^T per group and (CB o L) X per head inside each
chunk plus the chunk states, reading x, B, C (bf16) and dt, cumsum(dt A)
(f32), writing the diagonal output and the chunk states (f32)."""
from bench.trace import kernel_calls, roofline_share

PATTERN = r"^ssd_intra_chunk\b"


def call_cost(m: dict, batch: int, seq: int):
    d = m["d_model"]
    di = m["ssm_expand"] * d
    p, n, G, l = m["ssm_headdim"], m["ssm_state"], m["ssm_ngroups"], m["ssm_chunk"]
    nh = di // p
    tokens = batch * seq
    flops = tokens * (2 * (l + 1) / 2 * (n * G + nh * p) + 2 * nh * p * n)
    nbytes = tokens * (2 * nh * p + 4 * nh + 4 * nh + 2 * 2 * G * n
                       + 4 * nh * p) + 4 * batch * (seq // l) * nh * p * n
    return flops, nbytes


def read(ctx):
    flops, nbytes = call_cost(ctx.model, ctx.traffic["batch_per_chip"],
                              ctx.traffic["seq_len"])
    share, _ = roofline_share(ctx.trace, ctx.peaks,
                              kernel_calls(ctx.trace, PATTERN), flops, nbytes)
    return share
