"""mfu (%): the forward and backward FLOPs that every pass of the traced
window requires (each ISGD step's main pass and each subproblem trip, on
the global batch, counted by ``bench/trace.py::passes``), over window x
chips x the bf16 peak.  Per token a pass needs 3 x the forward: 2 x the
matmul weights it meets, plus causal attention (dense family) or the SSD
scan (ssm family).  Remat's recompute, the embedding lookup and
elementwise work are not counted."""


def dense_forward_flops_per_token(m: dict, seq: int) -> float:
    d, H, K, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * ff
    # causal: position i meets i + 1 keys; QK^T and PV, 2 flops per MAC
    attn = 2 * 2 * H * hd * (seq + 1) / 2
    return m["num_layers"] * (2 * layer + attn) + 2 * d * m["vocab_size"]


def ssm_forward_flops_per_token(m: dict, seq: int) -> float:
    d = m["d_model"]
    di = m["ssm_expand"] * d
    p, n, G, l = m["ssm_headdim"], m["ssm_state"], m["ssm_ngroups"], m["ssm_chunk"]
    nh = di // p
    layer = d * (2 * di + 2 * G * n + nh) + di * d
    # chunked SSD: causal C.B^T per group and (CB o L) X per head inside a
    # chunk, chunk states and their read-out per head
    ssd = 2 * (l + 1) / 2 * (n * G + nh * p) + 2 * 2 * nh * p * n
    return m["num_layers"] * (2 * layer + ssd) + 2 * d * m["vocab_size"]


def pass_flops(model: dict, family: str, batch: int, seq: int) -> float:
    per_tok = (dense_forward_flops_per_token(model, seq) if family == "dense"
               else ssm_forward_flops_per_token(model, seq))
    return 3 * per_tok * batch * seq


def read(ctx):
    cell = ctx.cell
    flops = pass_flops(ctx.model, cell.config["family"], cell.global_batch,
                       ctx.traffic["seq_len"]) * ctx.passes
    peak = ctx.trace.window_s * cell.chips * ctx.peaks["bf16_flops"]
    return 100.0 * flops / peak
