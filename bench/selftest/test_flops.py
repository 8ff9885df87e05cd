"""The FLOP and byte counts of the per-layer readers against hand counts at
a small shape."""
import importlib.util

import pytest

from bench.harness import BENCH


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


DENSE = {"num_layers": 2, "d_model": 8, "num_heads": 2, "num_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "vocab_size": 10}
SSM = {"num_layers": 1, "d_model": 4, "vocab_size": 10, "ssm_expand": 2,
       "ssm_headdim": 4, "ssm_state": 2, "ssm_ngroups": 1, "ssm_chunk": 2}


def test_dense_forward_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 64+32+32+64+384
    # = 576 weights -> 1152 flops; causal attention at S=3: position i
    # meets i+1 keys, mean 2, QK and PV: 2 * 2 * (2 heads * 4) * 2 = 64;
    # head 8 x 10 -> 160
    f = reader("mfu").dense_forward_flops_per_token(DENSE, 3)
    assert f == pytest.approx(2 * (1152 + 64) + 160)


def test_ssm_forward_flops_by_hand():
    # d_inner 8, 2 heads of 4, state 2, one group: in_proj 4 x (16 + 4 + 2)
    # = 88, out_proj 8 x 4 = 32 -> 240 flops; SSD at chunk 2: C.B^T and
    # (CB o L)X over (l+1)/2 = 1.5 causal pairs: 2 * 1.5 * (2 + 2*4) = 30,
    # states and read-out 2 * 2 * 2*4*2 = 64; head 4 x 10 -> 80
    f = reader("mfu").ssm_forward_flops_per_token(SSM, 4)
    assert f == pytest.approx(240 + 30 + 64 + 80)


def test_pass_flops_is_three_forwards():
    m = reader("mfu")
    assert m.pass_flops(DENSE, "dense", 2, 3) == pytest.approx(
        3 * 2 * 3 * m.dense_forward_flops_per_token(DENSE, 3))


def test_flash_attention_call_by_hand():
    # batch 1, 2 heads, 1 kv head, hd 4, S=3: causal pairs 6 per head,
    # 2 matmuls x 2 flops x 4 -> 16 per pair -> 192; bytes: q, o 2*3*4 each
    # per head (x2 heads), k, v 3*4 each, all bf16
    flops, nbytes = reader("flash_attention_roofline").call_cost(DENSE, 1, 3)
    assert flops == pytest.approx(192)
    assert nbytes == 2 * (2 * 2 * 12 + 2 * 12)


def test_fused_xent_call_by_hand():
    flops, nbytes = reader("fused_xent_roofline").call_cost(DENSE, 2, 3)
    assert flops == 2 * 6 * 8 * 10
    assert nbytes == 2 * 6 * 8 + 2 * 8 * 256 + 4 * 6 + 4 * 6


def test_ssd_scan_call_by_hand():
    # one row, S=4, 2 chunks of 2: per token 1.5 causal pairs x 2 flops x
    # (state 2 + 2 heads x 4) + states 2 x (2 x 4 x 2) = 30 + 32 = 62
    flops, nbytes = reader("ssd_scan_roofline").call_cost(SSM, 1, 4)
    assert flops == pytest.approx(4 * 62)
    per_tok = 2 * 8 + 4 * 2 + 4 * 2 + 2 * 2 * 2 + 4 * 8
    assert nbytes == 4 * per_tok + 4 * 2 * 2 * 4 * 2


def test_roofline_share_names_its_bound():
    from bench.trace import Event, TraceData, roofline_share
    t = TraceData(ops=[[]], host=[], t0=0.0, t1=1.0)
    calls = [Event("k", 0.0, 0.5), Event("k", 0.5, 1.0)]
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = roofline_share(t, peaks, calls, 25.0, 1.0)
    assert (share, bound) == (pytest.approx(50.0), "compute")
    share, bound = roofline_share(t, peaks, calls, 1.0, 5.0)
    assert (share, bound) == (pytest.approx(100.0), "memory")
    assert roofline_share(t, peaks, [], 1.0, 1.0) == (None, None)
