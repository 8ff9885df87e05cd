"""flash_attention_bwd_roofline on hand-made traces: the backward's events
are found by name apart from the forward's, its calls are counted from
the passes and layers, and a program without the kernel reads None."""
import pytest

from bench import harness as H
from bench import trace as TR
from bench.selftest.test_trace import context, ev, hand_trace
from bench.trace import TraceData

READ = H.metric_reader("flash_attention_bwd_roofline")


def with_ops(*ops):
    t = hand_trace()
    return TraceData(ops=[t.ops[0] + list(ops)], host=t.host, t0=t.t0,
                     t1=t.t1, async_ops=t.async_ops)


def least_time(ctx):
    from bench.metrics.flash_attention_bwd_roofline import call_cost
    flops, nbytes = call_cost(ctx.model, ctx.traffic["batch_per_chip"],
                              ctx.traffic["seq_len"])
    return max(flops / ctx.peaks["bf16_flops"],
               nbytes / ctx.peaks["hbm_bytes_per_s"])


def test_backward_is_apart_from_the_forward():
    t = with_ops(ev("flash_attention_bwd.7", 9.0, 9.5))
    assert [e.op for e in TR.kernel_calls(t, r"^flash_attention\b")] == [
        "flash_attention.6"]
    assert [e.op for e in TR.kernel_calls(t, r"^flash_attention_bwd")] == [
        "flash_attention_bwd.7"]


def test_calls_come_from_passes_and_layers():
    ctx = context(with_ops(ev("flash_attention_bwd.7", 9.0, 9.5)),
                  steps=16, trips=3)
    layers = ctx.model["num_layers"]
    assert READ(ctx) == pytest.approx(
        100.0 * 19 * layers * least_time(ctx) / 0.5)


def test_a_split_kernel_counts_once():
    one = context(with_ops(ev("flash_attention_bwd.7", 9.0, 9.5)))
    two = context(with_ops(ev("flash_attention_bwd_dkv.7", 9.0, 9.3),
                           ev("flash_attention_bwd_dq.8", 9.3, 9.5)))
    assert READ(two) == pytest.approx(READ(one))


def test_no_backward_kernel_reads_none():
    assert READ(context(hand_trace())) is None
