"""reduction.collective_share (%): device time on chip 0 of the collective
ops (all-gather, all-reduce, reduce-scatter, collective-permute, all-to-all;
an async one from its start to its done) over the traced window."""
import re

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)")


def read(ctx):
    t = ctx.trace
    evs = [e for e in t.ops[0] + t.async_ops[0] if COLLECTIVE.match(e.op)]
    return 100.0 * t.time(evs) / t.window_s
