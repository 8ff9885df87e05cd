"""Block-size selection shared by the Pallas kernels.

The benches only ever drove the kernels at 128-aligned shapes; training
bodies produce whatever ``B·S`` / seq / vocab the config family dictates.
``divisor_tile`` keeps the kernels' "tiles divide the axis" invariant by
shrinking the requested tile to the largest divisor of the axis length,
preferring MXU-aligned (multiple-of-``align``) candidates — on TPU the
config families are sized so an aligned divisor exists; the unaligned
fallback keeps ragged CPU/CI shapes correct (interpret mode has no MXU to
starve).
"""
from __future__ import annotations


def divisor_tile(n: int, want: int, align: int = 128) -> int:
    """Largest tile <= min(want, n) dividing n, preferring multiples of
    ``align``."""
    assert n >= 1 and want >= 1
    want = min(want, n)
    for b in range(want - want % align, 0, -align):
        if n % b == 0:
            return b
    b = want
    while n % b:
        b -= 1
    return b


def flash_tiles(Sq: int, Sk: int, hd: int) -> tuple[int, int]:
    """Default (bq, bk) of the flash attention kernel for a shape.

    Square tiles of 1024 for head dims up to 128, halved for each doubling
    of the head dim past that: the largest a f32 tile body (the (bq, bk)
    scores and probabilities, the (bq, hd) accumulator) fits in the TPU's
    default scoped VMEM.  On one v5e at B·H=32, S=4096, hd=128, bf16, 1024²
    tiles ran a causal call in 1.45 ms against 2.49 ms at 512² and 5.28 ms
    at 256² (per-call sweep, PERF.md §5).
    """
    want = max(128, 1024 * 128 // max(hd, 128))
    return divisor_tile(Sq, want), divisor_tile(Sk, want)


def flash_bwd_tiles(Sq: int, Sk: int, hd: int) -> tuple[int, int]:
    """Default (bq, bk) of the flash attention backward kernel for a shape.

    Square tiles of 512 for head dims up to 128, halved for each doubling
    of the head dim past that.  The tile body holds four f32 (bk, bq)
    arrays (scores, probabilities, dP, dS), so 1024² tiles (16 MiB) need a
    raised scoped-VMEM limit (``kernel.py::_bwd_vmem_limit``).  On one v5e
    at B·H=32, S=4096, hd=128, bf16, causal, a call took 2.766 ms at 512²
    against 2.756 at 1024², 2.780 at 512×1024 and 4.289 at 256² (per-call
    sweep, PERF.md §5): 1024² does not win enough to raise the limit.
    """
    want = max(128, 512 * 128 // max(hd, 128))
    return divisor_tile(Sq, want), divisor_tile(Sk, want)
