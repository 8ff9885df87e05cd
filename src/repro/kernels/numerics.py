"""Pallas-vs-reference numerics gate: ONE tolerance table, one sweep.

``TOLERANCES`` is the single source of truth for how far each Pallas kernel
may drift from its ``ref.py`` oracle, per compute dtype.  Three consumers
read it so the numbers cannot fork:

  * ``tests/test_kernel_numerics.py`` parametrizes the pytest matrix from
    ``iter_cases()`` (the tier-1 suite);
  * ``python -m repro.kernels.numerics`` runs the full dtype × shape grid
    and exits nonzero on any violation — the CI ``kernels`` job, so kernel
    drift fails the PR that causes it rather than the next bench run;
  * ``repro.train.zoo_parity``'s kernel leg reuses the per-kernel f32
    tolerances for its whole-model loss/grad comparison.

The shape grids deliberately include the training shapes the benches never
used: the ``paper-transformer-tiny`` / ``paper-ssm-tiny`` step-body shapes
and ragged (non-128-aligned) axes that exercise ``tiling.divisor_tile``.
The gate runs every kernel in interpret mode, so it checks the kernel
programs on any backend; the Mosaic lowering is compiled by
``tests/test_tpu_compile.py`` and checked on the chip by ``chip_smoke.py``
(``check_case(..., interpret=False)`` at model widths).
"""
from __future__ import annotations

import argparse

# kernel -> dtype name -> (rtol, atol).  bf16 tolerances cover input
# rounding (eps 2^-8) plus accumulation-order differences; f32 tolerances
# are a few ulps of the reduction reassociation.
TOLERANCES = {
    "fused_xent": {"float32": (1e-4, 1e-4), "bfloat16": (2e-2, 2e-2)},
    "flash_attention": {"float32": (2e-5, 2e-5), "bfloat16": (3e-2, 3e-2)},
    "ssd_scan": {"float32": (1e-3, 1e-3), "bfloat16": (3e-2, 3e-2)},
}

# fused_xent: (N, d, Vp, V)
XENT_SHAPES = [
    (128, 64, 512, 500),      # padded vocab, aligned tokens
    (256, 32, 1024, 1024),    # exact vocab
    (384, 32, 256, 256),      # N=B·S not a multiple of the 256 token tile
    (96, 48, 1024, 1000),     # ragged token axis
    (128, 64, 256, 256),      # paper-transformer-tiny head (d=64, V=256)
]

# flash_attention: (BH, S, hd, causal, window)
ATTN_SHAPES = [
    (4, 256, 64, True, None),
    (2, 256, 64, True, 64),     # sliding window
    (8, 64, 16, True, None),    # paper-transformer-tiny (B·H=8, S=64, hd=16)
    (2, 192, 32, True, 64),     # seq not 128-aligned
    (1, 128, 32, False, None),  # non-causal (encoder/cross)
    (2, 2048, 32, True, None),  # the default 1024 tiles leave a dead tile
    (1, 4096, 32, True, 1024),  # ... on both sides of a window
]

# ssd_scan: (b, S, nh, hd, G, ds, chunk)
SSD_SHAPES = [
    (2, 128, 4, 32, 1, 16, 32),
    (2, 64, 8, 16, 1, 32, 16),   # paper-ssm-tiny (d_inner=128, hd=16)
    (1, 96, 2, 16, 2, 8, 32),    # S not a multiple of the chunk
]

DTYPES = ("float32", "bfloat16")


def iter_cases():
    """Yields (kernel, dtype_name, shape_tuple) over the whole grid."""
    for dt in DTYPES:
        for shp in XENT_SHAPES:
            yield ("fused_xent", dt, shp)
        for shp in ATTN_SHAPES:
            yield ("flash_attention", dt, shp)
        for shp in SSD_SHAPES:
            yield ("ssd_scan", dt, shp)


def check_case(kernel: str, dtype_name: str, shape, *,
               interpret: bool = True) -> dict:
    """Run one (kernel, dtype, shape) cell -> report dict (no raising).
    ``interpret=False`` runs the Mosaic kernel (TPU only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    key = jax.random.PRNGKey(0)
    dtype = jnp.dtype(dtype_name)
    rtol, atol = TOLERANCES[kernel][dtype_name]

    def sub(i):
        return jax.random.fold_in(key, i)

    if kernel == "fused_xent":
        from repro.kernels.fused_xent import fused_xent, xent_ref
        N, d, Vp, V = shape
        h = jax.random.normal(key, (N, d), jnp.float32).astype(dtype)
        w = (jax.random.normal(sub(1), (d, Vp), jnp.float32) * 0.05).astype(dtype)
        y = jax.random.randint(sub(2), (N,), 0, V)
        out = fused_xent(h, w, y, vocab_size=V, interpret=interpret)
        ref = xent_ref(h, w, y, vocab_size=V)
        outs, refs = [out], [ref]
    elif kernel == "flash_attention":
        from repro.kernels.flash_attention import attention_ref, flash_attention
        BH, S, hd, causal, window = shape
        q = jax.random.normal(key, (BH, S, hd), jnp.float32).astype(dtype)
        k = jax.random.normal(sub(1), (BH, S, hd), jnp.float32).astype(dtype)
        v = jax.random.normal(sub(2), (BH, S, hd), jnp.float32).astype(dtype)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              interpret=interpret)
        ref = attention_ref(q, k, v, causal=causal, window=window)
        outs, refs = [out], [ref]
    else:
        from repro.kernels.ssd_scan import ssd_chunked_pallas, ssd_ref
        b, S, nh, hd, G, ds, chunk = shape
        x = jax.random.normal(key, (b, S, nh, hd), jnp.float32).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(sub(1), (b, S, nh)))
        A = -jnp.exp(jax.random.normal(sub(2), (nh,)) * 0.3)
        B = jax.random.normal(sub(3), (b, S, G, ds), jnp.float32).astype(dtype)
        C = jax.random.normal(sub(4), (b, S, G, ds), jnp.float32).astype(dtype)
        y1, s1 = ssd_chunked_pallas(x, dt, A, B, C, chunk=chunk,
                                    interpret=interpret)
        y2, s2 = ssd_ref(x, dt, A, B, C, chunk=chunk)
        outs, refs = [y1, s1], [y2, s2]

    max_abs = max_rel = 0.0
    ok = True
    for o, r in zip(outs, refs):
        o = np.asarray(o, np.float32)
        r = np.asarray(r, np.float32)
        err = np.abs(o - r)
        max_abs = max(max_abs, float(err.max()))
        denom = np.maximum(np.abs(r), 1e-30)
        max_rel = max(max_rel, float((err / denom).max()))
        ok &= bool(np.allclose(o, r, rtol=rtol, atol=atol))
    return {"kernel": kernel, "dtype": dtype_name, "shape": shape,
            "rtol": rtol, "atol": atol, "max_abs": max_abs,
            "max_rel": max_rel, "ok": ok}


def run_matrix(verbose: bool = False) -> list[dict]:
    reports = []
    for kernel, dtype_name, shape in iter_cases():
        rep = check_case(kernel, dtype_name, shape)
        reports.append(rep)
        if verbose or not rep["ok"]:
            print(f"  {rep['kernel']:16s} {rep['dtype']:9s} "
                  f"{str(rep['shape']):28s} max_abs={rep['max_abs']:.2e} "
                  f"max_rel={rep['max_rel']:.2e} "
                  f"tol=({rep['rtol']:g},{rep['atol']:g}) "
                  f"{'OK' if rep['ok'] else 'FAIL'}")
    return reports


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    import jax
    reports = run_matrix(verbose=args.verbose)
    bad = [r for r in reports if not r["ok"]]
    print(f"kernel-numerics backend={jax.default_backend()} "
          f"cases={len(reports)} failed={len(bad)} -> "
          f"{'OK' if not bad else 'FAIL'}")
    return 1 if bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
