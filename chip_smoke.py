"""Smoke test: ISGD training of the base-tier paper transformer on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # data=4 hybrid engine vs one chip

One chip (the default) runs two phases in this one process:

  1. train — ``repro.launch.train.main`` with the fused chunked engine over
     the device ring and the ISGD controller, at the zoo's base-tier widths
     (16 layers, d_model 1024, 16/8 heads of 64, d_ff 4096, vocab 32768),
     bf16, with the Pallas kernels compiled by Mosaic (``--kernels pallas``);
  2. kernels — the forward loss of the ``pallas`` and ``reference`` builds
     on the same params and batch, plus the flash-attention and fused-xent
     kernels against their oracles at model widths
     (``repro.kernels.numerics.check_case``), all within the bf16 entries of
     ``repro.kernels.numerics.TOLERANCES``.

``--chips 4`` runs only the four-chip path and what it is compared with:
the hybrid engine on a ``(data=4, model=1)`` mesh and the same global batch
on one chip, both through ``main``; the accelerate decisions must match and
the losses agree within the fused-xent bf16 tolerance.

Any failure — no TPU, a kernel mode other than ``pallas``, a non-finite
loss, a tolerance miss — exits 1 without printing a result.  On success the
last line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# base-tier run shared by every phase; --batch/--n-seqs/--devices per leg
TRAIN_ARGV = ["--model", "transformer", "--tier", "base", "--kernels",
              "pallas", "--precision", "bf16", "--chunk-steps", "8",
              "--steps", "16", "--seq", "1024"]
# kernels on the paper-transformer forward path
PATH_KERNELS = ("flash_attention", "fused_xent")


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def gib(n) -> str:
    return f"{n / 2**30:.2f}GiB"


def train(batch: int, n_seqs: int, devices: int):
    from repro.launch.train import main as train_main
    res = train_main(TRAIN_ARGV + ["--batch", str(batch), "--n-seqs",
                                   str(n_seqs), "--devices", str(devices)])
    check(res.kernels == "pallas", f"kernel mode {res.kernels!r}, not pallas")
    check(len(res.chunks) == 2, f"{len(res.chunks)} chunks, expected 2")
    return res


def step_series(res, key):
    import numpy as np
    return np.concatenate([np.asarray(c["metrics"][key]) for c in res.chunks])


def print_memory(res) -> None:
    for i, m in enumerate(res.memory):
        check(m is not None, f"device {i} reports no memory_stats()")
        print(f"memory: device {i} bytes_in_use={m['bytes_in_use']} "
              f"({gib(m['bytes_in_use'])}) "
              f"peak_bytes_in_use={m['peak_bytes_in_use']} "
              f"({gib(m['peak_bytes_in_use'])})")


def phase_train() -> None:
    import numpy as np
    print("== phase train: base-tier ISGD, chunked engine, pallas kernels")
    res = train(batch=8, n_seqs=32, devices=1)
    first, steady = res.chunks[0]["wall_s"], res.chunks[1]["wall_s"]
    k = res.chunks[1]["step"] - res.chunks[0]["step"]
    loss, psi = step_series(res, "loss"), step_series(res, "psi_bar")
    print(f"resolved kernels: {res.kernels}")
    print(f"params: {res.n_params} ({res.n_params / 1e6:.1f}M)")
    print(f"compile_s: {first - steady:.1f} (first dispatch {first:.1f}s "
          f"minus one warm dispatch {steady:.2f}s)")
    print(f"ms_per_step: {steady / k * 1e3:.1f} (warm chunk of {k} steps)")
    print(f"loss: {loss.tolist()}")
    print(f"psi_bar: {float(psi[-1]):.4f}")
    print(f"accel_count: {int(res.state.accel_count)} "
          f"sub_iters: {int(res.state.sub_iters)}")
    print_memory(res)
    check(loss.shape == (16,), f"loss series shape {loss.shape}")
    check(bool(np.isfinite(loss).all()), "non-finite loss")
    check(bool(np.isfinite(psi[-1])), "non-finite psi_bar")


def phase_kernels() -> None:
    import jax

    from repro.configs import zoo_config
    from repro.data import FCPRSampler, make_lm_tokens
    from repro.kernels.numerics import TOLERANCES, check_case
    from repro.models import build_model

    print("== phase kernels: pallas vs reference on the chip")
    cfg = zoo_config("transformer", "base")
    params = build_model(cfg).init(jax.random.PRNGKey(0), max_seq=1024)
    sampler = FCPRSampler(make_lm_tokens(0, 32, 1024, cfg.vocab_size),
                          batch_size=8, seed=1)
    batch = {k: jax.device_put(v) for k, v in sampler(0).items()}
    losses = {}
    for mode in ("pallas", "reference"):
        loss_fn = build_model(cfg, kernels=mode).loss_fn
        compiled = jax.jit(loss_fn).lower(params, batch).compile()
        if mode == "pallas":
            check("tpu_custom_call" in compiled.as_text(),
                  "pallas loss program holds no Mosaic kernel")
        losses[mode] = float(compiled(params, batch)[1])
    rtol = max(TOLERANCES[k]["bfloat16"][0] for k in PATH_KERNELS)
    atol = max(TOLERANCES[k]["bfloat16"][1] for k in PATH_KERNELS)
    diff = abs(losses["pallas"] - losses["reference"])
    bound = atol + rtol * abs(losses["reference"])
    print(f"forward loss: pallas={losses['pallas']:.6f} "
          f"reference={losses['reference']:.6f} |diff|={diff:.3e} "
          f"tol={bound:.3e} (atol + rtol*|reference|, rtol={rtol:g} "
          f"atol={atol:g}: the largest bf16 entries of TOLERANCES over "
          f"{', '.join(PATH_KERNELS)})")
    check(math.isfinite(losses["pallas"]), "non-finite pallas loss")
    check(diff <= bound, f"pallas loss off reference by {diff:.3e}")
    cases = [("flash_attention", (128, 1024, 64, True, None)),
             ("flash_attention_bwd", (128, 1024, 64, True, None)),
             ("fused_xent", (8192, 1024, 32768, 32768))]
    for kernel, shape in cases:
        rep = check_case(kernel, "bfloat16", shape, interpret=False)
        print(f"kernel {kernel} bf16 {shape}: max_abs={rep['max_abs']:.3e} "
              f"max_rel={rep['max_rel']:.3e} tol=(rtol={rep['rtol']:g}, "
              f"atol={rep['atol']:g}) {'OK' if rep['ok'] else 'FAIL'}")
        check(rep["ok"], f"{kernel} off its oracle")


def phase_four_chips() -> None:
    import numpy as np

    from repro.kernels.numerics import TOLERANCES
    print("== phase four chips: hybrid engine (data=4, model=1) vs one chip, "
          "global batch 16x1024")
    legs, accel = {}, {}
    for n in (4, 1):
        print(f"-- leg devices={n}")
        res = train(batch=16, n_seqs=64, devices=n)
        for c in res.chunks:
            m = c["metrics"]
            acc = "".join("1" if a else "0" for a in np.asarray(m["accelerated"]))
            print(f"leg {n} chunk ending step {c['step']}: "
                  f"loss={float(m['loss'][-1]):.6f} "
                  f"psi_bar={float(m['psi_bar'][-1]):.6f} accel={acc}")
        print_memory(res)
        check(len(res.memory) == n, f"{len(res.memory)} mesh devices, not {n}")
        accel[n] = int(res.state.accel_count)
        res.state = None                          # free its device copies
        legs[n] = res
    rtol, atol = TOLERANCES["fused_xent"]["bfloat16"]
    l4, l1 = step_series(legs[4], "loss"), step_series(legs[1], "loss")
    a4, a1 = step_series(legs[4], "accelerated"), step_series(legs[1], "accelerated")
    dev = float(np.max(np.abs(l4 - l1)))
    print(f"compare: max |loss4 - loss1| = {dev:.3e} over {l4.size} steps "
          f"(tol atol + rtol*|loss1|, rtol={rtol:g} atol={atol:g}: "
          f"TOLERANCES['fused_xent']['bfloat16']); accelerate decisions "
          f"{'match' if (a4 == a1).all() else 'DIFFER'} "
          f"(accel_count {accel[4]} vs {accel[1]})")
    check(bool(np.isfinite(l4).all() and np.isfinite(l1).all()),
          "non-finite loss")
    check(bool((a4 == a1).all()), "accelerate decisions differ")
    check(bool(np.allclose(l4, l1, rtol=rtol, atol=atol)),
          "losses disagree between 4 chips and 1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1 = train + kernel phases on one chip; 4 = the "
                         "four-chip data-parallel leg against one chip only")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: FAIL: {ROOT} holds no src/repro; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch import env as ENV
    cache_dir = ENV.setup_compilation_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    print(f"devices: {devices}")
    print(f"platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    try:
        check(dev.platform == "tpu",
              f"JAX found no TPU: its platform is {dev.platform!r}")
        check(len(devices) >= args.chips,
              f"--chips {args.chips} needs {args.chips} devices, JAX has "
              f"{len(devices)}")
        phases = ([phase_four_chips] if args.chips == 4
                  else [phase_train, phase_kernels])
        failures = []
        for phase in phases:
            try:
                phase()
            except SmokeFailure as e:
                print(f"chip_smoke: FAIL in {phase.__name__}: {e}",
                      file=sys.stderr)
                failures.append(phase.__name__)
            except Exception:
                # report and go on: one chip run shows every phase's fault
                traceback.print_exc()
                failures.append(phase.__name__)
        check(not failures, f"failed phases: {failures}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    entries = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    print(f"compile cache: {cache_dir} entries={entries}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
