"""Compile a cell's chunk program for a described TPU v5e, with no chip,
and print its ``memory_analysis()``: the sizing rehearsal.

    JAX_PLATFORMS=cpu python3 bench/aot.py <cell> [--reference]

One-chip cells compile for one device of a described ``v5e:2x2`` host,
four-chip cells for all four on a ``data=4`` mesh.  ``--reference`` also
compiles one step of the plain f32 reference (and of its fp8 control),
as ``bench/compare.py`` runs it on the first device.  The compilation cache
is off, so nothing is written.
"""
from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def gib(n):
    return f"{n / 2**30:.2f} GiB"


def report(what, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{what}: arguments {gib(m.argument_size_in_bytes)}, outputs "
          f"{gib(m.output_size_in_bytes)}, aliased "
          f"{gib(m.alias_size_in_bytes)}, temporaries "
          f"{gib(m.temp_size_in_bytes)}; total {gib(total)}", flush=True)
    return total


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--reference", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, SingleDeviceSharding
    from jax.sharding import PartitionSpec as P

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.launch import shardings as SH
    from repro.models import transformer as T

    from bench import compare, harness as H

    cell = H.find_cell(args.cell)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices[:cell.chips])
    cfg = H.model_config(cell)

    def loss_fn(params, batch):   # build_model refuses pallas off a TPU
        return T.lm_loss_fn(params, cfg, batch, remat=True,
                            remat_policy="full", kernels="pallas")

    mesh, init_fn, jchunk = H.build_engine(cell, devices, loss_fn)
    rep = NamedSharding(mesh, P())
    shapes = H.param_shapes(cell)
    n_params = sum(x.size for x in jax.tree.leaves(shapes))
    print(f"{cell.name}: {n_params / 1e9:.3f}B params, {cell.chips} chip(s), "
          f"global batch {cell.global_batch} x {cell.traffic['seq_len']}")
    p_sds = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                        sharding=rep), shapes)
    st = jax.eval_shape(init_fn, shapes)
    s_sh = SH.state_shardings(mesh, st, jax.tree.map(lambda _: rep, shapes))
    s_sds = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), st, s_sh)
    rows = cell.traffic["n_batches"] * cell.global_batch
    ring = {"tokens": jax.ShapeDtypeStruct(
        (rows, cell.traffic["seq_len"]), jnp.int32,
        sharding=NamedSharding(mesh, P("data")))}
    with mesh:
        compiled = jchunk.lower(s_sds, p_sds, ring, 0).compile()
    report("chunk program, per device", compiled)
    hlo = compiled.as_text()
    print(f"Mosaic kernels in the program: {hlo.count('tpu_custom_call')}; "
          f"all-gathers: {hlo.count('all-gather-start') + hlo.count(' all-gather(')}")
    if not args.reference:
        return
    one = SingleDeviceSharding(devices[0])
    p = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                    sharding=one), shapes)
    v = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                                    sharding=one), shapes)
    tok = jax.ShapeDtypeStruct((cell.global_batch, cell.traffic["seq_len"]),
                               jnp.int32, sharding=one)
    for quant in ("none", "fp8"):
        step = compare.reference_step(cell, quant)
        c = jax.jit(step, donate_argnums=(0, 1)).lower(p, v, tok).compile()
        report(f"reference step ({quant})", c)

if __name__ == "__main__":
    main()
