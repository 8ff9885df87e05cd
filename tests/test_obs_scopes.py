"""Name scopes and host spans that a profiler trace of training carries.

The benchmark reads the layer of each device op from the op's name-scope
path (``op_name`` in the compiled HLO, ``tf_op`` in the device trace), with
the scope strings written out in its own files.  These tests pin, on the
chunked hybrid engine's compiled chunk program, the strings and the forms
in which they reach ``op_name`` under ``jvp``, ``transpose`` and remat, and
the launcher loop's host spans in a profiler trace.
"""
import dataclasses
import glob
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import ISGDConfig
from repro.core.schedule import constant_lr
from repro.data import DeviceRing
from repro.distributed import make_chunked_hybrid_step
from repro.launch.mesh import make_training_mesh
from repro.launch.train import _drive_chunks
from repro.models import transformer as T
from repro.optim import RULES

N_BATCHES, BATCH, SEQ, K = 4, 2, 32, 2
TINY = {
    "dense": ("internlm2_1_8b", {"num_layers": 2, "d_model": 64,
                                 "num_heads": 4, "num_kv_heads": 2,
                                 "head_dim": 16, "d_ff": 128,
                                 "vocab_size": 500}),
    "ssm": ("mamba2_2_7b", {"num_layers": 2, "d_model": 64,
                            "vocab_size": 500, "ssm_state": 16,
                            "ssm_headdim": 16, "ssm_chunk": 16}),
}
HOST_SPANS = ["train/dispatch", "train/fetch", "train/obs", "train/log",
              "train/checkpoint"]


def _engine(family):
    arch, over = TINY[family]
    cfg = dataclasses.replace(get_config(arch), **over)

    def loss_fn(params, batch):
        return T.lm_loss_fn(params, cfg, batch, remat=True,
                            remat_policy="full", kernels="reference")

    mesh = make_training_mesh(devices=jax.devices()[:1])
    init_fn, jchunk = make_chunked_hybrid_step(
        loss_fn, RULES["momentum"](mu=0.9), ISGDConfig(n_batches=N_BATCHES),
        mesh, chunk_steps=K, inconsistent=True, lr_fn=constant_lr(0.05))
    params = T.init_params(jax.random.PRNGKey(0), cfg, max_seq=SEQ)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (N_BATCHES * BATCH, SEQ), 0, cfg.vocab_size)
    ring = DeviceRing({"tokens": tokens}, BATCH, mesh=mesh, axis=None,
                      relayout=True)
    with mesh:
        compiled = jchunk.lower(init_fn(params), params, ring.arrays,
                                0).compile()
    return types.SimpleNamespace(
        family=family, compiled=compiled, init_fn=init_fn, params=params,
        ring=ring, mesh=mesh,
        names=set(re.findall(r'op_name="([^"]*)"', compiled.as_text())))


@pytest.fixture(scope="module")
def dense():
    return _engine("dense")


@pytest.fixture(scope="module")
def ssm():
    return _engine("ssm")


@pytest.fixture(params=sorted(TINY))
def program(request):
    return request.getfixturevalue(request.param)


def _in_scope(path, scope):
    """``scope`` is a component of ``path``, plain or wrapped by a
    transform (``jvp(obs/lm_head)``)."""
    return re.search(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)",
                     path) is not None


def _with(names, scope):
    return [n for n in names if _in_scope(n, scope)]


def test_layer_scopes_reach_op_name_in_forward_and_backward(program):
    family, names = program.family, program.names
    mixer = "obs/ssm" if family == "ssm" else "obs/attn"
    layers = [mixer] + (["obs/mlp"] if family == "dense" else [])
    for scope in layers + ["obs/lm_head", "obs/update", "obs/psi_push"]:
        fwd = [n for n in _with(names, scope) if "transpose(" not in n]
        assert fwd, f"{scope}: no forward op"
    for scope in layers + ["obs/lm_head"]:
        assert any("transpose(" in n for n in _with(names, scope)), \
            f"{scope}: no backward op"
    for scope in layers:
        assert any("rematted_computation/" in n for n in _with(names, scope)), \
            f"{scope}: no recompute op"
    assert _with(names, "obs/accelerate")
    if family == "dense":
        assert not _with(names, "obs/ssm")
    else:
        assert not _with(names, "obs/attn") and not _with(names, "obs/mlp")


def test_ssd_scope_nests_inside_ssm_scope(program):
    """The SSD scan's own scope, inside the mixer's, in the forward, the
    backward and remat's recompute; a dense program has none."""
    ssd = _with(program.names, "obs/ssd")
    if program.family == "dense":
        assert not ssd
        return
    assert all(re.search(r"(^|[/(])obs/ssm/(.*/)?obs/ssd([/)]|$)", n)
               for n in ssd), [n for n in ssd if "obs/ssm/" not in n]
    assert [n for n in ssd if "transpose(" not in n], "no forward op"
    assert any("transpose(" in n for n in ssd), "no backward op"
    assert any("rematted_computation/" in n for n in ssd), "no recompute op"


def test_scope_forms_under_jvp_transpose_and_remat(program):
    """The exact forms the readers match: layer scopes inside the scan over
    layers stay plain after ``jvp()`` / ``transpose(jvp())`` and, in the
    backward, after ``checkpoint`` (recompute: ``rematted_computation``);
    the LM head, outside that scan, is wrapped by the transform itself."""
    names = program.names
    mixer = "obs/ssm" if program.family == "ssm" else "obs/attn"
    scan = "obs/chunk_scan/while/body/closed_call/"
    forms = [
        f"{scan}jvp()/while/body/closed_call/{mixer}/",
        f"{scan}transpose(jvp())/while/body/closed_call/checkpoint/{mixer}/",
        f"{scan}transpose(jvp())/while/body/closed_call/checkpoint/"
        f"rematted_computation/{mixer}/",
        f"{scan}jvp(obs/lm_head)/",
        f"{scan}transpose(jvp(obs/lm_head))/",
        f"{scan}obs/update/",
        f"{scan}obs/psi_push/",
        f"{scan}cond/branch_1_fun/obs/accelerate/",
    ]
    for form in forms:
        assert any(form in n for n in names), form


def test_launcher_loop_host_spans_in_profiler_trace(dense, tmp_path):
    class Sink:
        """Stands in for the observer and the checkpointer."""
        def chunk(self, j, ms):
            pass

        def maybe_save(self, j, **kw):
            pass

    sink = Sink()
    params = jax.tree.map(jnp.copy, dense.params)   # the chunk donates them
    with dense.mesh:
        state = dense.init_fn(params)
        with jax.profiler.trace(str(tmp_path)):
            _drive_chunks(dense.compiled, state, params, dense.ring, K, K,
                          obs=sink, ckpt=sink)
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    spans = sorted((e.start_ns, e.name)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.name.startswith("train/"))
    assert [name for _, name in spans] == HOST_SPANS
