"""launch/env.py: XLA flag composition + process-topology wiring.

The three guarantees the module docstring promises — append-never-clobber,
idempotent re-entry, single init — plus the CLI argument surface.  All
flag tests run against an explicit ``env=`` dict so nothing here touches
the real ``os.environ`` or initializes a jax backend.
"""
import argparse

import pytest

from repro.launch import env as ENV


# ---------------------------------------------------------------------------
# apply_xla_flags: append, never clobber
# ---------------------------------------------------------------------------
def test_apply_appends_after_user_flags():
    e = {"XLA_FLAGS": "--xla_user_thing=7"}
    out = ENV.apply_xla_flags(["--xla_new=1"], env=e)
    assert out == "--xla_user_thing=7 --xla_new=1"
    assert e["XLA_FLAGS"] == out


def test_user_set_flag_wins_by_default():
    e = {"XLA_FLAGS": "--xla_knob=user"}
    ENV.apply_xla_flags(["--xla_knob=ours", "--xla_other=1"], env=e)
    assert e["XLA_FLAGS"] == "--xla_knob=user --xla_other=1"


def test_override_replaces_in_place():
    e = {"XLA_FLAGS": "--xla_a=1 --xla_knob=old --xla_b=2"}
    ENV.apply_xla_flags(["--xla_knob=new"], env=e, override=True)
    # the stale occurrence is removed (not shadowed) and others survive
    assert e["XLA_FLAGS"] == "--xla_a=1 --xla_b=2 --xla_knob=new"


def test_apply_is_idempotent():
    e = {"XLA_FLAGS": "--xla_user_thing=7"}
    once = ENV.apply_xla_flags(list(ENV.GPU_ASYNC_FLAGS), env=e)
    twice = ENV.apply_xla_flags(list(ENV.GPU_ASYNC_FLAGS), env=e)
    assert once == twice == e["XLA_FLAGS"]


def test_apply_from_empty_env():
    e = {}
    ENV.apply_xla_flags(["--xla_a=1"], env=e)
    assert e["XLA_FLAGS"] == "--xla_a=1"


def test_flag_name_strips_value():
    assert ENV._flag_name("--xla_foo=3") == "--xla_foo"
    assert ENV._flag_name("--xla_bar") == "--xla_bar"


# ---------------------------------------------------------------------------
# platform-specific composition
# ---------------------------------------------------------------------------
def test_async_flags_gpu_appends_group():
    e = {"XLA_FLAGS": "--xla_user_thing=7"}
    ENV.apply_async_collective_flags("gpu", env=e)
    for flag in ENV.GPU_ASYNC_FLAGS:
        assert flag in e["XLA_FLAGS"].split()
    assert e["XLA_FLAGS"].split()[0] == "--xla_user_thing=7"


def test_async_flags_cpu_is_noop():
    e = {"XLA_FLAGS": "--xla_user_thing=7"}
    ENV.apply_async_collective_flags("cpu", env=e)
    assert e["XLA_FLAGS"] == "--xla_user_thing=7"


def test_async_flags_platform_from_env_var():
    e = {"JAX_PLATFORMS": "gpu,cpu"}
    ENV.apply_async_collective_flags(env=e)
    assert ENV.GPU_ASYNC_FLAGS[0] in e["XLA_FLAGS"].split()


def test_force_host_device_count_overrides_but_preserves():
    e = {"XLA_FLAGS":
         "--xla_user_thing=7 --xla_force_host_platform_device_count=2"}
    ENV.force_host_device_count(8, env=e)
    assert e["XLA_FLAGS"] == (
        "--xla_user_thing=7 --xla_force_host_platform_device_count=8")
    before = e["XLA_FLAGS"]
    ENV.force_host_device_count(8, env=e)           # idempotent re-entry
    assert e["XLA_FLAGS"] == before


# ---------------------------------------------------------------------------
# topology + CLI surface
# ---------------------------------------------------------------------------
def test_topology_coordinator_is_process_zero():
    assert ENV.ProcessTopology().is_coordinator
    assert ENV.ProcessTopology(process_id=0, num_processes=4).is_coordinator
    assert not ENV.ProcessTopology(process_id=3,
                                   num_processes=4).is_coordinator


def test_add_process_args_roundtrip_single_process():
    ap = argparse.ArgumentParser()
    ENV.add_process_args(ap)
    args = ap.parse_args([])
    topo = ENV.initialize_from_args(args)    # no coordinator -> no-op
    assert topo.num_processes == 1 and topo.is_coordinator


def test_initialize_requires_full_process_spec():
    with pytest.raises(ValueError, match="--num-processes"):
        ENV.initialize_distributed("127.0.0.1:1234")


def test_initialize_rejects_conflicting_reinit(monkeypatch):
    recorded = ENV.ProcessTopology(process_id=0, num_processes=2,
                                   coordinator="127.0.0.1:1234")
    monkeypatch.setattr(ENV, "_TOPOLOGY", recorded)
    # same args: returns the recorded topology, never re-initializes
    assert ENV.initialize_distributed("127.0.0.1:1234", 2, 0) is recorded
    with pytest.raises(RuntimeError, match="already initialized"):
        ENV.initialize_distributed("127.0.0.1:1234", 2, 1)


def test_single_process_call_respects_recorded_topology(monkeypatch):
    recorded = ENV.ProcessTopology(process_id=1, num_processes=2,
                                   coordinator="127.0.0.1:1234")
    monkeypatch.setattr(ENV, "_TOPOLOGY", recorded)
    assert ENV.initialize_distributed() is recorded


# ---------------------------------------------------------------------------
# setup_compilation_cache: env var wins, else a fixed in-checkout path
# ---------------------------------------------------------------------------
def test_compilation_cache_env_var_wins():
    import jax
    before = jax.config.jax_compilation_cache_dir
    env = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere/cache"}
    assert ENV.setup_compilation_cache(env=env) == "/elsewhere/cache"
    # JAX reads the variable itself: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_default_is_fixed_and_git_ignored():
    import os

    import jax
    before = jax.config.jax_compilation_cache_dir
    try:
        path = ENV.setup_compilation_cache(env={})
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
