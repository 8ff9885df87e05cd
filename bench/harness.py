"""One run of one cell: set-up, measured window, traced window, and the
comparison with the plain reference that decides ``correct``.

The window drives the program's own fused chunked hybrid engine
(``make_chunked_hybrid_step``, built as ``launch/train.py::run_sync`` builds
it) through the launcher's own loop ``_drive_chunks``, one FCPR epoch per
call.  Everything a cell needs is found by name: ``BENCHMARK.json`` names
the configuration (``bench/configs/<config>.json``, with its plain
reference ``bench/reference/<reference>.py``), the traffic mix
(``bench/traffic/<traffic>.json``), the limits of the comparison
(``bench/limits/<cell>.json``) and the per-layer metrics
(``bench/metrics/<metric>.py``).
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import trace as TR

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class BenchError(Exception):
    """A run that cannot produce a result (no chip, unknown device, a
    compile inside the window, a missing file)."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    per_layer: list

    @property
    def global_batch(self) -> int:
        return self.traffic["batch_per_chip"] * self.chips

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch * self.traffic["seq_len"]


def find_cell(name: str, benchmark: Path = ROOT / "BENCHMARK.json") -> Cell:
    spec = load_json(benchmark)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {benchmark.name}; "
                         f"known: {sorted(cells)}")
    w = cells[name]

    def applies(m):
        return name in m.get("workloads", [name])

    limits_path = BENCH / "limits" / f"{name}.json"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(BENCH / "configs" / f"{w['config']}.json"),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(limits_path) if limits_path.exists() else {},
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def reference_module(cell: Cell):
    return importlib.import_module(f"bench.reference.{cell.config['reference']}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def model_config(cell: Cell):
    from repro.configs import get_config
    return dataclasses.replace(get_config(cell.config["arch"]),
                               **cell.config["model"])


def loss_fn_for(cell: Cell, kernels: str):
    """The model's loss as ``build_model`` makes it.  ``pallas`` on the chip;
    the self-tests pass ``interpret`` or ``reference`` on the CPU."""
    cfg = model_config(cell)
    remat = cell.config["remat"]
    if kernels == "pallas":
        from repro.models import build_model
        return build_model(cfg, kernels="pallas", remat=remat != "none",
                           remat_policy="full").loss_fn
    from repro.models import transformer as T

    def loss_fn(params, batch):
        return T.lm_loss_fn(params, cfg, batch, remat=remat != "none",
                            remat_policy="full", kernels=kernels)
    return loss_fn


def build_engine(cell: Cell, devices, loss_fn):
    """Mesh, update rule, controller and the jitted chunk fn, exactly as
    ``run_sync`` builds the fused hybrid engine."""
    from repro.core import ISGDConfig
    from repro.core.schedule import constant_lr
    from repro.distributed import make_chunked_hybrid_step
    from repro.launch.mesh import make_training_mesh
    from repro.optim import RULES

    mesh = make_training_mesh(devices=devices)
    g = cell.config["isgd"]
    rule = RULES[g["rule"]](mu=g["mu"])
    icfg = ISGDConfig(n_batches=cell.traffic["n_batches"],
                      k_sigma=g["k_sigma"], stop=g["stop"],
                      epsilon=g["epsilon"])
    init_fn, jchunk = make_chunked_hybrid_step(
        loss_fn, rule, icfg, mesh, chunk_steps=cell.traffic["chunk_steps"],
        inconsistent=True, lr_fn=constant_lr(g["lr"]))
    return mesh, init_fn, jchunk


def param_shapes(cell: Cell):
    import jax

    from repro.models import transformer as T
    cfg = model_config(cell)
    return jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), cfg, max_seq=cell.traffic["seq_len"]))


def make_params(cell: Cell, key, sharding):
    """The weights, drawn from the seed by the configuration's reference
    module, on the device in one jitted call, in the type they train in."""
    import jax
    ck = (cell.name, sharding)
    if ck not in _INIT:
        ref = reference_module(cell)
        shapes = param_shapes(cell)
        model = cell.config["model"]
        out = jax.tree.map(lambda _: sharding, shapes)
        _INIT[ck] = jax.jit(lambda k: ref.init(k, shapes, model),
                            out_shardings=out)
    return _INIT[ck](key)


_INIT = {}


class ChunkCall:
    """The window's call: the program's chunk fn, keeping hold of the params
    it returns (``_drive_chunks`` hands back the state only) and marking the
    dispatch as a host span for the trace."""

    def __init__(self, jchunk, params):
        self.jchunk = jchunk
        self.params = params

    def __call__(self, state, params, arrays, j):
        import jax
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            state, params, ms = self.jchunk(state, params, arrays, j)
        self.params = params
        return state, params, ms


def leaf_norms(tree):
    """Per-leaf f32 norms, one per layer for the stacked block leaves.
    -> (names, jitted fn tree -> (n,) norms)."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    names = []
    for path, x in flat:
        label = jax.tree_util.keystr(path)
        stacked = "'blocks'" in label
        if stacked:
            names += [f"{label}[layer {i}]" for i in range(x.shape[0])]
        else:
            names.append(label)

    def norms(t, base=None):
        out = []
        leaves = jax.tree_util.tree_flatten_with_path(t)[0]
        bases = (jax.tree.leaves(base) if base is not None
                 else [None] * len(leaves))
        for (path, x), b in zip(leaves, bases):
            x = x.astype(jnp.float32)
            if b is not None:
                x = x - b.astype(jnp.float32)
            if "'blocks'" in jax.tree_util.keystr(path):
                out.append(jnp.sqrt(jnp.sum(x * x, axis=tuple(
                    range(1, x.ndim)))))
            else:
                out.append(jnp.sqrt(jnp.sum(x * x))[None])
        return jnp.concatenate(out)
    return names, norms


# ---------------------------------------------------------------------------
# compile counter
# ---------------------------------------------------------------------------
class CompileCounter:
    """Counts XLA backend compiles (a program loaded from the persistent
    cache is not one)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def device_info(devices):
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def check_devices(cell: Cell, require_chip: bool):
    """The chips the cell asks for, and their peaks; a missing chip or a
    device kind not in ``bench/peaks.json`` is an error."""
    import jax
    devices = jax.devices()
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX's devices are "
                             f"{devices[0].platform!r}")
        if len(devices) < cell.chips:
            raise BenchError(f"cell {cell.name} needs {cell.chips} chips, "
                             f"JAX has {len(devices)}")
        if devices[0].device_kind not in peaks:
            raise BenchError(f"device kind {devices[0].device_kind!r} is "
                             f"not in bench/peaks.json")
        return devices[:cell.chips], peaks[devices[0].device_kind]
    return devices[:cell.chips], None


class Program:
    """The program under test, built once per process: the engine on the
    cell's chips, and what starts a run of it from a seed."""

    def __init__(self, cell: Cell, devices, kernels: str, loss_wrap=None):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch import shardings as SH
        self.cell, self.devices = cell, devices
        loss_fn = loss_fn_for(cell, kernels)
        if loss_wrap is not None:
            loss_fn = loss_wrap(loss_fn)
        self.mesh, self.init_fn, self.jchunk = build_engine(cell, devices,
                                                            loss_fn)
        self.rep = NamedSharding(self.mesh, P())
        shapes = param_shapes(cell)
        self.p_sh = jax.tree.map(lambda _: self.rep, shapes)
        self.init_state = jax.jit(self.init_fn, out_shardings=SH.state_shardings(
            self.mesh, jax.eval_shape(self.init_fn, shapes), self.p_sh))
        self.names, norms = leaf_norms(shapes)
        self.norms = jax.jit(norms)

    def start(self, seed: int):
        """Weights and the epoch from the seed, on the device.
        -> (call, state, ring, epoch, hard batch indices)."""
        import jax

        from repro.data import DeviceRing
        from repro.launch import shardings as SH

        from bench.traffic.gen import make_epoch, seed_words
        cell = self.cell
        w_seed, _ = seed_words(seed, 2)
        params = make_params(cell, jax.random.PRNGKey(w_seed), self.rep)
        params, _ = SH.hybrid_params_placement(self.mesh, params)
        state = self.init_state(params)
        epoch, hard = make_epoch(cell.traffic, seed,
                                 cell.config["model"]["vocab_size"],
                                 cell.chips)
        ring = DeviceRing({"tokens": epoch}, cell.global_batch,
                          mesh=self.mesh, axis=None, relayout=True)
        return ChunkCall(self.jchunk, params), state, ring, epoch, hard

    def first_chunk(self, seed, call, state, ring):
        """The first chunk through the window's own call and feed: the
        steps that the reference follows.  -> (state, j, chunks, numbers
        of the program: per-step losses, velocity and update norms)."""
        import jax

        from repro.launch.train import _drive_chunks

        from bench.traffic.gen import seed_words
        k = self.cell.traffic["chunk_steps"]
        state, j, first = _drive_chunks(call, state, call.params, ring, k, k)
        w_seed, _ = seed_words(seed, 2)
        p0 = make_params(self.cell, jax.random.PRNGKey(w_seed), self.rep)
        prog = {"loss": [float(x) for x in first[0]["metrics"]["loss"]],
                "velocity": np.asarray(self.norms(state.base)),
                "update": np.asarray(self.norms(call.params, p0))}
        return state, j, first, prog

    def trip_chunk(self, seed, start):
        """The program replayed from the seed through the window's own call
        to ``start`` (a chunk boundary), as the run drove it, then one chunk
        more.  -> (params and momentum at ``start`` on the host, the
        program's update norms over that chunk, the replay's chunks)."""
        import jax

        from repro.launch.train import _drive_chunks
        k = self.cell.traffic["chunk_steps"]
        call, state, ring, _, _ = self.start(seed)
        state, _, chunks = _drive_chunks(call, state, call.params, ring,
                                         start, k)
        host = jax.device_get((call.params, state.base))
        state, _, more = _drive_chunks(call, state, call.params, ring,
                                       start + k, k, start=start)
        base = jax.device_put(host[0], self.rep)
        update = np.asarray(self.norms(call.params, base))
        return host, update, chunks + more


def series_of(chunks):
    """Per-step controller metrics of ``chunks``, concatenated."""
    return {key: np.concatenate([np.asarray(c["metrics"][key])
                                 for c in chunks])
            for key in ("loss", "limit", "accelerated", "sub_iters")}


def setup_cache():
    import jax

    from repro.launch import env as ENV
    ENV.setup_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True, kernels: str = "pallas",
        loss_wrap=None, keep_trace=None, log=print) -> dict:
    """Set-up, window (or traced epoch), then the comparison.  Returns the
    result object of the contract.  Untraced, ``tokens_per_s`` is the
    tokens through the ISGD step's forward+backward passes per second: each
    main step and each subproblem trip on its batch (``trace.passes``) over the
    window's wall time; tracing off.  ``loss_wrap`` (the fault tests) wraps
    the program's loss before the engine is built; ``keep_trace`` (the
    trace recorder of the self-tests) is a path that keeps the traced
    run's ``.xplane.pb``."""
    import jax

    from repro.launch.train import _drive_chunks

    from bench import compare

    setup_cache()
    counter = CompileCounter()
    devices, peaks = check_devices(cell, require_chip)
    tr = cell.traffic
    n_b, k = tr["n_batches"], tr["chunk_steps"]
    assert n_b % k == 0, "an epoch is a whole number of chunks"

    prog_ = Program(cell, devices, kernels, loss_wrap)
    call, state, ring, epoch, hard = prog_.start(seed)
    log(f"cell {cell.name}: seed {seed}, {len(devices)} x "
        f"{devices[0].device_kind}, hard batches at {hard}")
    with prog_.mesh:
        state, j, chunks, prog = prog_.first_chunk(seed, call, state, ring)
        for _ in range(tr["warmup_epochs"]):
            state, j, more = _drive_chunks(call, state, call.params, ring,
                                           j + n_b - (j % n_b), k, start=j)
            chunks += more
        setup_s = time.perf_counter() - t_start
        compiles_before = counter.n
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        t0 = time.perf_counter()
        w0 = len(chunks)
        if trace:
            jax.profiler.start_trace(trace_dir)
        while True:
            with jax.profiler.TraceAnnotation("bench/epoch"):
                state, j, more = _drive_chunks(call, state, call.params, ring,
                                               j + n_b, k, start=j)
            chunks += more
            if trace or time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        window_compiles = counter.n - compiles_before
        if trace:
            jax.profiler.stop_trace()
        window_s = t1 - t0
        log(f"set-up {setup_s:.3f} s, window {window_s:.3f} s over "
            f"{len(chunks) - w0} chunks, compiles in window: "
            f"{window_compiles}")
        if window_compiles:
            raise BenchError(f"{window_compiles} compile(s) inside the "
                             f"measured window")
        mem = [d.memory_stats() or {} for d in devices]
        memory_peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
        log(f"memory: peak {memory_peak} of {mem[0].get('bytes_limit')} "
            f"bytes on the fullest chip; chip 0's stats {mem[0]}")
        win = chunks[w0:]
        steps = sum(len(c["metrics"]["loss"]) for c in win)
        trips = sum(int(np.sum(c["metrics"]["sub_iters"])) for c in win)
        del state, call, ring
        series = series_of(chunks)
        w_start = len(series["loss"]) - steps
        log(f"window: {steps} steps, {trips} subproblem trips; accelerated "
            f"at steps {np.flatnonzero(series['accelerated']).tolist()} of "
            f"{len(series['loss'])} (window from step {w_start})")
        trip = None
        fire = compare.first_firing(series, w_start)
        if fire is not None:
            start = fire - fire % k
            host, update, replay = prog_.trip_chunk(seed, start)
            drift = np.abs(series_of(replay)["loss"]
                           - series["loss"][:start + k])
            log(f"replayed steps 0-{start + k - 1} from the seed: largest "
                f"loss change against the run {float(drift.max()):.3e}")
            trip = (start, host, update)
            del host

    numbers = compare.compare(cell, seed, epoch, prog, prog_.names, series,
                              trip, log=log)
    del trip
    correct = all(v["value"] <= v["limit"] for v in numbers.values())
    result = {"correct": bool(correct), "attempted": steps,
              "failed": 0 if correct else steps, "trips": trips,
              "window_s": window_s}
    if trace:
        if keep_trace:
            shutil.copy(TR.xplane_path(trace_dir), keep_trace)
        tdata = TR.load(trace_dir, len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = TR.Context(cell=cell, peaks=peaks, trace=tdata, steps=steps,
                         trips=trips)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device_info(devices),
                                memory_peak_bytes=int(memory_peak),
                                busy_s=tdata.busy_s(), window_s=tdata.window_s)
        result["breakdown"] = TR.breakdown(tdata)
    else:
        result["metrics"] = {
            "tokens_per_s": {"value": TR.passes(steps, trips)
                             * cell.tokens_per_step / window_s,
                             "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        result["device"] = dict(device_info(devices),
                                memory_peak_bytes=int(memory_peak))
    result["compared"] = numbers
    return result
