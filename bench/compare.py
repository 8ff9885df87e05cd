"""The comparison that decides ``correct``.

Training, by the steps the window's own call made first: the program's
first chunk (``chunk_steps`` ISGD steps on distinct batches of the epoch,
all inside the controller's warm-up, where ISGD is its base rule) against
the plain float32 reference (``bench/reference/<config>.py``) following the
same steps from the same weights.  Compared:

- ``loss_gap``: the largest gap between the program's and the reference's
  loss over those steps (nats);
- ``velocity_gap``: the momentum after the chunk, by the worst leaf (each
  layer of a stacked leaf is a leaf): |program norm - reference norm| over
  the larger of the reference's norm of that leaf and of the median leaf;
- ``update_gap``: the same for the parameters' change over the chunk;
- ``controller_off``: over every step of the run, the steps where the
  program's accelerate decision disagrees with a plain replay of the SPC
  limit (psi_bar + k sigma over the last n_b losses, +inf before one epoch)
  on the program's own losses, where accelerate and subproblem trips
  disagree, or where the trips pass ``stop``.  Decisions on a loss within
  ``TIE`` of the limit are not judged.

The subproblem, by the window's first accelerated step: the reference
follows the chunk that holds it from the program's parameters and momentum
at the chunk's start (the program replayed from the seed through the
window's own call, as the run drove it), with as many Alg.2 trips at each
accelerated step as the program made.  Compared:

- ``trip_loss_gap``: the largest gap between the window's and the
  reference's loss over the chunk; steps after a firing start from the
  subproblem's answer;
- ``trip_update_gap``: the parameters' change over the chunk, trips
  included, by the worst leaf as ``update_gap`` (the program's end from its
  replay of the chunk);
- ``trips_off``: the decisions over the chunk where the program and the
  reference's own losses disagree: to accelerate, and after each trip, to
  run another (the reference's trip loss above its limit).  Ties as above.

A window with no accelerated step has no trip to count or to compare.

Leaves whose reference gradient at the first step is under a thousandth of
the median leaf's move by round-off alone and are left out of the two gaps.
"""
from __future__ import annotations

import numpy as np

#: relative band around the SPC limit inside which a decision is a tie
TIE = 1e-3
#: leaves with a first-step reference gradient under this share of the
#: median leaf's are left out of the norm gaps
SILENT_LEAF = 1e-3


_JIT = {}


def _cached(key, make):
    if key not in _JIT:
        _JIT[key] = make()
    return _JIT[key]


def reference_step(cell, quant="none"):
    """One momentum step of the plain reference: f32 compute at the highest
    matmul precision, each parameter kept in the type it is stored in.
    ``step(p, v, tokens) -> (p, v, loss, per-leaf gradient norms)``."""
    import jax
    import jax.numpy as jnp

    from bench import harness as H
    from bench.reference import common

    ref = H.reference_module(cell)
    model = dict(cell.config["model"])
    g = cell.config["isgd"]
    mu, lr = g["mu"], g["lr"]
    q = {"none": common.ident, "fp8": common.fp8}[quant]
    _, norms = H.leaf_norms(H.param_shapes(cell))

    def step(p, v, tokens):
        p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(ref.loss)(p32, tokens, model, q)
        v = jax.tree.map(lambda vi, gi: mu * vi - lr * gi, v, grads)
        p = jax.tree.map(lambda pi, wi, vi: (wi + vi).astype(pi.dtype),
                         p, p32, v)
        return p, v, loss, norms(grads)
    return step


def reference_trip(cell, quant="none"):
    """One Alg.2 trip of the plain reference: the loss psi and its gradient
    at ``w`` in f32 at the highest matmul precision, then
    ``w - zeta ((psi - limit) g + epsilon (w - w0) / n_w)``, kept in the type
    each parameter is stored in.  ``trip(w, w0, tokens, limit) -> (w, psi)``."""
    import jax
    import jax.numpy as jnp

    from bench import harness as H
    from bench.reference import common

    ref = H.reference_module(cell)
    model = dict(cell.config["model"])
    g = cell.config["isgd"]
    zeta = g.get("zeta") or g["lr"]
    eps = g["epsilon"]
    n_w = float(sum(x.size for x in jax.tree.leaves(H.param_shapes(cell))))
    q = {"none": common.ident, "fp8": common.fp8}[quant]

    def trip(w, w0, tokens, limit):
        w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
        with jax.default_matmul_precision("highest"):
            psi, grads = jax.value_and_grad(ref.loss)(w32, tokens, model, q)

        def upd(wi, gi, w0i):
            d = (psi - limit) * gi + eps * (wi - w0i.astype(jnp.float32)) / n_w
            return (wi - zeta * d).astype(w0i.dtype)
        return jax.tree.map(upd, w32, grads, w0), psi
    return trip


def first_firing(series, start):
    """The first accelerated step at or after ``start``, or None."""
    fired = np.flatnonzero(np.asarray(series["accelerated"], bool)[start:])
    return int(fired[0]) + start if fired.size else None


def _limit(losses, n_b, k_sigma):
    w = np.asarray(losses[-n_b:], np.float64)
    return w.mean() + k_sigma * w.std()


def reference_follow(cell, epoch, series, start, params, velocity, *,
                     quant="none"):
    """The plain reference (or the control, ``quant='fp8'``) over the chunk
    from step ``start``, from the program's parameters and momentum there
    (host arrays): each step's momentum update, its SPC limit over the
    program's losses before ``start`` and its own since, and at each
    accelerated step of the program as many trips as it made
    (``series['sub_iters']``), each trip's loss kept.  -> dict of per-step
    losses and limits, trip losses and the share of weights the trips
    moved, the first step's gradient norms, and update norms over the
    chunk."""
    import jax
    import jax.numpy as jnp

    from bench import harness as H

    n_b, gb = cell.traffic["n_batches"], cell.global_batch
    k, k_sigma = cell.traffic["chunk_steps"], cell.config["isgd"]["k_sigma"]
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    _, norms = H.leaf_norms(params)
    jstep = _cached(("step", cell.name, quant, 1),
                    lambda: jax.jit(reference_step(cell, quant),
                                    donate_argnums=(0, 1)))
    jtrip = _cached(("trip", cell.name, quant),
                    lambda: jax.jit(reference_trip(cell, quant)))
    jnorms = _cached(("norms", cell.name), lambda: jax.jit(norms))
    jmoved = _cached(("moved", cell.name), lambda: jax.jit(
        lambda a, b: sum(jnp.sum(x != y) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b))) / sum(
                x.size for x in jax.tree.leaves(a))))
    p = jax.device_put(params, one)
    v = jax.device_put(velocity, one)
    losses = [float(x) for x in series["loss"][:start]]
    out = {"start": start, "loss": [], "limit": [], "psi": {}, "moved": {},
           "grad0": None}
    for s in range(start, start + k):
        b = s % n_b
        rows = jax.device_put(epoch[b * gb:(b + 1) * gb], one)
        p, v, loss, gn = jstep(p, v, rows)
        if out["grad0"] is None:
            out["grad0"] = np.asarray(gn)
        losses.append(float(loss))
        limit = _limit(losses, n_b, k_sigma)
        out["loss"].append(losses[-1])
        out["limit"].append(limit)
        if series["sub_iters"][s]:
            w0, psi = p, []
            for _ in range(int(series["sub_iters"][s])):
                p, x = jtrip(p, w0, rows, limit)
                psi.append(float(x))
            out["psi"][s] = psi
            out["moved"][s] = float(jmoved(p, w0))
            del w0
    del v
    out["update"] = np.asarray(jnorms(p, jax.device_put(params, one)))
    return out


def _tie(x, limit):
    return abs(x - limit) <= TIE * abs(limit)


def trip_numbers(series, update, ref, stop, log=print):
    """-> {trip_loss_gap, trip_update_gap, trips_off} of the window
    (``series``) and the program's replayed chunk (``update``: its update
    norms) against ``reference_follow``'s ``ref``."""
    start = ref["start"]
    prog = np.asarray(series["loss"], np.float64)[start:start + len(ref["loss"])]
    gap = float(np.max(np.abs(prog - np.asarray(ref["loss"]))))
    keep = ref["grad0"] >= SILENT_LEAF * np.median(ref["grad0"])
    upd, _ = norm_gap(update, ref["update"], keep)
    off = 0
    for i, (x, lim) in enumerate(zip(ref["loss"], ref["limit"])):
        s = start + i
        off += int((x > lim) != bool(series["accelerated"][s])
                   and not _tie(x, lim))
        used = int(series["sub_iters"][s])
        for n, psi in enumerate(ref["psi"].get(s, [])[:stop - 1], start=1):
            off += int((psi > lim) != (n < used) and not _tie(psi, lim))
    log(f"subproblem in the chunk from step {start}: trips "
        f"{np.asarray(series['sub_iters'][start:start + len(prog)]).tolist()}"
        f"; loss program {np.round(prog, 5).tolist()} reference "
        f"{np.round(ref['loss'], 5).tolist()}; limits "
        f"{np.round(ref['limit'], 5).tolist()}; trip losses "
        f"{ {s: np.round(x, 5).tolist() for s, x in ref['psi'].items()} }, "
        f"weights they moved {ref['moved']}")
    return {"trip_loss_gap": gap, "trip_update_gap": upd, "trips_off": off}


def reference_run(cell, seed, epoch, *, quant="none", part=1, log=print):
    """The plain reference (or the control, ``quant='fp8'``; or a fault
    that leaves rows out, ``part=n``: each step on the first 1/n of its
    batch, the mean over those) over the first chunk's steps, on the
    first device, after the program's state is freed.  It computes in f32
    at the highest matmul precision and keeps each parameter in the type
    the configuration stores it in (bf16 matrices, f32 norm scales), as the
    program's update does.  -> dict of losses, first-step gradient norms,
    and velocity / update norms."""
    import jax
    import jax.numpy as jnp

    from bench import harness as H
    from bench.traffic.gen import seed_words

    k = cell.traffic["chunk_steps"]
    gb = cell.global_batch
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    w_seed, _ = seed_words(seed, 2)
    key = jax.random.PRNGKey(w_seed)
    p0 = H.make_params(cell, key, one)
    names, norms = H.leaf_norms(p0)
    jstep = _cached(("step", cell.name, quant, part),
                    lambda: jax.jit(reference_step(cell, quant),
                                    donate_argnums=(0, 1)))
    jnorms = _cached(("norms", cell.name), lambda: jax.jit(norms))
    zeros = _cached(("zeros", cell.name), lambda: jax.jit(
        lambda t: jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), t)))
    p = p0
    v = zeros(p)
    losses, gnorm0 = [], None
    for t in range(k):
        rows = epoch[t * gb:(t + 1) * gb]
        rows = rows[:gb // part]
        p, v, loss, gn = jstep(p, v, jax.device_put(rows, one))
        losses.append(float(loss))
        if gnorm0 is None:
            gnorm0 = np.asarray(gn)
    vel = np.asarray(jnorms(v))
    del v
    p0 = H.make_params(cell, key, one)
    upd = np.asarray(jnorms(p, p0))
    del p, p0
    return {"loss": losses, "grad0": gnorm0, "velocity": vel, "update": upd,
            "names": names}


def norm_gap(prog, ref, keep):
    """Worst leaf of |prog - ref| / max(ref, median ref) over ``keep``."""
    scale = np.maximum(ref, np.median(ref[keep]))
    gap = np.abs(np.asarray(prog) - ref) / scale
    i = int(np.argmax(np.where(keep, gap, -1.0)))
    return float(gap[i]), i


def gaps(prog, ref, log=print):
    """-> {loss_gap, velocity_gap, update_gap} of ``prog`` against ``ref``
    (both as ``reference_run`` returns them)."""
    keep = ref["grad0"] >= SILENT_LEAF * np.median(ref["grad0"])
    names = ref["names"]
    if not keep.all():
        log(f"left out as silent: {[n for n, k in zip(names, keep) if not k]}")
    loss_gap = float(np.max(np.abs(np.asarray(prog["loss"])
                                   - np.asarray(ref["loss"]))))
    vel, iv = norm_gap(prog["velocity"], ref["velocity"], keep)
    upd, iu = norm_gap(prog["update"], ref["update"], keep)
    log(f"loss program {np.round(prog['loss'], 5).tolist()}")
    log(f"loss reference {np.round(ref['loss'], 5).tolist()}")
    log(f"worst velocity leaf {names[iv]}, worst update leaf {names[iu]}")
    return {"loss_gap": loss_gap, "velocity_gap": vel, "update_gap": upd}


def spc_replay(losses, n_b, k_sigma):
    """Plain SPC limit per step, in float64: the mean plus k population
    standard deviations of the last n_b losses (this step's included);
    +inf until n_b losses have been seen."""
    losses = np.asarray(losses, np.float64)
    out = np.full(losses.shape, np.inf)
    for t in range(n_b - 1, len(losses)):
        w = losses[t - n_b + 1:t + 1]
        out[t] = w.mean() + k_sigma * w.std()
    return out


def controller_off(series, n_b, k_sigma, stop, log=print):
    loss = np.asarray(series["loss"], np.float64)
    acc = np.asarray(series["accelerated"], bool)
    trips = np.asarray(series["sub_iters"])
    limit_prog = np.asarray(series["limit"], np.float64)
    limit = spc_replay(loss, n_b, k_sigma)
    finite = np.isfinite(limit)
    tie = finite & (np.abs(loss - limit) <= TIE * np.abs(limit))
    want = finite & (loss > limit)
    wrong = (acc != want) & ~tie
    wrong |= np.isfinite(limit_prog) != finite
    wrong |= acc != (trips > 0)
    wrong |= trips > stop
    rel = np.abs(limit_prog[finite] - limit[finite]) / np.abs(limit[finite])
    log(f"controller: {int(acc.sum())} accelerated of {len(loss)} steps, "
        f"{int(tie.sum())} ties, largest relative limit gap "
        f"{float(rel.max()) if rel.size else 0.0:.3e}")
    return int(wrong.sum())


def compare(cell, seed, epoch, prog, names, series, trip=None, log=print):
    """-> {number: {"value", "limit"}} for the run.  ``trip`` is None, or
    as ``harness.Program.trip_chunk`` gives it for the chunk that holds the
    window's first accelerated step: (start, (params, momentum) there on
    the host, the program's update norms over the chunk)."""
    g = cell.config["isgd"]
    trip_values = {}
    if trip is not None:
        start, (params, velocity), update = trip
        del trip
        followed = reference_follow(cell, epoch, series, start, params,
                                    velocity)
        del params, velocity
        trip_values = trip_numbers(series, update, followed, g["stop"],
                                   log=log)
    else:
        log("no accelerated step in the window: no subproblem to compare")
    ref = reference_run(cell, seed, epoch, log=log)
    assert ref["names"] == names
    values = gaps(prog, ref, log=log)
    values["controller_off"] = controller_off(
        series, cell.traffic["n_batches"], g["k_sigma"], g["stop"], log=log)
    values.update(trip_values)
    limits = dict(cell.limits.get("limits", {}))
    limits.setdefault("controller_off", 0)
    limits.setdefault("trips_off", 0)
    out = {}
    for name, v in values.items():
        lim = limits.get(name)
        out[name] = {"value": v, "limit": lim if lim is not None else -1.0}
    return out
