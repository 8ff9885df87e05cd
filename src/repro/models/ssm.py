"""Mamba2 mixer — SSD (state-space duality) form [arXiv:2405.21060].

TPU adaptation (DESIGN.md §2): the within-chunk computation is expressed as
decay-masked block matmuls (MXU-friendly), and the cross-chunk recurrence is a
``lax.scan`` over chunk states — O(S/chunk) sequential steps instead of O(S).
The same chunk decomposition backs the Pallas ``ssd_scan`` kernel.

Decode is the dual recurrent form: an O(1) state update per token; the "KV
cache" of an SSM layer is just (conv_state, ssd_state).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.mode import scan_unroll
from repro.models.layers import dense_init, rms_norm
from repro.obs.timing import SSD, named_scope


def conv_channels(cfg) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def init_ssm(key, cfg, dtype=jnp.bfloat16):
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_nheads
    cch = conv_channels(cfg)
    ks = jax.random.split(key, 3)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * di + 2 * cfg.ssm_ngroups * cfg.ssm_state + nh), dtype),
        "conv_w": dense_init(ks[1], (cfg.conv_width, cch), dtype),
        "conv_b": jnp.zeros((cch,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(jnp.full((nh,), 0.01, jnp.float32))),
        "gnorm": jnp.zeros((di,), jnp.float32),
        "out_proj": dense_init(ks[2], (di, d), dtype),
    }


def _split_proj(cfg, zxbcdt):
    di, gs = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * gs]
    dt = zxbcdt[..., 2 * di + 2 * gs:]
    return z, xBC, dt


def _causal_conv(xBC, w, b):
    """Depthwise causal conv, width cw. xBC: (B, S, C); w: (cw, C)."""
    cw = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (cw - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i] for i in range(cw))
    return jax.nn.silu(out + b)


def _segsum(dAh):
    """dAh: (..., cl) cumulative-decay matrix L[i,j] = exp(Σ_{j<m<=i} dA_m), i>=j.

    The exponent is masked to -inf above the diagonal *before* the exp: there
    it is +Σ|dA|, which overflows over a long chunk at published decays, and
    ``where(mask, exp(diff), 0)`` would then pass 0 · inf = NaN into the
    gradient."""
    cl = dAh.shape[-1]
    cum = jnp.cumsum(dAh, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((cl, cl), bool))
    return jnp.exp(jnp.where(mask, diff, -jnp.inf))


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """SSD forward over chunks.

    x: (b, S, nh, hd); dt: (b, S, nh) (post-softplus); A: (nh,) negative;
    B, C: (b, S, G, ds).  Returns (y: (b, S, nh, hd), final_state:
    (b, nh, hd, ds)).
    """
    b, S, nh, hd = x.shape
    G, ds = B.shape[-2], B.shape[-1]
    cl = min(chunk, S)
    while S % cl:                # largest dividing chunk (kernel twin agrees)
        cl -= 1
    nc = S // cl
    rep = nh // G

    # broadcast groups -> heads
    Bh = jnp.repeat(B, rep, axis=-2).reshape(b, nc, cl, nh, ds)
    Ch = jnp.repeat(C, rep, axis=-2).reshape(b, nc, cl, nh, ds)
    xr = x.reshape(b, nc, cl, nh, hd)
    dtr = dt.reshape(b, nc, cl, nh)
    xdt = xr * dtr[..., None]

    dAh = jnp.moveaxis(dtr * A, -1, -2)                          # (b, nc, nh, cl)
    cum = jnp.cumsum(dAh, axis=-1)                               # (b, nc, nh, cl)

    # --- intra-chunk: decay-masked block matmul ------------------------------
    L = _segsum(dAh)                                             # (b, nc, nh, cl, cl)
    CB = jnp.einsum("bnihd,bnjhd->bnhij", Ch.astype(jnp.float32),
                    Bh.astype(jnp.float32))
    Y_diag = jnp.einsum("bnhij,bnjhp->bnihp", CB * L, xdt.astype(jnp.float32))

    # --- chunk states --------------------------------------------------------
    decay_states = jnp.exp(cum[..., -1:] - cum)                  # (b, nc, nh, cl)
    states = jnp.einsum("bnhj,bnjhp,bnjhd->bnhpd",
                        decay_states, xdt.astype(jnp.float32),
                        Bh.astype(jnp.float32))                  # (b, nc, nh, hd, ds)
    chunk_decay = jnp.exp(cum[..., -1])                          # (b, nc, nh)

    # --- inter-chunk recurrence ----------------------------------------------
    init = (jnp.zeros((b, nh, hd, ds), jnp.float32) if initial_state is None
            else initial_state.astype(jnp.float32))

    def step(state, inp):
        s_n, decay_n = inp                                       # (b,nh,hd,ds), (b,nh)
        new = state * decay_n[..., None, None] + s_n
        return new, state                                        # emit state BEFORE chunk

    final_state, prevs = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
        unroll=scan_unroll())
    prevs = jnp.moveaxis(prevs, 0, 1)                            # (b, nc, nh, hd, ds)

    # --- inter-chunk contribution --------------------------------------------
    Y_off = jnp.einsum("bnihd,bnhpd,bnhi->bnihp",
                       Ch.astype(jnp.float32), prevs, jnp.exp(cum))
    y = (Y_diag + Y_off).reshape(b, S, nh, hd)
    return y, final_state


def ssm_forward(params, cfg, x, kernels: str = "reference"):
    """Full-sequence Mamba2 mixer. x: (B, S, d) -> (y, (conv_state, ssd_state))."""
    b, S, d = x.shape
    di, nh, hd = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    G, ds = cfg.ssm_ngroups, cfg.ssm_state
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = _split_proj(cfg, zxbcdt)
    conv_state = xBC[:, -(cfg.conv_width - 1):, :]               # cache tail
    xBC = _causal_conv(xBC, params["conv_w"], params["conv_b"])
    xs = xBC[..., :di].reshape(b, S, nh, hd)
    Bm = xBC[..., di:di + G * ds].reshape(b, S, G, ds)
    Cm = xBC[..., di + G * ds:].reshape(b, S, G, ds)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    with named_scope(SSD):
        if kernels != "reference":
            from repro.kernels.ssd_scan.ops import ssd_chunked_pallas
            y, ssd_state = ssd_chunked_pallas(xs, dt, A, Bm, Cm,
                                              chunk=cfg.ssm_chunk,
                                              interpret=kernels == "interpret")
        else:
            y, ssd_state = ssd_chunked(xs, dt, A, Bm, Cm, chunk=cfg.ssm_chunk)
    y = y + params["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(b, S, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["gnorm"], cfg.norm_eps)
    return y @ params["out_proj"], (conv_state, ssd_state)


def ssm_decode(params, cfg, x, conv_state, ssd_state):
    """One-token recurrent update.

    x: (B, 1, d); conv_state: (B, cw-1, cch); ssd_state: (B, nh, hd, ds).
    """
    b = x.shape[0]
    di, nh, hd = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    G, ds = cfg.ssm_ngroups, cfg.ssm_state
    zxbcdt = x @ params["in_proj"]
    z, xBC, dt = _split_proj(cfg, zxbcdt)                        # (B,1,*)
    window = jnp.concatenate([conv_state, xBC], axis=1)          # (B, cw, cch)
    new_conv_state = window[:, 1:, :]
    out = jnp.einsum("bwc,wc->bc", window, params["conv_w"]) + params["conv_b"]
    xBC = jax.nn.silu(out)[:, None, :]                           # (B,1,cch)
    xs = xBC[..., :di].reshape(b, nh, hd)
    Bm = jnp.repeat(xBC[..., di:di + G * ds].reshape(b, G, ds), nh // G, axis=1)
    Cm = jnp.repeat(xBC[..., di + G * ds:].reshape(b, G, ds), nh // G, axis=1)
    dt1 = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # (B, nh)
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dt1 * A)                                     # (B, nh)
    xdt = xs.astype(jnp.float32) * dt1[..., None]                # (B, nh, hd)
    new_state = (ssd_state * decay[..., None, None]
                 + jnp.einsum("bhp,bhd->bhpd", xdt, Bm.astype(jnp.float32)))
    y = jnp.einsum("bhpd,bhd->bhp", new_state, Cm.astype(jnp.float32))
    y = y + params["D"][:, None] * xs.astype(jnp.float32)
    y = y.reshape(b, 1, di).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), params["gnorm"], cfg.norm_eps)
    return y @ params["out_proj"], new_conv_state, new_state
