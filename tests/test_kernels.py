"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.flash_attention.kernel import live_q_range, live_tiles
from repro.kernels.flash_attention.ops import gqa_flash, gqa_ref
from repro.kernels.fused_xent import fused_xent, xent_ref
from repro.kernels.ssd_scan import ssd_chunked_pallas, ssd_ref

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# fused cross entropy
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,d,Vp,V,dtype", [
    (128, 64, 512, 500, jnp.float32),
    (256, 32, 1024, 1024, jnp.float32),
    (128, 64, 768, 700, jnp.bfloat16),
    (64, 128, 256, 256, jnp.float32),
])
def test_fused_xent_sweep(N, d, Vp, V, dtype):
    h = jax.random.normal(KEY, (N, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(jax.random.fold_in(KEY, 1), (d, Vp), jnp.float32)
         * 0.05).astype(dtype)
    labels = jax.random.randint(jax.random.fold_in(KEY, 2), (N,), 0, V)
    out = fused_xent(h, w, labels, vocab_size=V, bn=64, bv=256, interpret=True)
    ref = xent_ref(h, w, labels, vocab_size=V)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


def test_fused_xent_gold_never_in_padding():
    N, d, Vp, V = 64, 32, 512, 300
    h = jax.random.normal(KEY, (N, d))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (d, Vp)) * 0.05
    labels = jnp.full((N,), V - 1)
    out = fused_xent(h, w, labels, vocab_size=V, bn=64, bv=128, interpret=True)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("BH,S,hd,causal,window,dtype", [
    (4, 256, 64, True, None, jnp.float32),
    (2, 256, 64, True, 64, jnp.float32),
    (2, 128, 32, False, None, jnp.float32),
    (2, 256, 128, True, None, jnp.bfloat16),
    (1, 512, 64, True, 128, jnp.float32),
])
def test_flash_attention_sweep(BH, S, hd, causal, window, dtype):
    q = jax.random.normal(KEY, (BH, S, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (BH, S, hd),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (BH, S, hd),
                          jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, bq=64, bk=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


DEAD_TILE_SHAPES = [
    (2, 512, True, None, 64, 64),
    (1, 1024, True, None, 64, 64),
    (1, 1024, True, 128, 64, 64),     # window: dead tiles on both sides
    (2, 512, True, None, 128, 64),
    (2, 512, True, None, 64, 128),
    (2, 512, True, 96, 128, 64),      # window edge inside a tile, bq != bk
    (2, 256, False, None, 64, 64),    # every tile live
    (2, 512, False, 128, 64, 64),     # window alone: dead tiles on the left
    (2, 192, True, None, 64, 64),     # ragged: 3 tiles of 64
    (1, 512, True, 66, 64, 64),       # window reaches one row into a tile
]


@pytest.mark.parametrize("BH,S,causal,window,bq,bk", DEAD_TILE_SHAPES)
def test_flash_attention_skips_dead_tiles(BH, S, causal, window, bq, bk):
    """Tiles the mask kills are skipped; live tiles wholly inside the mask
    skip the mask; the result is the oracle's."""
    hd = 32
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), (BH, S, hd))
               for i in range(3))
    out = flash_attention(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("BH,S,causal,window,bq,bk,dtype", [
    *(shape + (jnp.float32,) for shape in DEAD_TILE_SHAPES),
    (2, 512, True, 96, 128, 64, jnp.bfloat16),
])
def test_flash_attention_grads_match_ref(BH, S, causal, window, bq, bk,
                                         dtype):
    """The backward kernel's dq, dk, dv are the oracle's vjp, with the
    dead tiles of both sweeps skipped."""
    hd = 32
    q, k, v, g = (jax.random.normal(jax.random.fold_in(KEY, i), (BH, S, hd),
                                    jnp.float32).astype(dtype)
                  for i in range(4))
    out = jax.vjp(lambda *a: gqa_flash(*(x[:, :, None] for x in a),
                                       causal=causal, window=window, bq=bq,
                                       bk=bk, interpret=True)[:, :, 0],
                  q, k, v)[1](g)
    ref = jax.vjp(lambda *a: attention_ref(*a, causal=causal, window=window),
                  q, k, v)[1](g)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for o, r in zip(out, ref):
        assert o.dtype == dtype
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


def test_live_tiles_pins():
    assert live_tiles(4096, 4096, 512, 512, True, None) == 36
    assert live_tiles(4096, 4096, 128, 128, True, None) == 528
    # window 128 of 64-tiles: the diagonal tile and the two before it
    assert live_tiles(1024, 1024, 64, 64, True, 128) == 1 + 2 + 14 * 3
    assert live_tiles(4096, 4096, 512, 512, False, None) == 64
    assert live_tiles(4096, 4096, 512, 1024, False, None) == 32


# (S, bq, bk, causal, window); window 66 at 64-tiles reaches one row into
# the second query tile after a key tile
MASK_SHAPES = [
    (512, 64, 64, True, None), (512, 128, 64, True, 96),
    (512, 64, 128, True, 200), (512, 64, 64, False, 128),
    (384, 128, 64, True, 64), (256, 64, 64, False, None),
    (512, 64, 64, True, 66),
]


def _alive(S, bq, bk, causal, window):
    """(q tile, k tile) pairs holding an unmasked entry, from the dense mask."""
    qpos = np.arange(S)[:, None]
    kpos = np.arange(S)[None, :]
    mask = np.ones((S, S), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    return mask.reshape(S // bq, bq, S // bk, bk).any(axis=(1, 3))


@pytest.mark.parametrize("S,bq,bk,causal,window", MASK_SHAPES)
def test_live_tiles_matches_mask(S, bq, bk, causal, window):
    """live_tiles counts exactly the tile pairs holding an unmasked entry."""
    alive = _alive(S, bq, bk, causal, window)
    assert live_tiles(S, S, bq, bk, causal, window) == int(alive.sum())


@pytest.mark.parametrize("S,bq,bk,causal,window", MASK_SHAPES)
def test_live_q_range_matches_mask(S, bq, bk, causal, window):
    """live_q_range gives each key tile exactly the query tiles holding an
    unmasked entry, the first to the last with none dead between."""
    alive = _alive(S, bq, bk, causal, window)
    for j in range(S // bk):
        first, last = live_q_range(j, bq=bq, bk=bk, nq=S // bq,
                                   causal=causal, window=window)
        live = np.flatnonzero(alive[:, j])
        assert (int(first), int(last)) == (live[0], live[-1])
        assert len(live) == live[-1] - live[0] + 1


def test_gqa_wrapper_matches_ref():
    B, S, H, K, hd = 2, 128, 8, 2, 32
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, hd))
    out = gqa_flash(q, k, v, bq=64, bk=64, interpret=True)
    ref = gqa_ref(q, k, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_gqa_flash_grads_match_ref():
    """Grads through the GQA wrapper: the K/V repeat's vjp sums each
    group's dK/dV from the backward kernel."""
    B, S, H, K, hd = 2, 128, 8, 2, 32
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, K, hd))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v) ** 2)
    out = jax.grad(loss(lambda *a: gqa_flash(*a, bq=64, bk=64,
                                             interpret=True)),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(gqa_ref), argnums=(0, 1, 2))(q, k, v)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(o, r, rtol=2e-5, atol=2e-5)


def test_flash_matches_model_attention_path():
    """Kernel ≡ the chunked-scan XLA path used by the models."""
    from repro.models.layers import _attend_chunked
    B, S, H, hd = 1, 256, 4, 32
    q = jax.random.normal(KEY, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, H, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, hd))
    model_out = _attend_chunked(q, k, v, causal=True, window=32, q_chunk=64)
    kern_out = gqa_flash(q, k, v, causal=True, window=32, bq=64, bk=64,
                         interpret=True)
    np.testing.assert_allclose(model_out, kern_out, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,S,nh,hd,G,ds,chunk", [
    (2, 128, 4, 32, 1, 16, 32),
    (1, 64, 2, 16, 1, 32, 16),
    (2, 128, 4, 32, 2, 16, 64),
])
def test_ssd_kernel_sweep(b, S, nh, hd, G, ds, chunk):
    x = jax.random.normal(KEY, (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (b, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (nh,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, S, G, ds))
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, S, G, ds))
    y1, s1 = ssd_chunked_pallas(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, s2 = ssd_ref(x, dt, A, B, C, chunk=chunk)
    np.testing.assert_allclose(y1, y2, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s1, s2, rtol=1e-3, atol=1e-3)


def test_ssd_chunking_invariance():
    """The chunked algorithm must be exact: chunk size cannot change results."""
    b, S, nh, hd, ds = 1, 64, 2, 16, 8
    x = jax.random.normal(KEY, (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (b, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (nh,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, S, 1, ds))
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, S, 1, ds))
    y16, s16 = ssd_ref(x, dt, A, B, C, chunk=16)
    y64, s64 = ssd_ref(x, dt, A, B, C, chunk=64)
    np.testing.assert_allclose(y16, y64, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s16, s64, rtol=1e-4, atol=1e-4)


def _ssd_naive(x, dt, A, B, C):
    """The step-by-step SSM recurrence (one group), in jnp so that
    ``jax.grad`` runs through it."""
    def step(state, inp):
        x_t, dt_t, B_t, C_t = inp          # (b,nh,hd), (b,nh), (b,ds), (b,ds)
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + jnp.einsum("bhp,bd->bhpd", x_t * dt_t[..., None], B_t))
        return state, jnp.einsum("bhpd,bd->bhp", state, C_t)

    b, _, nh, hd = x.shape
    init = jnp.zeros((b, nh, hd, B.shape[-1]))
    final, ys = jax.lax.scan(step, init, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B[:, :, 0], C[:, :, 0])))
    return jnp.moveaxis(ys, 0, 1), final


def test_ssd_matches_naive_recurrence():
    """Oracle of the oracle: step-by-step SSM recurrence."""
    b, S, nh, hd, ds = 1, 32, 2, 8, 4
    x = jax.random.normal(KEY, (b, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1), (b, S, nh)))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (nh,)) * 0.3)
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, S, 1, ds))
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, S, 1, ds))
    y_ref, s_ref = ssd_ref(x, dt, A, B, C, chunk=8)
    y_naive, state = _ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(y_ref, y_naive, rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(s_ref, state, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("impl", ["reference", "pallas_interpret"])
def test_ssd_grads_finite_at_published_decay(impl):
    """Gradients of x, dt, A, B and C at Mamba2's published decay: dt 0.1
    and A = -16 over a 64-long chunk put +Σ|dt A| ≈ 102 above the
    diagonal of the segment sum, past f32's exp range, so the mask has to
    come before the exp."""
    b, S, nh, hd, ds = 1, 64, 2, 16, 8
    x = jax.random.normal(KEY, (b, S, nh, hd))
    dt = jnp.full((b, S, nh), 0.1)
    A = jnp.array([-16.0, -1.0])
    B = jax.random.normal(jax.random.fold_in(KEY, 3), (b, S, 1, ds))
    C = jax.random.normal(jax.random.fold_in(KEY, 4), (b, S, 1, ds))
    wy = jax.random.normal(jax.random.fold_in(KEY, 5), (b, S, nh, hd))
    ws = jax.random.normal(jax.random.fold_in(KEY, 6), (b, nh, hd, ds))
    ssd = {"reference": lambda *a: ssd_ref(*a, chunk=S),
           "pallas_interpret": lambda *a: ssd_chunked_pallas(
               *a, chunk=S, interpret=True)}[impl]

    def loss(f):
        def g(*args):
            y, s = f(*args)
            return jnp.sum(y * wy) + jnp.sum(s * ws)
        return jax.grad(g, argnums=(0, 1, 2, 3, 4))

    got = loss(ssd)(x, dt, A, B, C)
    want = loss(_ssd_naive)(x, dt, A, B, C)
    for name, g, w in zip("x dt A B C".split(), got, want):
        assert np.isfinite(g).all(), name
        # f32 both ways, summed in different orders (a chunk's decays as
        # exp of cumsum differences, the recurrence as a running product):
        # round-off of a few ulp of the largest term, relative to the
        # gradient's own scale
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)


def test_fused_xent_custom_vjp_matches_ref():
    """The kernel is trainable: custom VJP ≡ autodiff of the oracle."""
    from repro.kernels.fused_xent.ops import fused_xent_sum, xent_ref_sum
    B, S, d, Vp, V = 2, 64, 32, 512, 500
    h = jax.random.normal(KEY, (B, S, d))
    w = jax.random.normal(jax.random.fold_in(KEY, 1), (d, Vp)) * 0.05
    y = jax.random.randint(jax.random.fold_in(KEY, 2), (B, S), 0, V)
    m = jnp.ones((B, S)).at[:, -1].set(0.0)

    def lf(h, w):
        t, c = fused_xent_sum(h, w, y, m, V, True)
        return t / c

    def lr(h, w):
        t, c = xent_ref_sum(h, w, y, m, V)
        return t / c

    v1, g1 = jax.value_and_grad(lf, argnums=(0, 1))(h, w)
    v2, g2 = jax.value_and_grad(lr, argnums=(0, 1))(h, w)
    assert abs(float(v1 - v2)) < 1e-5
    np.testing.assert_allclose(g1[0], g2[0], rtol=1e-3, atol=1e-4)
    np.testing.assert_allclose(g1[1], g2[1], rtol=1e-3, atol=1e-4)


def test_model_trains_with_fused_xent():
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("internlm2_1_8b").reduced()
    m1 = build_model(cfg, kernels="interpret")
    m2 = build_model(cfg, kernels="reference")
    params = m1.init(KEY, max_seq=32)
    batch = {"tokens": jnp.ones((2, 32), jnp.int32)}
    (l1, _), g1 = jax.value_and_grad(m1.loss_fn, has_aux=True)(params, batch)
    (l2, _), g2 = jax.value_and_grad(m2.loss_fn, has_aux=True)(params, batch)
    assert abs(float(l1 - l2)) < 5e-3
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        # bf16 grads: atol covers ~2 ulp at magnitude ~2 (bf16 eps 2^-8);
        # fused vs reference accumulate in different orders
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=5e-2, atol=2e-2)


def test_pallas_mode_needs_tpu_and_never_falls_back(monkeypatch):
    """``pallas`` resolves to itself on a TPU backend and raises, naming
    the backend, anywhere else — no silent reference fallback."""
    from repro.kernels import policy
    monkeypatch.setattr(policy.jax, "default_backend", lambda: "tpu")
    assert policy.resolve_kernels("pallas") == "pallas"
    monkeypatch.setattr(policy.jax, "default_backend", lambda: "cpu")
    with pytest.raises(RuntimeError, match="TPU backend.*'cpu'"):
        policy.resolve_kernels("pallas")
    for mode in ("reference", "interpret"):
        assert policy.resolve_kernels(mode) == mode
    with pytest.raises(ValueError):
        policy.resolve_kernels("mosaic")


def test_launcher_rejects_pallas_off_tpu(monkeypatch, tmp_path):
    from repro.kernels import policy
    from repro.launch.train import main
    # a set variable keeps main from turning on the in-checkout cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(policy.jax, "default_backend", lambda: "cpu")
    with pytest.raises(SystemExit, match="needs a TPU backend"):
        main(["--model", "transformer", "--kernels", "pallas"])
