"""Custom-VJP parity for the fused decision-fusion loss kernel.

Locks the blocked backward Pallas kernel (interpret mode on CPU CI) against
``jax.grad`` through the float64 reference: dlogits for every avail-mask
configuration, exact-zero gradients for masked modalities and zero-cotangent
(sample-mask-padded) rows, the fused ζ/δ partials (gsq/gdot), the dict
front-end's fwd+grad agreement with ``core.fusion.multimodal_loss``, the
Gram-form tracker refresh, and end-to-end ``engine="fused:pallas"`` vs
``engine="fused"`` equivalence over a multi-round scan.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fusion as core_fusion
from repro.core.convergence import (grad_gram, tracker_update_cohort,
                                    tracker_update_gram)
from repro.kernels.fusion_loss import ops as kops
from repro.kernels.fusion_loss.ref import fusion_loss_ref_grads

RNG = np.random.default_rng(7)

# (M, T, V, bt, bv): divisible tiles, and tiles that divide neither T nor V
SHAPES = [
    (2, 16, 32, 8, 16),
    (3, 10, 13, 8, 8),
]
AVAIL_KINDS = ["full", "random", "empty_rows", "modality_out"]


def _avail(kind: str, M: int, T: int) -> jnp.ndarray:
    if kind == "full":
        a = np.ones((M, T))
    elif kind == "random":
        a = RNG.integers(0, 2, (M, T)).astype(float)
    elif kind == "empty_rows":
        a = RNG.integers(0, 2, (M, T)).astype(float)
        a[:, :3] = 0.0              # tokens with *no* modality available
    else:                           # modality_out: one head entirely absent
        a = np.ones((M, T))
        a[-1] = 0.0
    return jnp.asarray(a, jnp.float32)


def _case(M, T, V):
    logits = jnp.asarray(RNG.normal(size=(M, T, V)) * 3, jnp.float32)
    labels = jnp.asarray(RNG.integers(0, V, T), jnp.int32)
    cf = jnp.asarray(RNG.normal(size=T), jnp.float32)        # d_fused
    cm = jnp.asarray(RNG.normal(size=(M, T)), jnp.float32)   # d_modal
    return logits, labels, cf, cm


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M,T,V,bt,bv", SHAPES)
@pytest.mark.parametrize("kind", AVAIL_KINDS)
def test_vjp_dlogits_vs_f64_ref(M, T, V, bt, bv, kind):
    """jax.grad through the kernel == float64 oracle for every mask shape."""
    logits, labels, cf, cm = _case(M, T, V)
    avail = _avail(kind, M, T)

    def scalar(lg):
        f, m = kops.fusion_loss(lg, labels, avail, block_t=bt, block_v=bv,
                                interpret=True)
        return (f * cf).sum() + (m * cm).sum()

    dl = jax.jit(jax.grad(scalar))(logits)
    with jax.enable_x64(True):
        d_ref, _, _ = fusion_loss_ref_grads(logits, labels, avail, cf, cm)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(d_ref),
                               rtol=1e-4, atol=2e-5)
    # avail-masked (modality, token) slots must be *exactly* zero
    hole = np.asarray(avail)[..., None] == 0.0
    assert np.all(np.asarray(dl)[np.broadcast_to(hole, dl.shape)] == 0.0)


@pytest.mark.parametrize("M,T,V,bt,bv", SHAPES)
def test_vjp_zero_cotangent_rows_exactly_zero(M, T, V, bt, bv):
    """Sample-mask padding reaches the kernel as zero cotangents — rows with
    zero cotangent must produce bitwise-zero dlogits columns."""
    logits, labels, cf, cm = _case(M, T, V)
    pad = np.zeros(T, bool)
    pad[T // 2:] = True
    cf = cf * jnp.asarray(~pad, jnp.float32)
    cm = cm * jnp.asarray(~pad, jnp.float32)[None]

    def scalar(lg):
        f, m = kops.fusion_loss(lg, labels, block_t=bt, block_v=bv,
                                interpret=True)
        return (f * cf).sum() + (m * cm).sum()

    dl = np.asarray(jax.grad(scalar)(logits))
    assert np.all(dl[:, pad, :] == 0.0)
    assert np.any(dl[:, ~pad, :] != 0.0)


@pytest.mark.parametrize("M,T,V,bt,bv", SHAPES)
@pytest.mark.parametrize("kind", ["random", "empty_rows"])
def test_fused_partials_gsq_gdot(M, T, V, bt, bv, kind):
    """The backward's tile-accumulated ζ/δ partials match the f64 oracle."""
    logits, labels, cf, cm = _case(M, T, V)
    avail = _avail(kind, M, T)
    dl, gsq, gdot = kops.fusion_loss_grads(logits, labels, avail, cf, cm,
                                           block_t=bt, block_v=bv,
                                           interpret=True)
    with jax.enable_x64(True):
        d_ref, gsq_ref, gdot_ref = fusion_loss_ref_grads(
            logits, labels, avail, cf, cm)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(d_ref),
                               rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gsq), np.asarray(gsq_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gdot), np.asarray(gdot_ref),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
def test_front_end_fwd_and_grad_vs_core_fusion():
    """Dict front-end (broadcast head + scalar avail + sample mask) agrees
    with core.fusion.multimodal_loss in value and gradient."""
    B, S, V = 2, 6, 48
    lg = {"text": jnp.asarray(RNG.normal(size=(B, S, V)), jnp.float32),
          "vision": jnp.asarray(RNG.normal(size=(B, 1, V)), jnp.float32)}
    y = jnp.asarray(RNG.integers(0, V, (B, S)), jnp.int32)
    smask = jnp.asarray(RNG.integers(0, 2, (B, S)), jnp.float32)
    vw = {"text": 4.0, "vision": 1.5}
    av = {"text": jnp.float32(1.0), "vision": jnp.float32(1.0)}

    def tot_k(lg):
        t, met = kops.fused_multimodal_loss(lg, y, vw, avail=av,
                                            sample_mask=smask, block_t=4,
                                            block_v=16, interpret=True)
        return t, met

    def tot_c(lg):
        t, met = core_fusion.multimodal_loss(lg, y, vw, avail=av,
                                             sample_mask=smask)
        return t, met

    (t_k, met_k) = tot_k(lg)
    (t_c, met_c) = tot_c(lg)
    np.testing.assert_allclose(float(t_k), float(t_c), rtol=1e-5)
    for key in ("F", "G", "G_text", "G_vision"):
        np.testing.assert_allclose(float(met_k[key]), float(met_c[key]),
                                   rtol=1e-5, atol=1e-6)
    g_k = jax.grad(lambda p: tot_k(p)[0])(lg)
    g_c = jax.grad(lambda p: tot_c(p)[0])(lg)
    for m in lg:
        assert g_k[m].shape == lg[m].shape
        np.testing.assert_allclose(np.asarray(g_k[m]), np.asarray(g_c[m]),
                                   rtol=1e-4, atol=1e-6)


def test_front_end_unavailable_modality_zero_grad():
    """A client without a modality (scalar avail 0) gets exactly zero
    gradient for that head under the cohort-style vmap."""
    B, S, V = 2, 4, 32
    lg = {"audio": jnp.asarray(RNG.normal(size=(B, S, V)), jnp.float32),
          "image": jnp.asarray(RNG.normal(size=(B, S, V)), jnp.float32)}
    y = jnp.asarray(RNG.integers(0, V, (B, S)), jnp.int32)
    av = {"audio": jnp.float32(1.0), "image": jnp.float32(0.0)}

    g = jax.grad(lambda p: kops.fused_multimodal_loss(
        p, y, avail=av, block_t=4, block_v=16, interpret=True)[0])(lg)
    assert np.all(np.asarray(g["image"]) == 0.0)
    assert np.any(np.asarray(g["audio"]) != 0.0)


# ---------------------------------------------------------------------------
def test_tracker_gram_matches_cohort_diff():
    """Gram-form refresh == direct-difference refresh on the same cohort."""
    J, K = 4, 8
    tree = {"w": jnp.asarray(RNG.normal(size=(J, 5, 3)), jnp.float32),
            "b": jnp.asarray(RNG.normal(size=(J, 7)), jnp.float32)}
    mask_c = jnp.asarray([True, True, True, False])
    w_c = jnp.asarray([0.5, 0.3, 0.2, 0.0], jnp.float32)
    tree = jax.tree.map(lambda x: x * mask_c.reshape(
        (J,) + (1,) * (x.ndim - 1)), tree)   # padding slots carry zeros
    agg = jax.tree.map(lambda x: jnp.tensordot(w_c, x, axes=1), tree)
    idx = jnp.asarray([1, 3, 4, 6])
    has = jnp.ones(K, bool)
    z0 = jnp.float32(0.7)
    d0 = jnp.linspace(0.1, 0.9, K).astype(jnp.float32)

    za, da = tracker_update_cohort(z0, d0, tree, agg, mask_c, idx, has, 0.5)
    zb, db = tracker_update_gram(z0, d0, grad_gram(tree), w_c, mask_c, idx,
                                 has, 0.5)
    np.testing.assert_allclose(float(za), float(zb), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(da), np.asarray(db),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
def test_fused_round_engine_pallas_equivalence():
    """engine='fused:pallas' reproduces engine='fused' — params, energy
    queues and ζ/δ trackers — over a multi-round scan at f32 tolerance."""
    from repro.fl.runtime import MFLExperiment

    def run(engine):
        exp = MFLExperiment(dataset="crema_d", scheduler="jcsba", K=6,
                            n_samples=120, seed=3, engine=engine,
                            eval_every=10)
        for _ in range(2):
            exp.run_round()
        return exp

    a, b = run("fused"), run("fused:pallas")
    for x, y in zip(jax.tree.leaves(a.global_params),
                    jax.tree.leaves(b.global_params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    np.testing.assert_allclose(a.queues.Q, b.queues.Q, atol=1e-4)
    for m in a.bound.zeta:
        assert abs(a.bound.zeta[m] - b.bound.zeta[m]) < 1e-3
        np.testing.assert_allclose(a.bound.delta[m], b.bound.delta[m],
                                   atol=1e-4)
