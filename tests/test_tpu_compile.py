"""Compile the Pallas kernels for a TPU v5e without a chip attached.

Interpret mode accepts block shapes and layouts that the TPU's compiler
(Mosaic) refuses, so every kernel of the main path is lowered here with
``interpret=False`` for a described ``v5e:2x2`` topology and compiled; the
compiled text must hold the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU's library, and the test
workers all import this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.fusion_loss import ops as fusion_ops
from repro.kernels.ssd_scan.ops import ssd_forward


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _fusion_fwd(logits, labels):
    return fusion_ops.fusion_loss(logits, labels, interpret=False)


def _fusion_grad(logits, labels):
    def total(lg):
        f, m = fusion_ops.fusion_loss(lg, labels, interpret=False)
        return f.sum() + m.sum()
    return jax.grad(total)(logits)


def _cohort_grad(audio, image, labels, avail, smask):
    """The cohort BGD's loss call: per-client [T, C] logits under a J-way
    vmap, scalar per-modality availability, sample mask."""
    def one(a, i, y, av, sm):
        total, _ = fusion_ops.fused_multimodal_loss(
            {"audio": a, "image": i}, y, {"audio": 6.0, "image": 1.0},
            avail={"audio": av[0], "image": av[1]}, sample_mask=sm,
            interpret=False)
        return total
    return jax.vmap(jax.grad(one, argnums=(0, 1)))(audio, image, labels,
                                                     avail, smask)


def _ssd(x, dt, A, Bm, Cm, *, chunk):
    return ssd_forward(x, dt, A, Bm, Cm, chunk, interpret=False)


def _attention(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


def _ssd_shapes(B, S, nh, hp, N):
    return [(B, S, nh, hp), (B, S, nh), (nh,), (B, S, N), (B, S, N)]


F32, I32 = jnp.float32, jnp.int32
J, T, C = 10, 8, 6              # crema_d cohort: J clients, T samples, C classes
CASES = {
    "fusion_fwd_2x1024x8192": (_fusion_fwd, [(2, 1024, 8192), (1024,)],
                               [F32, I32]),
    "fusion_grad_2x1024x8192": (_fusion_grad, [(2, 1024, 8192), (1024,)],
                                [F32, I32]),
    "fusion_cohort_grad_crema_d": (
        _cohort_grad, [(J, T, C), (J, T, C), (J, T), (J, 2), (J, T)],
        [F32, F32, I32, F32, F32]),
    # the FL ssd encoder: S=32, d_inner 64 = 8 heads x 8, N=16, chunk 8
    "ssd_encoder": (functools.partial(_ssd, chunk=8),
                    _ssd_shapes(16, 32, 8, 8, 16), [F32] * 5),
    "ssd_S1024_hp64_N128": (functools.partial(_ssd, chunk=128),
                            _ssd_shapes(1, 1024, 8, 64, 128), [F32] * 5),
    # the FL transformer encoder: S=32, 4 heads of 8
    "flash_attention_hd8": (_attention, [(16, 32, 4, 8)] * 3, [F32] * 3),
    "flash_attention_hd128": (_attention, [(1, 1024, 8, 128)] * 3,
                              [F32] * 3),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes, dtypes = CASES[case]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in zip(shapes, dtypes)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
