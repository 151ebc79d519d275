"""SensorFormer + sequence-parallel training on the virtual 8-device mesh:
the sharded path must match the single-device dense oracle exactly."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from iotml.models.transformer import SensorFormer
from iotml.parallel.mesh import make_mesh
from iotml.parallel.seq_parallel import (make_sp_train_step,
                                         sp_next_step_loss_reference)


def _x(B=4, T=32, F=18, seed=0):
    return np.random.default_rng(seed).normal(size=(B, T, F)).astype(np.float32)


def test_sensorformer_forward_shapes():
    m = SensorFormer(features=18, d_model=32, num_heads=2, num_layers=2)
    x = jnp.asarray(_x())
    params = m.init(jax.random.PRNGKey(0), x)["params"]
    out = m.apply({"params": params}, x)
    assert out.shape == x.shape
    scores = SensorFormer.anomaly_scores(out, x)
    assert scores.shape == (4, 31)


def test_sensorformer_flash_interpret_matches_dense():
    dense = SensorFormer(features=18, d_model=32, num_heads=2, num_layers=1)
    flash = SensorFormer(features=18, d_model=32, num_heads=2, num_layers=1,
                         attn_mode="flash_interpret")
    x = jnp.asarray(_x(T=40))
    params = dense.init(jax.random.PRNGKey(1), x)["params"]
    np.testing.assert_allclose(
        np.asarray(dense.apply({"params": params}, x)),
        np.asarray(flash.apply({"params": params}, x)),
        rtol=2e-4, atol=2e-4)


def test_sp_train_step_matches_dense_oracle():
    mesh = make_mesh((2, 4), ("data", "seq"))
    model = SensorFormer(features=18, d_model=32, num_heads=2, num_layers=2,
                         attn_mode="ring", ring_axis="seq")
    tx = optax.adam(1e-3)
    init, step, put_x = make_sp_train_step(model, tx, mesh)

    x = _x(B=4, T=32)
    state = init(jax.random.PRNGKey(0), x)
    params0 = jax.device_get(state.params)

    # oracle loss with the same params, dense attention, single device
    dense = model.clone(attn_mode="dense")
    want = float(sp_next_step_loss_reference(dense, params0, jnp.asarray(x)))

    state, metrics = step(state, put_x(x))
    got = float(metrics["loss"])
    assert got == pytest.approx(want, rel=1e-5)

    # gradients flowed: params changed, loss drops over a few steps
    losses = [got]
    for _ in range(5):
        state, metrics = step(state, put_x(x))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_sp_gradients_match_dense_oracle():
    """With SGD the param delta is -lr*grad, so comparing post-step params
    compares the sharded gradients themselves against the dense oracle's.
    (Adam's first step is -lr*sign(g) — scale-free — which would amplify
    float noise in near-zero grads into full-size deltas.)"""
    mesh = make_mesh((2, 4), ("data", "seq"))
    model = SensorFormer(features=18, d_model=32, num_heads=2, num_layers=1,
                         attn_mode="ring", ring_axis="seq")
    tx = optax.sgd(0.1)
    init, step, put_x = make_sp_train_step(model, tx, mesh)
    x = _x(B=4, T=32, seed=5)
    state = init(jax.random.PRNGKey(2), x)
    params0 = jax.device_get(state.params)

    dense = model.clone(attn_mode="dense")
    ref_grads = jax.grad(
        lambda p: sp_next_step_loss_reference(dense, p, jnp.asarray(x)))(params0)
    want = jax.tree.map(lambda p, g: p - 0.1 * np.asarray(g),
                        params0, jax.device_get(ref_grads))
    state, _ = step(state, put_x(x))
    got = jax.device_get(state.params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_qkv_projection_keeps_dense_generals_parameters_and_values():
    """`attn/qkv` is read by the benchmark's reference, `mlops/
    checkpoint.py` and `models/h5_*` as a `DenseGeneral((3, H, D))`'s
    tree: same names, shapes and initial values, and the three products
    are that layer's three slices."""
    import flax.linen as nn
    from iotml.models.transformer import MultiHeadAttention, QKVProjection

    H, D = 4, 8
    x = jnp.asarray(_x(B=2, T=16))[..., :12]
    key = jax.random.PRNGKey(5)
    one = nn.DenseGeneral((3, H, D))
    want = one.init(key, x)["params"]
    got = QKVProjection(H, D).init(key, x)["params"]
    assert jax.tree.map(jnp.shape, got) == jax.tree.map(jnp.shape, want) \
        == {"kernel": (12, 3, H, D), "bias": (3, H, D)}
    np.testing.assert_array_equal(got["kernel"], want["kernel"])
    got["bias"] = want["bias"] = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, H, D)), jnp.float32)
    qkv = one.apply({"params": want}, x)
    for i, part in enumerate(QKVProjection(H, D).apply({"params": got}, x)):
        np.testing.assert_allclose(part, qkv[:, :, i], rtol=1e-6, atol=1e-6)
    tree = MultiHeadAttention(H * D, H).init(key, jnp.zeros((1, 4, H * D)))
    assert jax.tree.map(jnp.shape, tree["params"]) == {
        "qkv": {"kernel": (H * D, 3, H, D), "bias": (3, H, D)},
        "out": {"kernel": (H, D, H * D), "bias": (H * D,)}}


def test_tracing_a_flash_model_records_the_kernels_geometry():
    """`iotml_flash_grid_steps{kernel}` and its neighbours say what each
    kernel's last compiled call engaged: one value a kernel, the grid
    the rule implies."""
    from iotml.obs.metrics import default_registry
    from iotml.ops import attention

    jax.clear_caches()   # recorded when a shape is traced, not on a hit
    B, T, H, D = 2, 300, 2, 16
    flash = SensorFormer(features=18, d_model=H * D, num_heads=H,
                         num_layers=1, attn_mode="flash_interpret")
    x = jnp.asarray(_x(B=B, T=T))
    params = flash.init(jax.random.PRNGKey(2), x)["params"]
    jax.grad(lambda p: jnp.sum(flash.apply({"params": p}, x)))(params)
    got = default_registry.collect()
    # the backward took the one kernel: its dQ column fits
    ran = ("fwd", "bwd_fused")
    assert got["iotml_flash_backward_fused"] == 1
    for kernel in ran:
        g = attention.flash_geometry(kernel, T, D, 4, B, H, True)
        for name, want in (("grid_steps", g.grid_steps),
                           ("block_q", g.block_q), ("block_k", g.block_k),
                           ("heads_per_step", g.heads),
                           ("lanes_per_step", g.heads * D),
                           ("operand_copies", 3 if kernel == "fwd" else 6)):
            assert got[f'iotml_flash_{name}{{kernel="{kernel}"}}'] == want
    # one series a kernel: the two-kernel form's only where an earlier
    # test of this process ran it
    said = {k for k in got if k.startswith("iotml_flash_grid_steps")}
    assert {f'iotml_flash_grid_steps{{kernel="{k}"}}' for k in ran} <= said \
        <= {f'iotml_flash_grid_steps{{kernel="{k}"}}'
            for k in attention.KERNELS}
