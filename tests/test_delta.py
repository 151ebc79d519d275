"""`ops.delta.kda_scan`, the chunked gated delta rule with a channel-wise
decay, against the recurrence stepped position by position
(`kda_reference` of `rule_inputs`, the whole window's at once): outputs
and the gradients of q, k, v, the gate (so g), β, `A_log` and `dt_bias` —
at windows that are no multiple of a chunk and shorter than one, at
decays no `e^−G` survives, at the rule's two plain corners, and two
windows of a batch apart.  The scan's inner part runs through the two
Pallas kernels here, interpreted (chunks of 8 and more): they are held
to the plain jnp form too, results and cotangents, and say that they
engaged."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from iotml.obs.metrics import default_registry
from iotml.ops import delta
from iotml.ops.delta import kda_reference, kda_scan

#: a gate whose softplus is 0 in float32: α = 1, nothing decays
NO_DECAY = -200.0
NAMES = ("q", "k", "v", "f", "beta", "a_log", "dt_bias")


def _gate(g):
    """The f that `rule_inputs` turns into the log-decay g < 0 under
    `a_log` = 0 and `dt_bias` = 0: softplus's inverse of −g."""
    return jnp.log(jnp.expm1(-jnp.asarray(g, jnp.float32)))


def _operands(T, B=2, H=2, K=8, V=6, seed=0, decay=0.1):
    """q and k as a mixer makes them (no norm yet), a gate whose g is
    −`decay` · [0.1, 2] a channel under `a_log` = 0 and `dt_bias` = 0,
    β in (0, 1)."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.normal(size=(B, T, H, K)), rng.normal(size=(B, T, H, K)),
        rng.normal(size=(B, T, H, V)),
        _gate(-decay * rng.uniform(0.1, 2.0, size=(B, T, H, K))),
        rng.uniform(0.0, 1.0, size=(B, T, H)),
        np.zeros(H), np.zeros((H, K))))


def _stepped(q, k, v, f, beta, a_log, dt_bias):
    q, k, g = delta.rule_inputs(q, k, f, a_log, dt_bias)
    return kda_reference(q, k, v, g, beta)


def _both(operands, chunk):
    """((o, gradients) chunked, (o, gradients) stepped) under one
    seeded weighting of the outputs."""
    w = jnp.asarray(np.random.default_rng(9).normal(
        size=operands[2].shape), jnp.float32)
    out = []
    with jax.default_matmul_precision("highest"):
        for rule in (lambda *a: kda_scan(*a, chunk), _stepped):
            out.append(jax.jit(jax.value_and_grad(
                lambda *a: (lambda o: (jnp.sum(w * o), o))(rule(*a)),
                argnums=range(7), has_aux=True))(*operands))
    return [(o, grads) for (_, o), grads in out]


def _close(got, want, rtol=2e-5, floor=1e-30):
    scale = max(float(jnp.abs(want).max()), floor)
    assert np.isfinite(np.asarray(got)).all()
    assert float(jnp.abs(got - want).max()) <= rtol * scale


@pytest.mark.parametrize("T,chunk", [
    (100, 16), (100, 64),   # no multiple of the chunk, nor of a segment's
    (10, 16), (10, 64),     # shorter than one chunk
    (300, 16),              # two segments of sixteen chunks, the last short
    (40, 8),                # a chunk under a sub-block
])
def test_the_chunked_rule_is_the_stepped_recurrence(T, chunk):
    rng = np.random.default_rng(T + chunk)
    operands = _operands(T)[:5] + tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.uniform(-0.5, 1.0, size=2), rng.normal(size=(2, 8))))
    (o, grads), (want, wants) = _both(operands, chunk)
    _close(o, want)
    for name, g, w in zip(NAMES, grads, wants):
        _close(g, w), name


def test_decays_no_inverse_survives_stay_finite_and_exact():
    """g = −2 a position and channel over chunks of 64: G reaches −128
    inside a chunk, `e^−G` is 4e55 — outputs and every gradient are
    finite and the recurrence's."""
    q, k, v, f, *rest = _operands(130)
    (o, grads), (want, wants) = _both(
        (q, k, v, _gate(jnp.full_like(f, -2.0)), *rest), 64)
    assert float(jnp.abs(want).max()) > 0.03
    _close(o, want)
    for got, w in zip(grads, wants):
        _close(got, w)


def test_without_writes_the_state_only_decays():
    """β = 0 from the window's middle on: nothing is removed and nothing
    written — what the first half left fades a channel at a time, and
    where the decay is 1 as well one query reads one answer for good;
    β = 0 throughout: the state stays zero."""
    T = 48
    q, k, v, f, beta, *gate = _operands(T, decay=0.05)
    beta = beta.at[:, T // 2:].set(0.0)
    (o, grads), (want, wants) = _both((q, k, v, f, beta, *gate), 16)
    _close(o, want)
    for got, w in zip(grads, wants):
        _close(got, w)
    still = (jnp.broadcast_to(q[:, :1], q.shape), k, v,
             f.at[:, T // 2:].set(NO_DECAY), beta, *gate)
    with jax.default_matmul_precision("highest"):
        held = kda_scan(*still, 16)[:, T // 2:]
        never = kda_scan(q, k, v, f, jnp.zeros_like(beta), *gate, 16)
    assert float(jnp.abs(held[:, :1]).max()) > 1e-3
    _close(held, jnp.broadcast_to(held[:, :1], held.shape), rtol=1e-5)
    assert not np.asarray(never).any()


def test_the_plain_delta_rule_reads_back_what_it_wrote():
    """α = 1 and β = 1: the state forgets nothing but what it overwrites,
    so a key reads its own value back — `S_tᵀ k_t = v_t`, and the query
    that is that key, scaled by K^-½ as queries are, K^-½ of it."""
    q, k, v, f, beta, *gate = _operands(70)
    (o, grads), (want, wants) = _both(
        (k, k, v, jnp.full_like(f, NO_DECAY), jnp.ones_like(beta), *gate), 32)
    _close(o, want)
    _close(o, v * k.shape[-1] ** -0.5, rtol=1e-4)
    # whatever the decay, a key reads its value back: g's gradient is 0
    for got, w in zip(grads, wants):
        _close(got, w, floor=1.0)


def test_two_windows_of_a_batch_do_not_leak():
    """A batch's windows are walked by ONE scan, the state zeroed where
    a window starts: each is what it is alone, and no gradient crosses."""
    operands = _operands(40, B=3)
    with jax.default_matmul_precision("highest"):
        whole = kda_scan(*operands, 16)
        for b in range(3):
            alone = kda_scan(*(a[b:b + 1] for a in operands[:5]),
                             *operands[5:], 16)
            _close(whole[b:b + 1], alone, rtol=1e-6)
        crossed = jax.grad(lambda v: jnp.sum(kda_scan(
            *operands[:2], v, *operands[3:], 16)[1]))(operands[2])
    assert np.asarray(crossed[1]).any()
    assert not np.asarray(crossed[0]).any() \
        and not np.asarray(crossed[2]).any()


def test_the_rule_reads_normed_keys_scaled_queries_and_a_softplus_gate():
    """`rule_inputs`, which the scan applies a segment at a time: unit
    keys, queries of length K^-½, and `g = −exp(A_log) · softplus(f +
    dt_bias)` a head and channel — written out here."""
    q, k, _, f, _, _, _ = _operands(20)
    rng = np.random.default_rng(3)
    a_log, dt_bias = rng.uniform(-0.5, 1.0, size=2), rng.normal(size=(2, 8))
    got = delta.rule_inputs(q, k, f, jnp.asarray(a_log, jnp.float32),
                            jnp.asarray(dt_bias, jnp.float32))
    q64, k64, f64 = (np.asarray(a, np.float64) for a in (q, k, f))
    want = (q64 / np.sqrt((q64 ** 2).sum(-1, keepdims=True) + 1e-6) / 8 ** .5,
            k64 / np.sqrt((k64 ** 2).sum(-1, keepdims=True) + 1e-6),
            -np.exp(a_log)[:, None] * np.logaddexp(0.0, f64 + dt_bias))
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), w, rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("C,sub", [(64, 16), (32, 16), (16, 16), (8, 8)])
def test_the_inverse_in_blocks_is_the_inverse(C, sub):
    rng = np.random.default_rng(C)
    a = np.tril(rng.normal(size=(5, C, C)), -1).astype(np.float32)
    got = delta.unit_lower_inverse(jnp.moveaxis(jnp.asarray(a), 0, 2), sub)
    want = np.linalg.inv(np.eye(C) + a.astype(np.float64))
    np.testing.assert_allclose(np.moveaxis(np.asarray(got), 2, 0), want,
                               rtol=0, atol=2e-4 * np.abs(want).max())


def _inner_operands(b, n, C, h, K, V, g=None, seed=0):
    """What the inner part reads of a segment of n chunks: unit keys,
    queries of length K^-½, G summed from each chunk's start (of `g` a
    position, or −0.1 · [0.1, 2] a channel), β in (0, 1)."""
    rng = np.random.default_rng(seed)
    q, k = (a / np.linalg.norm(a, axis=-1, keepdims=True)
            for a in rng.normal(size=(2, b, n, C, h, K)))
    decay = -0.1 * rng.uniform(0.1, 2.0, size=(b, n, C, h, K)) \
        if g is None else np.full((b, n, C, h, K), g)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        q * K ** -0.5, k, np.cumsum(decay, axis=2),
        rng.normal(size=(b, n, C, h, V)),
        rng.uniform(0.0, 1.0, size=(b, n, C, h))))


def _inner_kernels(q, k, G, v, beta, heads):
    """`kda_intra` of a segment as the scan hands it over."""
    b, n, C, h, K = k.shape
    L = n * C
    geom = delta.intra_geometry(L, h, K, v.shape[-1], C, True)
    if heads:
        geom = geom._replace(heads=heads)
    w, u, qk = delta.kda_intra(
        *(a.reshape(b, L, -1) for a in (q, k, G, v)), beta.reshape(b, L, h),
        C, geom)
    return w.reshape(k.shape), u.reshape(v.shape), qk


def _inner_both(operands, heads):
    """((w, u, qk), cotangents of q, k, G, v, β) by the kernels and by
    the plain form, under one seeded weighting of the three results."""
    C = operands[1].shape[2]
    out = []
    with jax.default_matmul_precision("highest"):
        for inner in (lambda *a: _inner_kernels(*a, heads),
                      lambda *a: delta.intra_plain(*a, min(delta.SUB, C))):
            shapes = jax.eval_shape(inner, *operands)
            ws = [jnp.asarray(np.random.default_rng(7 + i).normal(
                size=s.shape), jnp.float32) for i, s in enumerate(shapes)]
            (_, got), grads = jax.jit(jax.value_and_grad(
                lambda *a: (lambda o: (sum(jnp.sum(w * r) for w, r in
                                           zip(ws, o)), o))(inner(*a)),
                argnums=range(5), has_aux=True))(*operands)
            out.append(tuple(got) + tuple(grads))
    return out


@pytest.mark.parametrize("b,n,C,h,heads,g", [
    (1, 2, 64, 2, None, None),   # the cell's chunk: two chunks a step
    (1, 3, 64, 4, 2, None),      # an odd segment (T no multiple of two
                                 # chunks): a chunk a step, two head groups
    (2, 7, 16, 2, 1, None),      # two windows, seven chunks, a head a step
    (2, 16, 8, 2, None, None),   # a chunk of one sub-block of 8: no merge
    (1, 4, 32, 3, 3, None),      # one merge; three heads a step
    (1, 2, 64, 2, 1, -2.0),      # G reaches −128 inside a chunk
])
def test_the_kernels_are_the_plain_inner_part(b, n, C, h, heads, g):
    """`iotml_kda_intra_fwd` and `iotml_kda_intra_bwd`, interpreted,
    against `intra_plain`: W, U, the queries' scores and the cotangents
    of q, k, G, v and β, all finite."""
    got, want = _inner_both(_inner_operands(b, n, C, h, 8, 6, g, seed=n + C),
                            heads)
    if g is not None:
        # no e^−G survives; the scores between far sub-blocks vanish
        assert float(jnp.abs(want[2]).max()) > 1e-3
    for name, a, w in zip(("w", "u", "qk", "dq", "dk", "dG", "dv", "dbeta"),
                          got, want):
        _close(a, w), name


@pytest.mark.parametrize("L,H,K,V,chunk,interpret,want", [
    (1024, 32, 128, 128, 64, False, (128, 8)),   # `kl-train-backlog`
    (1024, 4, 128, 128, 64, False, (128, 4)),    # few heads: all a step
    (1024, 32, 128, 128, 4, False, None),        # under the sublane tile
    (1024, 32, 128, 128, 256, False, None),      # over a step's lanes
    (1024, 32, 64, 128, 64, False, None),        # heads of half a tile
    (192, 32, 128, 128, 64, False, None),        # three chunks: no 128 rows
    (192, 2, 8, 6, 64, True, (64, 2)),           # … which interpreted run
    (112, 2, 8, 6, 16, True, (112, 2)),
])
def test_the_kernels_tiling_is_derived_from_the_shapes(L, H, K, V, chunk,
                                                       interpret, want):
    got = delta.intra_geometry(L, H, K, V, chunk, interpret)
    assert (got and tuple(got)) == want


def test_a_call_says_its_chunks_and_the_states_it_keeps():
    jax.clear_caches()
    operands = _operands(300, B=2, H=2, K=8, V=6)
    jax.grad(lambda *a: jnp.sum(kda_scan(*a, 16)))(*operands)
    said = default_registry.collect()
    # a segment's sixteen chunks of two heads, a call of either kernel
    assert said['iotml_kda_intra_kernel{direction="fwd"}'] == 32
    assert said['iotml_kda_intra_kernel{direction="bwd"}'] == 32
    kda_scan(*_operands(40), 4)        # a chunk under the sublane tile
    said = default_registry.collect()
    assert said['iotml_kda_intra_kernel{direction="fwd"}'] == 0
    assert said['iotml_kda_intra_kernel{direction="bwd"}'] == 0
    kda_scan(*operands, 16)
    said = default_registry.collect()
    assert said["iotml_kda_chunk_size"] == 16
    assert said["iotml_kda_chunks"] == 19          # ⌈300 / 16⌉
    # two segments of sixteen chunks a window: the state entering each
    assert said["iotml_kda_state_bytes"] == 2 * 2 * 2 * 8 * 6 * 4
    with pytest.raises(ValueError, match="power of two"):
        kda_scan(*_operands(40), 24)
