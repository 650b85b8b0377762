"""Allclose tests for the fused flash-decode Pallas kernel: plain sweep,
both cache layouts, sliding windows, partial-statistics mode (+ the
stats_merge algebra), and policy-selected accumulation dtypes."""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_partial,
                                            decode_attention_ref)
from repro.runtime import ExecPolicy


@pytest.mark.parametrize("b,h,hkv,d,smax,clen", [
    (2, 8, 8, 64, 512, 300),      # MHA
    (1, 8, 2, 64, 1024, 1024),    # GQA 4:1, full cache
    (2, 4, 1, 80, 640, 17),       # MQA, unaligned head dim, short ctx
    (1, 16, 4, 128, 512, 511),
])
def test_allclose_vs_ref(b, h, hkv, d, smax, clen):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, smax, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, smax, d), jnp.float32)
    out = decode_attention(q, kc, vc, clen, block_s=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_per_slot_cache_len_vector():
    """(B,) cache_len: each batch row is masked against its own length
    (the serving engine's ragged continuous-batching contract)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    b, h, hkv, d, smax = 4, 8, 4, 64, 768
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    kc = jax.random.normal(ks[1], (b, hkv, smax, d), jnp.float32)
    vc = jax.random.normal(ks[2], (b, hkv, smax, d), jnp.float32)
    clen = jnp.array([1, 255, 500, 768], jnp.int32)
    out = decode_attention(q, kc, vc, clen, block_s=256, interpret=True)
    ref = decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_bf16_cache():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 1, 4, 64), jnp.float32)
    kc = jax.random.normal(ks[1], (1, 2, 256, 64)).astype(jnp.bfloat16)
    vc = jax.random.normal(ks[2], (1, 2, 256, 64)).astype(jnp.bfloat16)
    out = decode_attention(q, kc, vc, 200, block_s=128, interpret=True)
    ref = decode_attention_ref(q, kc.astype(jnp.float32),
                               vc.astype(jnp.float32), 200)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def _rand_cache(seed, b, h, hkv, d, smax, layout="bhsd"):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    shape = (b, hkv, smax, d) if layout == "bhsd" else (b, smax, hkv, d)
    kc = jax.random.normal(ks[1], shape, jnp.float32)
    vc = jax.random.normal(ks[2], shape, jnp.float32)
    return q, kc, vc


def test_bshd_layout():
    """The sequence-major cache feeds the kernel through layout-aware
    index maps — no transpose, same numbers as head-major."""
    q, kc, vc = _rand_cache(3, 2, 8, 4, 64, 512)
    clen = jnp.array([77, 512], jnp.int32)
    ref = decode_attention(q, kc, vc, clen, block_s=128, interpret=True)
    out = decode_attention(q, kc.transpose(0, 2, 1, 3),
                           vc.transpose(0, 2, 1, 3), clen, layout="bshd",
                           block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("window,clen", [
    (1, 300), (64, 300), (127, 512), (128, 512), (512, 512), (700, 300),
])
def test_windowed_vs_ref(window, clen):
    """Sliding-window sweep == windowed reference reduction, including
    window == 1, block-straddling windows and window > cache_len."""
    q, kc, vc = _rand_cache(4, 2, 8, 4, 64, 512)
    cl = jnp.array([clen, max(1, clen - 37)], jnp.int32)
    out = decode_attention(q, kc, vc, cl, window=window, block_s=128,
                           interpret=True)
    ref = decode_attention_ref(q, kc, vc, cl, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


def test_partial_stats_merge_matches_full():
    """Manually split the cache into 4 slices, run each in
    partial-statistics mode with its seq_offset, fold with stats_merge
    (the pairwise rule) — the result must equal the one-shot kernel."""
    from repro.core.softmax import SoftmaxStats, stats_merge
    from repro.core.vexp import get_exp_fn
    b, h, hkv, d, smax = 2, 8, 4, 64, 512
    q, kc, vc = _rand_cache(5, b, h, hkv, d, smax)
    clen = jnp.array([1, 389], jnp.int32)
    full = decode_attention(q, kc, vc, clen, block_s=64, interpret=True)
    exp_fn = get_exp_fn("vexp")
    nsh, loc = 4, smax // 4
    stats, acc = None, None
    # fold in a deliberately shuffled order: the merge is commutative
    for i in (2, 0, 3, 1):
        m, l, a = decode_attention_partial(
            q, kc[:, :, i * loc:(i + 1) * loc],
            vc[:, :, i * loc:(i + 1) * loc], clen, i * loc,
            block_s=64, interpret=True)
        if stats is None:
            stats, acc = SoftmaxStats(m=m, l=l), a
        else:
            merged, aa, ab = stats_merge(stats, SoftmaxStats(m=m, l=l),
                                         exp_fn=exp_fn)
            acc = acc * aa + a * ab
            stats = merged
    out = (acc * (1.0 / jnp.maximum(stats.l, 1e-30))).reshape(b, 1, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=2e-3, rtol=2e-3)


def test_partial_empty_shard_is_merge_identity():
    """A slice entirely past cache_len returns (NEG_INF, 0, 0)."""
    q, kc, vc = _rand_cache(6, 1, 4, 2, 64, 256)
    m, l, acc = decode_attention_partial(
        q, kc, vc, jnp.array([100], jnp.int32), 512, block_s=128,
        interpret=True)
    assert float(jnp.max(m)) <= -1e29
    assert float(jnp.abs(l).max()) == 0.0
    assert float(jnp.abs(acc).max()) == 0.0


def test_accum_dtype_bf16_close_but_distinct():
    """accum_dtype="bfloat16" must actually change the compiled program
    (satellite: it used to be hashed into the jit key and ignored) while
    staying within bf16 round-off of the f32 accumulation."""
    q, kc, vc = _rand_cache(7, 2, 8, 4, 64, 512)
    clen = jnp.array([300, 512], jnp.int32)
    f32 = decode_attention(
        q, kc, vc, clen,
        policy=ExecPolicy(kernel_backend="pallas", block_s=128))
    bf16 = decode_attention(
        q, kc, vc, clen,
        policy=ExecPolicy(kernel_backend="pallas", block_s=128,
                          accum_dtype="bfloat16"))
    assert not np.array_equal(np.asarray(f32), np.asarray(bf16)), \
        "bfloat16 accumulation compiled an identical program to float32"
    np.testing.assert_allclose(np.asarray(bf16), np.asarray(f32),
                               atol=5e-2, rtol=5e-2)


# ------------------------------------------- lane-dense "bshd" (pool form)

def _lane_dense(x):
    """(B, S, Hkv, d) -> (B, S, Hkv*d): the serving pool's "bshd" form."""
    return x.reshape(*x.shape[:2], -1)


def _head_major(x):
    return x.transpose(0, 2, 1, 3)


# per-slot lengths: one token, a block edge (block_s 256), mid-block, full
_CLENS = jnp.array([1, 256, 701, 1024], jnp.int32)


@pytest.mark.parametrize("window", [None, 300])
@pytest.mark.parametrize("h,hkv,d", [
    (8, 8, 64),      # two heads per 128-lane block (gpt2-small's head dim)
    (8, 2, 64),      # GQA 4:1, two heads per block
    (8, 8, 32),      # four heads per block
    (16, 4, 32),     # GQA 4:1, four heads per block
    (4, 4, 128),     # one head per block: read as it is
    (8, 2, 128),     # GQA, one head per block
])
def test_lane_dense_bshd_vs_ref(h, hkv, d, window):
    """The kernel reads the (B, S, Hkv*d) pool unpadded, 128 // d heads
    per lane block with block-diagonal queries: equal to the reference,
    and to the lane-padded head-major path on the same inputs."""
    q, kc, vc = _rand_cache(11, 4, h, hkv, d, 1024, layout="bshd")
    ref = decode_attention_ref(q, kc, vc, _CLENS, window=window,
                               layout="bshd")
    out = decode_attention(q, _lane_dense(kc), _lane_dense(vc), _CLENS,
                           window=window, layout="bshd", block_s=256,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    padded = decode_attention(q, _head_major(kc), _head_major(vc), _CLENS,
                              window=window, block_s=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(padded),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("h,hkv,d", [
    (4, 1, 64),      # MQA: one head cannot fill a 128-lane block
    (6, 3, 64),      # three heads: the last block would be half a head pair
    (4, 2, 80),      # 80 does not divide 128
    (6, 2, 48),
])
def test_lane_dense_bshd_pad_path(h, hkv, d):
    """Shapes whose heads cannot fill whole 128-lane blocks take the
    explicit lane-padding path: same numbers from the lane-dense and the
    4-D view, equal to the reference and to the head-major path."""
    from repro.kernels.decode_attention.ops import _lane_heads
    q, kc, vc = _rand_cache(12, 4, h, hkv, d, 1024, layout="bshd")
    assert _lane_heads(q, _lane_dense(kc), "bshd") == 1
    ref = decode_attention_ref(q, kc, vc, _CLENS, layout="bshd")
    dense = decode_attention(q, _lane_dense(kc), _lane_dense(vc), _CLENS,
                             layout="bshd", block_s=256, interpret=True)
    four = decode_attention(q, kc, vc, _CLENS, layout="bshd", block_s=256,
                            interpret=True)
    padded = decode_attention(q, _head_major(kc), _head_major(vc), _CLENS,
                              block_s=256, interpret=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(four))
    np.testing.assert_allclose(np.asarray(dense), np.asarray(padded),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("d,hp", [(32, 4), (64, 2), (128, 1), (80, 0),
                                  (96, 0)])
def test_heads_per_block(d, hp):
    """Heads per 128-lane block follow from the head dim alone (with the
    head count filling whole blocks); 0 selects the padding path."""
    from repro.kernels.decode_attention.ops import _heads_per_block
    assert _heads_per_block(d, 8) == hp


def test_group_ungroup_roundtrip():
    """Block-diagonal query rows carry each head in its own lanes and
    zeros elsewhere; ``_ungroup`` recovers every head exactly."""
    from repro.kernels.decode_attention.ops import _group_q, _ungroup
    qg = jax.random.normal(jax.random.PRNGKey(13), (2, 8, 3, 32))
    grouped = _group_q(qg, 4)
    assert grouped.shape == (2, 2, 12, 128)
    rows = np.asarray(grouped).reshape(2, 2, 4, 3, 4, 32)
    for j in range(4):
        for i in range(4):
            if i != j:
                assert (rows[:, :, j, :, i] == 0).all()
            else:
                np.testing.assert_array_equal(
                    rows[:, :, j, :, i],
                    np.asarray(qg).reshape(2, 2, 4, 3, 32)[:, :, j])
    np.testing.assert_array_equal(np.asarray(_ungroup(grouped, 4, 32)),
                                  np.asarray(qg))


def test_lane_dense_partial_stats_merge_matches_full():
    """Partial-statistics mode over four sequence slices of the lane-dense
    pool, folded with stats_merge, equals the one-shot kernel: the
    grouped rows' (m, l, acc) map back to heads before the merge."""
    from repro.core.softmax import SoftmaxStats, stats_merge
    from repro.core.vexp import get_exp_fn
    b, h, hkv, d, smax = 4, 8, 4, 64, 1024
    q, kc, vc = _rand_cache(14, b, h, hkv, d, smax, layout="bshd")
    kd, vd = _lane_dense(kc), _lane_dense(vc)
    full = decode_attention(q, kd, vd, _CLENS, layout="bshd", block_s=128,
                            interpret=True)
    exp_fn = get_exp_fn("vexp")
    loc = smax // 4
    stats, acc = None, None
    for i in (3, 1, 0, 2):
        m, l, a = decode_attention_partial(
            q, kd[:, i * loc:(i + 1) * loc], vd[:, i * loc:(i + 1) * loc],
            _CLENS, i * loc, layout="bshd", block_s=128, interpret=True)
        assert m.shape == (b, hkv, h // hkv, 1) and a.shape[-1] == d
        if stats is None:
            stats, acc = SoftmaxStats(m=m, l=l), a
        else:
            merged, aa, ab = stats_merge(stats, SoftmaxStats(m=m, l=l),
                                         exp_fn=exp_fn)
            acc = acc * aa + a * ab
            stats = merged
    out = (acc * (1.0 / jnp.maximum(stats.l, 1e-30))).reshape(b, 1, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=2e-3, rtol=2e-3)


# ------------------------------------- the stacked pool, read by layer index

@pytest.mark.parametrize("window", [None, 200])
@pytest.mark.parametrize("layout,d,smax", [
    ("bshd", 64, 512),     # two heads per lane block, read where it lies
    ("bshd", 128, 512),    # one head per lane block, read where it lies
    ("bhsd", 128, 512),    # head-major, lanes already whole
    ("bhsd", 64, 512),     # head-major, lane-padded: the layer is sliced
    ("bshd", 64, 384),     # S not a multiple of the block: sliced
])
def test_stacked_pool_layer_index_equals_layer_slice(layout, d, smax,
                                                     window):
    """The kernel over a stacked (L, ...) pool at layer ``l`` equals the
    same kernel on ``pool[l]`` for every ``l``, bit for bit: the index map
    selects the same blocks that a slice would hand it."""
    n_layers, b, h, hkv = 3, 4, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    one = (b, smax, hkv * d) if layout == "bshd" else (b, hkv, smax, d)
    kp = jax.random.normal(ks[1], (n_layers,) + one).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (n_layers,) + one).astype(jnp.bfloat16)
    clens = jnp.array([1, 256, 300, smax], jnp.int32)
    kw = dict(window=window, layout=layout, block_s=256, interpret=True)
    for l in range(n_layers):
        stacked = decode_attention(q, kp, vp, clens, layer=jnp.int32(l),
                                   **kw)
        sliced = decode_attention(q, kp[l], vp[l], clens, **kw)
        np.testing.assert_array_equal(np.asarray(stacked),
                                      np.asarray(sliced))
    ref = decode_attention_ref(q, kp[1].astype(jnp.float32),
                               vp[1].astype(jnp.float32), clens,
                               window=window, layout=layout)
    out = decode_attention(q, kp, vp, clens, layer=jnp.int32(1), **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("arch,layout", [("gpt2-small", "bshd"),
                                         ("gpt2-small", "bhsd"),
                                         ("h2o-danube3-4b", "bshd")])
def test_decode_step_parked_rows_leave_pool_untouched(arch, layout):
    """A ``decode_step`` over the carried pool writes each live row's
    token at its position in every layer and nothing else: rows with
    ``live == 0`` keep every pool row bit-untouched (the window ring's
    branch included)."""
    from repro.configs import get_config
    from repro.models import api
    from repro.runtime import resolve_policy
    cfg = dataclasses.replace(get_config(arch).reduced(),
                              kv_cache_layout=layout)
    pol = resolve_policy(cfg, env={}, kernel_backend="pallas",
                         exp_backend="vexp")
    b, s = 4, 32
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    cache = api.init_cache(cfg, b, s)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    cache = {n: jax.random.normal(k, c.shape).astype(c.dtype)
             for (n, c), k in zip(sorted(cache.items()), ks)}
    pos = jnp.array([3, 5, 20, 9], jnp.int32)
    live = jnp.array([1, 0, 1, 0], jnp.int32)
    tok = jnp.array([[7], [8], [9], [10]], jnp.int32)
    _, out = jax.jit(lambda p, c: api.decode_step(
        p, cfg, tok, c, pos, policy=pol, live=live))(params, cache)
    s_ax = 2 if cfg.kv_cache_layout == "bshd" else 3
    wpos = pos % cfg.sliding_window if cfg.sliding_window else pos
    for name in ("k", "v"):
        before = np.asarray(jnp.moveaxis(cache[name], s_ax, 2)
                            .astype(jnp.float32))
        after = np.asarray(jnp.moveaxis(out[name], s_ax, 2)
                           .astype(jnp.float32))
        for row in range(b):
            if live[row]:
                written = np.zeros(after.shape[2], bool)
                written[int(wpos[row])] = True
                np.testing.assert_array_equal(after[:, row, ~written],
                                              before[:, row, ~written])
                assert (after[:, row, written]
                        != before[:, row, written]).any()
            else:
                np.testing.assert_array_equal(after[:, row], before[:, row])
