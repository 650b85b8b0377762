"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes and no NaNs; plus prefill/decode round-trips
for the families that serve."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.configs import REGISTRY, get_config
from repro.models import api

ARCHS = sorted(REGISTRY)


def _smoke_batch(cfg, key, b=2, s=32):
    ks = jax.random.split(key, 3)
    s_txt = s - cfg.n_vision_tokens if cfg.family == "vlm" else s
    batch = {
        "tokens": jax.random.randint(ks[0], (b, s_txt), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (b, s_txt), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["extra"] = jax.random.normal(
            ks[2], (b, cfg.n_vision_tokens, cfg.vision_embed_dim))
    if cfg.family == "audio":
        batch["extra"] = jax.random.normal(ks[2], (b, s, cfg.frame_input_dim))
        batch["labels"] = jax.random.randint(ks[1], (b, s), 0, cfg.vocab)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_smoke(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = api.init_params(cfg, key)
    batch = _smoke_batch(cfg, jax.random.PRNGKey(1))

    @jax.jit
    def step(params, batch):
        loss, grads = jax.value_and_grad(
            lambda p: api.loss_fn(p, cfg, batch))(params)
        new = jax.tree.map(lambda p, g: p - 1e-3 * g.astype(p.dtype),
                           params, grads)
        return loss, new

    loss, new_params = step(params, batch)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    # loss should be ~ln(vocab) for a random init
    assert 0.5 * np.log(cfg.vocab) < float(loss) < 3.0 * np.log(cfg.vocab)
    leaves = jax.tree.leaves(new_params)
    assert all(np.isfinite(np.asarray(l, np.float32)).all() for l in leaves), \
        f"{arch}: NaN in updated params"
    # a second step must reduce nothing structurally (shapes preserved)
    for a, b in zip(jax.tree.leaves(params), leaves):
        assert a.shape == b.shape


@pytest.mark.parametrize("arch", [a for a in ARCHS
                                  if REGISTRY[a].family != "audio"])
def test_prefill_decode_smoke(arch):
    cfg = get_config(arch).reduced()
    b, s = 2, 32
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg, jax.random.PRNGKey(1), b, s)
    logits, cache = api.prefill(params, cfg, batch)
    assert logits.shape[0] == b and logits.shape[-1] == cfg.vocab
    assert np.isfinite(np.asarray(logits)).all(), f"{arch}: NaN prefill"

    if cache is None:
        cache = api.init_cache(cfg, b, 64)
    # continue decoding two tokens
    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    pos = s if cfg.family != "vlm" else s  # absolute position
    for i in range(2):
        logits2, cache = api.decode_step(params, cfg, tok, cache,
                                         jnp.int32(pos + i))
        assert logits2.shape == (b, 1, cfg.vocab)
        assert np.isfinite(np.asarray(logits2)).all(), f"{arch}: NaN decode"
        tok = jnp.argmax(logits2, -1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_shapes(arch):
    from repro.configs.base import SHAPES
    cfg = get_config(arch)
    for name in cfg.shapes:
        specs = api.input_specs(cfg, SHAPES[name])
        assert specs, f"{arch}/{name}: empty specs"
    # every non-applicable assigned shape has a recorded skip reason
    for name in SHAPES:
        if name not in cfg.shapes:
            assert name in cfg.skip_notes, f"{arch}: {name} skipped w/o note"


def test_decode_matches_prefill_tail():
    """Decoding token t with a cache == prefilling through t (dense)."""
    cfg = get_config("gpt2-small").reduced()
    b, s = 1, 16
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, cfg.vocab)
    full_logits, _ = api.prefill(params, cfg, {"tokens": toks})

    # prefill first s-1 tokens, then decode the last one
    head_logits, cache = api.prefill(params, cfg, {"tokens": toks[:, :-1]})
    # grow cache to length s
    ck = api.init_cache(cfg, b, s)["k"].at[:, :, :s - 1].set(cache["k"])
    cv = jnp.zeros_like(ck).at[:, :, :s - 1].set(cache["v"])
    dec_logits, _ = api.decode_step(params, cfg, toks[:, -1:],
                                    {"k": ck, "v": cv}, jnp.int32(s - 1))
    np.testing.assert_allclose(np.asarray(full_logits[:, -1]),
                               np.asarray(dec_logits[:, 0]),
                               atol=0.15, rtol=0.05)
