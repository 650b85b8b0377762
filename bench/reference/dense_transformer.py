"""Plain float32 reference of a pre-norm decoder-only transformer (the
GPT-2 block as this system's ``gpt2-small`` configuration runs it).

Per layer: ``x += Wo . attn(RoPE(Wq LN1(x) + bq), RoPE(Wk ...), Wv ...)``
with causal softmax at scale 1/sqrt(head_dim), then
``x += Wd gelu_tanh(Wu LN2(x) + bu) + bd``; a final LayerNorm and the
tied embedding give the logits over the real vocabulary. Rotary
positions rotate interleaved pairs (dims 2i, 2i+1) at frequency
theta^(-2i/d). Everything is float32 with exact ``exp``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from common import normal


def check(spec: dict) -> None:
    """This reference covers only the block described above."""
    want = {"norm": "layernorm", "act": "gelu", "use_bias": True,
            "tie_embeddings": True, "parallel_block": False,
            "sliding_window": None, "rope_pct": 1.0}
    bad = {k: spec.get(k) for k, v in want.items() if spec.get(k) != v}
    if bad:
        raise ValueError(f"dense_transformer reference does not cover {bad}")


def init_params(spec: dict, key) -> dict:
    """Random weights in the served layout. Every matrix is N(0, 1/fan_in),
    so each layer's output is of the order of its input and the next
    token depends on the whole context, not only on the last one; the
    embedding (tied to the output) is N(0, 0.02) as in GPT-2; biases
    N(0, 0.02); norm gains 1 + N(0, 0.1)."""
    check(spec)
    L, d, f = spec["n_layers"], spec["d_model"], spec["d_ff"]
    h, hd = spec["n_heads"], spec["head_dim"]
    kvh = spec["n_kv_heads"]
    vp = spec["vocab_padded"]
    ks = iter(jax.random.split(key, 32))

    def mat(fan_in, fan_out):
        return normal(next(ks), (L, fan_in, fan_out), fan_in ** -0.5)

    def vec(n, std=0.02):
        return normal(next(ks), (L, n), std)

    def norm():
        return {"w": 1.0 + vec(d, 0.1), "b": vec(d)}

    layers = {
        "ln_attn": norm(),
        "attn": {"wq": mat(d, h * hd), "wk": mat(d, kvh * hd),
                 "wv": mat(d, kvh * hd), "wo": mat(h * hd, d),
                 "bq": vec(h * hd), "bk": vec(kvh * hd),
                 "bv": vec(kvh * hd)},
        "ln_mlp": norm(),
        "mlp": {"wu": mat(d, f), "wd": mat(f, d), "bu": vec(f),
                "bd": vec(d)},
    }
    emb = normal(next(ks), (vp, d), 0.02)
    emb = emb.at[spec["vocab"]:].set(0.0)
    return {"layers": layers,
            "ln_f": {"w": 1.0 + normal(next(ks), (d,), 0.1),
                     "b": normal(next(ks), (d,), 0.02)},
            "embed": emb}


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _rope(x, theta):
    """x (B, T, H, D): rotate pairs (2i, 2i+1) by position x theta^(-2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654
                                     * (x + 0.044715 * x ** 3)))


def hidden(params: dict, spec: dict, tokens, mm) -> jax.Array:
    """Final normed hidden states (B, T, d) of ``tokens`` (B, T)."""
    h, hd, kvh = spec["n_heads"], spec["head_dim"], spec["n_kv_heads"]
    eps = spec["norm_eps"]
    x = params["embed"][tokens].astype(jnp.float32)
    b, t, _ = x.shape
    causal = jnp.tril(jnp.ones((t, t), bool))

    def layer(x, p):
        a = p["attn"]
        y = _layernorm(x, p["ln_attn"]["w"], p["ln_attn"]["b"], eps)
        q = (mm(y, a["wq"]) + a["bq"]).reshape(b, t, h, hd)
        k = (mm(y, a["wk"]) + a["bk"]).reshape(b, t, kvh, hd)
        v = (mm(y, a["wv"]) + a["bv"]).reshape(b, t, kvh, hd)
        q, k = _rope(q, spec["rope_theta"]), _rope(k, spec["rope_theta"])
        k = jnp.repeat(k, h // kvh, axis=2)
        v = jnp.repeat(v, h // kvh, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / hd ** 0.5
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, v,
                       precision=jax.lax.Precision.HIGHEST)
        x = x + mm(o.reshape(b, t, h * hd), a["wo"])
        m = p["mlp"]
        y = _layernorm(x, p["ln_mlp"]["w"], p["ln_mlp"]["b"], eps)
        x = x + mm(_gelu_tanh(mm(y, m["wu"]) + m["bu"]), m["wd"]) + m["bd"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _layernorm(x, params["ln_f"]["w"], params["ln_f"]["b"], eps)


def logits(params: dict, spec: dict, x, mm) -> jax.Array:
    """Logits over the real vocabulary from hidden states (..., d)."""
    return mm(x, params["embed"][:spec["vocab"]].T)
