"""Model operations of one decode step of a dense transformer: one new
token for each live row.

2 x (parameters in matrix products) per token, the unembedding over the
real vocabulary included and the embedding gather not; plus attention's
4 x heads x head_dim x context per layer on the live context. Recomputed
or padded work does not count.
"""


def matmul_params(model: dict) -> float:
    d, L, v = model["d_model"], model["n_layers"], model["vocab"]
    h, kvh, hd = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    per = d * hd * (h + 2 * kvh) + h * hd * d + 2 * d * model["d_ff"]
    return L * per + d * v


def flops(model: dict, ctx) -> float:
    """``ctx``: context length (positions attended, new token included)
    of each live row."""
    ctx = list(ctx)
    out = 2.0 * matmul_params(model) * len(ctx)
    return out + 4.0 * model["n_heads"] * model["head_dim"] * \
        model["n_layers"] * sum(ctx)
