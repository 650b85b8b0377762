"""Operations and bytes of one flash-decode call: one layer's attention of
one new query token per live row against that row's cached keys and
values.

The algorithm's work, at the configuration's widths and dtypes: QK^T and
PV are 2 x ctx x head_dim multiply-adds per query head each; it reads the
live K and V rows (bf16, the true head dim, no lane padding) once, reads
the query and writes the output. Rows that hold no request and cache rows
past a row's length are not work.
"""


def flops(model: dict, ctx) -> float:
    """``ctx``: cache lengths (keys attended) of the live rows."""
    return 4.0 * model["n_heads"] * model["head_dim"] * float(sum(ctx))


def bytes_moved(model: dict, ctx, kv_bytes: int = 2,
                act_bytes: int = 2) -> float:
    h, kvh, d = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    kv = 2.0 * kvh * d * kv_bytes * float(sum(ctx))
    return kv + len(ctx) * 2.0 * h * d * act_bytes
