"""Whether what the timed path served is right, by the plain reference.

Once the window has closed and the server's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest,
is run through the configuration's float32 reference: one teacher-forced
forward over each prompt followed by its served tokens. At every served
position the reference's best logit is compared with its logit for the
token the system served; the number judged is the widest such gap over
the sample. It is 0 where the system served the reference's own argmax
everywhere, and grows with the system's numerical error wherever the top
logits are close.

The control puts the same reference, with fp8 matrix products, in the
system's place: at each position it reads the gap of the token the fp8
forward puts first.
"""

from __future__ import annotations

import numpy as np

# The sample holds at least this many served tokens (or every request).
MIN_TOKENS = 512
MAX_REQUESTS = 16
# Served positions scored per logits block (bounds the (n, vocab) slab).
BLOCK = 256
# The reference's batch is MAX_REQUESTS rows and its length a multiple of
# PAD_T, so that a few shapes serve every seed (padding follows the real
# tokens and cannot reach them through a causal model).
PAD_T = 128


def pick(done: list, seed: int) -> list:
    """Indices into ``done`` [(prompt, served)]: the longest request and
    then a seeded random order, until MIN_TOKENS served tokens."""
    if not done:
        return []
    total = [len(p) + len(s) for p, s in done]
    first = int(np.argmax(total))
    rng = np.random.default_rng([int(seed), 1])
    rest = [int(i) for i in rng.permutation(len(done)) if i != first]
    out, n = [first], len(done[first][1])
    for i in rest:
        if n >= MIN_TOKENS or len(out) >= MAX_REQUESTS:
            break
        out.append(i)
        n += len(done[i][1])
    return out


def _batch(seqs: list):
    """Tokens (B, T) of prompt + served[:-1], and for every served token
    its (row, position, token)."""
    import jax.numpy as jnp
    t = max(len(p) + len(s) - 1 for p, s in seqs)
    t = -(-t // PAD_T) * PAD_T
    toks = np.zeros((MAX_REQUESTS, t), np.int32)
    rows, pos, tok = [], [], []
    for b, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s]).astype(np.int32)[:-1]
        toks[b, :len(full)] = full
        for i, x in enumerate(s):
            rows.append(b)
            pos.append(len(p) - 1 + i)
            tok.append(int(x))
    return jnp.asarray(toks), np.array(rows), np.array(pos), np.array(tok)


def gaps(ref, params, spec: dict, seqs: list, *, control: bool = False):
    """Per served token: reference best logit minus the reference logit of
    the served token (``control``: of the token the fp8 forward ranks
    first). Returns a float64 array, one entry per served token."""
    import jax
    import jax.numpy as jnp
    from common import MATMULS

    toks, rows, pos, tok = _batch(seqs)
    f32 = MATMULS["f32"]
    h_ref = jax.jit(lambda p, t: ref.hidden(p, spec, t, f32))(params, toks)
    h_ctl = None
    if control:
        fp8 = MATMULS["fp8"]
        h_ctl = jax.jit(lambda p, t: ref.hidden(p, spec, t, fp8))(params,
                                                                 toks)

    @jax.jit
    def block(p, h, hc, r, q, k):
        lg = ref.logits(p, spec, h[r, q], f32)                  # (n, V)
        if hc is None:
            pick_ = k
        else:
            pick_ = jnp.argmax(ref.logits(p, spec, hc[r, q], MATMULS["fp8"]),
                               -1)
        got = jnp.take_along_axis(lg, pick_[:, None], -1)[:, 0]
        return jnp.max(lg, -1) - got

    out = []
    n = len(tok)
    for a in range(0, n, BLOCK):
        sl = slice(a, min(a + BLOCK, n))
        pad = BLOCK - (sl.stop - sl.start)
        r = np.pad(rows[sl], (0, pad))
        q = np.pad(pos[sl], (0, pad))
        k = np.pad(tok[sl], (0, pad))
        g = block(params, h_ref, h_ctl, jnp.asarray(r), jnp.asarray(q),
                  jnp.asarray(k, jnp.int32))
        out.append(np.asarray(g, np.float64)[:BLOCK - pad])
    return np.concatenate(out)
