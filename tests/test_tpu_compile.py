"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

Interpret mode cannot see what the chip's compiler refuses (block shapes
that break the (8, 128) tiling rule, vector shape casts Mosaic does not
lower). These tests compile each serving kernel with ``interpret=False``
at gpt2-small widths (12 heads, head dim 64, 1024 positions, 8 slots) for
a described ``v5e:2x2`` topology and check that the kernel survived into
the program as a ``tpu_custom_call``; the serving decode program is
compiled whole, and its layer loop inspected op by op.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import math
import os
import re

import pytest
import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_partial_packed)
from repro.kernels.decode_attention.ops import decode_attention_paged
from repro.kernels.flash_attention.ops import flash_attention
from repro.runtime import ExecPolicy

B, H, D, S = 8, 12, 64, 1024          # gpt2-small serving pool
PAGE = 64
EXP = ("exact", "vexp", "vexp_hw")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return compiled


def _check_kernel_bitcasts(text):
    """Where each kernel row holds several heads in its lanes, no bitcast
    of the Pallas call's output may drop elements: such a bitcast keeps
    the same lanes of every row (right for a lane-padded head, which
    keeps its first d). On a v5e, XLA folded the reshape, per-row lane
    slices and stack that map grouped rows back to heads into one, and
    every second head came out wrong."""
    lines = text.splitlines()
    for kname, _, kdims in _array_ops(
            [ln.strip() for ln in lines if 'tpu_custom_call"' in ln]):
        for _, op, d in _array_ops(
                [ln.strip() for ln in lines if f"(%{kname})" in ln]):
            assert op != "bitcast" or math.prod(d) == math.prod(kdims), \
                f"{kname} {kdims} bitcast to {d}"


def _arg(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _policy(exp):
    return ExecPolicy(exp_backend=exp, interpret=False)


@pytest.mark.parametrize("exp", EXP)
def test_flash_attention(one_chip, exp):
    pol = _policy(exp)
    x = _arg((4, S, H, D), jnp.bfloat16, one_chip)
    _compile(lambda q, k, v: flash_attention(q, k, v, True, None, None,
                                             128, 128, False, pol),
             x, x, x)


@pytest.mark.parametrize("exp", EXP)
@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_decode_attention(one_chip, layout, exp):
    pol = _policy(exp)
    cache = (B, S, H, D) if layout == "bshd" else (B, H, S, D)
    q = _arg((B, 1, H, D), jnp.bfloat16, one_chip)
    kv = _arg(cache, jnp.bfloat16, one_chip)
    clen = _arg((B,), jnp.int32, one_chip)
    compiled = _compile(lambda q, k, v, c: decode_attention(
        q, k, v, c, layout=layout, policy=pol), q, kv, kv, clen)
    if layout == "bshd":                 # two heads of 64 per lane block
        _check_kernel_bitcasts(compiled.as_text())


@pytest.mark.parametrize("layout,d", [("bshd", 64), ("bhsd", 128)])
def test_decode_attention_stacked_pool(one_chip, layout, d):
    """Flash-decode over a stacked 12-layer pool at a traced layer index:
    the kernel takes the whole pool and selects the layer in its index
    maps, so no layer of it is sliced out before the call."""
    pol = _policy("vexp")
    one = (B, S, H * d) if layout == "bshd" else (B, H, S, d)
    pool = (12,) + one
    q = _arg((B, 1, H, d), jnp.bfloat16, one_chip)
    kv = _arg(pool, jnp.bfloat16, one_chip)
    clen = _arg((B,), jnp.int32, one_chip)
    lay = _arg((), jnp.int32, one_chip)
    text = _compile(lambda q, k, v, c, l: decode_attention(
        q, k, v, c, layer=l, layout=layout, policy=pol),
        q, kv, kv, clen, lay).as_text()
    kernel = [ln for ln in text.splitlines() if 'tpu_custom_call"' in ln]
    assert len(kernel) == 1
    pool_t = "bf16[" + ",".join(map(str, pool)) + "]"
    assert kernel[0].count(pool_t) == 2, "the kernel does not read the pool"
    assert not [d_ for _, op, d_ in _array_ops(
        [ln.strip() for ln in text.splitlines()])
        if op in ("dynamic-slice", "copy") and d_[-len(one):] == one]


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_decode_attention_paged(one_chip, layout):
    pol = _policy("vexp")
    n_pages = B * (S // PAGE) + 1
    pool = ((n_pages, PAGE, H, D) if layout == "bshd"
            else (n_pages, H, PAGE, D))
    q = _arg((B, 1, H, D), jnp.bfloat16, one_chip)
    kv = _arg(pool, jnp.bfloat16, one_chip)
    tab = _arg((B, S // PAGE), jnp.int32, one_chip)
    clen = _arg((B,), jnp.int32, one_chip)
    _compile(lambda q, k, v, t, c: decode_attention_paged(
        q, k, v, t, c, layout=layout, policy=pol), q, kv, kv, tab, clen)


@pytest.mark.parametrize("layout", ["bshd", "bhsd"])
def test_decode_attention_partial_packed(one_chip, layout):
    """The shard-local sweep of the sequence-sharded decode: one shard's
    quarter of the cache on a four-chip mesh."""
    pol = _policy("vexp")
    local = S // 4
    cache = (B, local, H, D) if layout == "bshd" else (B, H, local, D)
    q = _arg((B, 1, H, D), jnp.bfloat16, one_chip)
    kv = _arg(cache, jnp.bfloat16, one_chip)
    clen = _arg((B,), jnp.int32, one_chip)
    off = _arg((), jnp.int32, one_chip)
    _compile(lambda q, k, v, c, o: decode_attention_partial_packed(
        q, k, v, c, o, layout=layout, policy=pol), q, kv, kv, clen, off)


# ------------------------------------------- the serving decode program

SLOTS = 32                            # the benchmark's pool: 32 x 1024


def _array_ops(lines):
    """(name, opcode, dims) of each array-valued instruction."""
    out = []
    for ln in lines:
        m = re.match(r"(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     ln)
        if m:
            dims = tuple(int(x) for x in m.group(2).split(",") if x)
            out.append((m.group(1), m.group(3), dims))
    return out


def _loop_body(text):
    """Top-level instructions of the program's one while loop (the layer
    scan): computations are ``%name (...) -> ... {`` blocks of indented
    instruction lines."""
    comps, cur = {}, None
    for ln in text.splitlines():
        m = re.match(r"(?:ENTRY )?%(\S+) \(", ln)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and ln.startswith("  "):
            cur.append(ln.strip())
    bodies = re.findall(r" while\(.*?body=%([\w.-]+)", text)
    assert len(bodies) == 1, f"{len(bodies)} while loops"
    return comps[bodies[0]]


@pytest.fixture(scope="module")
def serving_decode(one_chip):
    """The decode program the server runs, at gpt2-small widths over the
    benchmark's pool, compiled once for the described chip: (compiled,
    cfg, number of parameter leaves)."""
    from repro.configs import get_config
    from repro.models import api
    from repro.models.decode_state import _programs
    from repro.runtime import resolve_policy
    cfg = get_config("gpt2-small")
    pol = resolve_policy(cfg, env={}, kernel_backend="pallas",
                         exp_backend="vexp", interpret=False)

    def arg(x):
        return _arg(x.shape, x.dtype, one_chip)

    params = jax.tree.map(arg, jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree.map(arg, jax.eval_shape(
        lambda: api.init_cache(cfg, SLOTS, S)))
    assert cache["k"].shape == (cfg.n_layers, SLOTS, S,
                                cfg.n_kv_heads * cfg.hd)
    tok = _arg((SLOTS, 1), jnp.int32, one_chip)
    vec = _arg((SLOTS,), jnp.int32, one_chip)
    decode = _programs(cfg, pol)[2]
    compiled = decode.lower(params, tok, cache, vec, vec).compile()
    return compiled, cfg, len(jax.tree.leaves(params))


def test_serving_decode_step_reads_pool_unpadded(serving_decode):
    """The serving decode program's layer loop reads each layer's K and V
    as the pool stores them. No pad, no copy of a layer's cache (the 4-D
    relayout or the 128-lane padded view), one flash-decode kernel."""
    compiled, cfg, _ = serving_decode
    hkv, hd = cfg.n_kv_heads, cfg.hd
    text = compiled.as_text()
    body = _loop_body(text)
    ops = _array_ops(body)
    pads = [(n, d) for n, op, d in ops if op == "pad"]
    assert not pads, f"pads in the layer loop: {pads}"
    layer = SLOTS * S * hkv * hd
    copies = [(n, d) for n, op, d in ops
              if op == "copy" and math.prod(d) >= layer]
    assert not copies, f"layer-sized copies in the layer loop: {copies}"
    assert not [d for _, _, d in ops
                if d in ((SLOTS, S, hkv, hd), (SLOTS, S, hkv * 128))]
    kernels = [ln for ln in body if 'custom_call_target="tpu_custom_call"'
               in ln]
    assert len(kernels) == 1, f"{len(kernels)} Pallas calls per layer"
    _check_kernel_bitcasts(text)


def _instructions(text):
    """Every instruction line of every computation, entry included."""
    return [ln.strip() for ln in text.splitlines()
            if ln.startswith("  ") and " = " in ln]


def test_serving_decode_step_carries_pool_in_place(serving_decode):
    """The whole decode program, entry included, moves nothing of the
    stacked pool but the token rows it writes. The layer scan carries the
    pool and flash-decode reads its layer by index, so there is no copy,
    slice or write-back of a layer's or the pool's K/V, no pool-sized
    buffer the program allocates, and the donated pool aliases the
    output. The one pool-sized instruction per leaf is the row write, a
    scatter that updates its operand in place."""
    compiled, cfg, n_params = serving_decode
    text = compiled.as_text()
    layer = SLOTS * S * cfg.n_kv_heads * cfg.hd
    lines = _instructions(text)
    big = [(n, op, d, ln) for (n, op, d), ln in      # the K/V arrays
           ((o, ln) for ln in lines for o in _array_ops([ln]))
           if math.prod(d) >= layer and SLOTS in d and S in d]
    moves = [(n, op, d) for n, op, d, _ in big
             if op in ("copy", "dynamic-slice", "dynamic-update-slice")
             or op == "fusion" and re.search(
                 r"copy|dynamic-slice|dynamic-update-slice", n)]
    assert not moves, f"layer- or pool-sized moves of the pool: {moves}"
    allocs = [(n, d) for n, op, d, _ in big if op == "custom-call"]
    assert not allocs, f"pool-sized buffers allocated: {allocs}"
    writes = [ln for n, op, _, ln in big if op == "fusion"]
    assert len(writes) == 2, f"{len(writes)} pool-sized fusions"
    for ln in writes:
        assert "kv_write/scatter" in ln and '"aliasing_operands"' in ln, ln
    head = text[:text.index("\n")]
    alias = dict((int(p), int(o)) for o, p in re.findall(
        r"\{(\d+)\}: \((\d+), \{\}", head))
    pool_args = (n_params + 1, n_params + 2)      # after params and tokens
    assert all(a in alias for a in pool_args), \
        f"pool parameters {pool_args} not aliased: {alias}"
    pool_bytes = cfg.n_layers * layer * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes, f"{temp} bytes of temporaries"
