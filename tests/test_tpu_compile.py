"""Compile the main path's Pallas kernels for a TPU v5e, without a chip.

Interpret mode cannot see what the chip's compiler refuses (block shapes
that break the (8, 128) tiling rule, vector shape casts Mosaic does not
lower). These tests compile each serving kernel with ``interpret=False``
at gpt2-small widths (12 heads, head dim 64, 1024 positions, 8 slots) for
a described ``v5e:2x2`` topology and check that the kernel survived into
the program as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

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
    _compile(lambda q, k, v, c: decode_attention(q, k, v, c, layout=layout,
                                                 policy=pol),
             q, kv, kv, clen)


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
