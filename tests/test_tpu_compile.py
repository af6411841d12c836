"""Main-path Pallas kernels compile natively for a described TPU v5e at
gpt3_126m widths (d=768, d_ff=3072, 12 KV heads of 64, page size 16).

Nothing runs: the TPU compiler installed with jax lowers and compiles each
kernel with ``interpret=False`` for a chip that is described, not
attached, and refuses what the chip would refuse (misaligned slices,
unsupported shape casts, VMEM overruns).  The topology is described inside
a module fixture — never at import — so every pytest-xdist worker collects
the same tests and only the worker running this file loads the TPU
library.  The persistent compilation cache is off around these compiles
(a described-chip executable cannot be read back from it).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.core.bcq import BCQConfig
from repro.kernels import ops
from repro.kernels.bcq_linear import bcq_linear_pallas
from repro.kernels.common import page_gather_attention
from repro.models import layers

CFG = BCQConfig()
ARCH = get_arch("gpt3_126m")
PS, N_PAGES, B, MAXP = 16, 64, 8, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize(
    "k,n", [(ARCH.d_model, 3 * ARCH.d_model), (ARCH.d_ff, ARCH.d_model)],
    ids=["768x2304", "3072x768"],
)
def test_fused_linear_compiles_for_v5e(one_chip, k, n):
    tile_k = ops.default_tile_k(k, CFG)

    def fused(x, idx, sel, inv, cb, s_x):
        return bcq_linear_pallas(
            x, idx, sel, inv, cb, s_x, CFG, tile_k=tile_k, interpret=False
        )

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    txt = _compiled_text(
        fused, s((128, k), jnp.float32), s((n, k // 2), jnp.uint8),
        s((n, k // 16), jnp.uint8), s((n, k // CFG.array_len), jnp.float32),
        s((CFG.n_codebooks, CFG.n_entries), jnp.float32), s((), jnp.float32),
    )
    assert "tpu_custom_call" in txt and "bcq_linear" in txt


def _pool_specs(sharding, kind):
    pool = jax.eval_shape(
        lambda: layers.cache_init(N_PAGES, PS, ARCH.n_kv_heads, ARCH.head_dim, kind, CFG)
    )
    return {n: _spec(sharding, a.shape, a.dtype) for n, a in pool.items()}


@pytest.mark.parametrize(
    "kind,chunk",
    [(kind, chunk) for chunk in (1, 64) for kind in ("bf16", "int8", "bcq4")],
    ids=lambda v: {1: "decode", 64: "chunked"}.get(v, v),
)
def test_page_gather_compiles_for_v5e(one_chip, kind, chunk):
    def attend(q, pool, bt, kv_len, cb):
        return page_gather_attention(q, pool, bt, kv_len, kind, CFG, cb, interpret=False)

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    txt = _compiled_text(
        attend, s((B, chunk, ARCH.n_heads, ARCH.head_dim), jnp.bfloat16),
        _pool_specs(one_chip, kind), s((B, MAXP), jnp.int32), s((B,), jnp.int32),
        s((CFG.n_codebooks, CFG.n_entries), jnp.float32),
    )
    assert "tpu_custom_call" in txt and "page_gather_attention" in txt
