"""Main-path Pallas kernels compile natively for a described TPU v5e at
the widths of the benchmark's models: gpt3_126m (d=768, d_ff=3072, 12 KV
heads of 64) and StarCoder2-3B (d=3072, d_ff=12288, 24 query heads over
2 KV heads of 128), page size 16.

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
from repro.kernels.bcq_linear import VMEM_BUDGET, bcq_linear_pallas, fit_k_block, vmem_bytes
from repro.kernels.common import page_gather_attention
from repro.models import layers

CFG = BCQConfig()
ARCH = get_arch("gpt3_126m")
SC2 = get_arch("starcoder2_3b")
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


def _linears(arch) -> set:
    """(K, N) of every fused linear of one dense layer (separate q/k/v)."""
    d, f, hd = arch.d_model, arch.d_ff, arch.head_dim
    return {(d, arch.n_heads * hd), (d, arch.n_kv_heads * hd), (arch.n_heads * hd, d), (d, f), (f, d)}


@pytest.mark.parametrize(
    "k,n",
    [(ARCH.d_model, 3 * ARCH.d_model), (ARCH.d_ff, ARCH.d_model),
     (SC2.d_model, SC2.d_model), (SC2.d_model, SC2.d_ff), (SC2.d_ff, SC2.d_model)],
    ids=["768x2304", "3072x768", "3072x3072", "3072x12288", "12288x3072"],
)
def test_fused_linear_compiles_for_v5e(one_chip, k, n):
    """At an 8-row chunk launch's M = 512, its f32 output cast as the model
    casts it: XLA may then keep the output in VMEM beside the kernel's own
    scoped VMEM, which the whole-K step at K = 12288 overran."""
    tile_k = ops.default_tile_k(k, CFG)

    def fused(x, idx, sel, inv, cb, s_x):
        return bcq_linear_pallas(
            x, idx, sel, inv, cb, s_x, CFG, tile_k=tile_k, interpret=False
        ).astype(jnp.bfloat16)

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    txt = _compiled_text(
        fused, s((512, k), jnp.float32), s((n, k // 2), jnp.uint8),
        s((n, k // 16), jnp.uint8), s((n, k // CFG.array_len), jnp.float32),
        s((CFG.n_codebooks, CFG.n_entries), jnp.float32), s((), jnp.float32),
    )
    assert "tpu_custom_call" in txt and "bcq_linear" in txt


def test_fused_linear_vmem_within_budget():
    """The fused linear's stated VMEM need (``vmem_bytes``) stays within
    its 12 MiB budget at every linear of both models, at the tiles the
    served path launches; a step's VMEM depends on its tiles and K, not on
    M, whose 128-row tiles the grid walks (M = 128, 256, 512 alike).  The
    whole-K step at StarCoder2's d_ff would not fit: K is split there."""
    for k, n in sorted(_linears(ARCH) | _linears(SC2)):
        tk = ops.default_tile_k(k, CFG)
        kb = fit_k_block(k, 128, 128, tk, CFG)
        assert k % kb == 0 and kb % tk == 0
        assert vmem_bytes(128, 128, k, kb, tk, CFG) <= VMEM_BUDGET, (k, n, kb)
        assert kb == k or k == SC2.d_ff, (k, kb)  # gpt3 keeps whole K
    tk = ops.default_tile_k(SC2.d_ff, CFG)
    assert vmem_bytes(128, 128, SC2.d_ff, SC2.d_ff, tk, CFG) > VMEM_BUDGET
    assert fit_k_block(SC2.d_ff, 128, 128, tk, CFG) < SC2.d_ff


def _pool_specs(sharding, kind, arch=ARCH):
    pool = jax.eval_shape(
        lambda: layers.cache_init(N_PAGES, PS, arch.n_kv_heads, arch.head_dim, kind, CFG)
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


@pytest.mark.parametrize("chunk", [1, 64], ids=["decode", "chunked"])
def test_page_gather_gqa_compiles_for_v5e(one_chip, chunk):
    """StarCoder2's GQA over bcq4 pages: 12 query heads a KV head (a
    head-major block of 12·64 = 768 query rows per 64-token chunk), head
    dim 128, tables 256 pages wide (max_len 4096)."""
    def attend(q, pool, bt, kv_len, cb):
        return page_gather_attention(q, pool, bt, kv_len, "bcq4", CFG, cb, interpret=False)

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    txt = _compiled_text(
        attend, s((B, chunk, SC2.n_heads, SC2.head_dim), jnp.bfloat16),
        _pool_specs(one_chip, "bcq4", SC2), s((B, 4096 // PS), jnp.int32), s((B,), jnp.int32),
        s((CFG.n_codebooks, CFG.n_entries), jnp.float32),
    )
    assert "tpu_custom_call" in txt and "page_gather_attention" in txt


@pytest.mark.parametrize(
    "arch,maxp",
    [(ARCH, 2048 // PS), (SC2, 4096 // PS)],
    ids=["gpt3_126m-128x128", "starcoder2_3b-128x256"],
)
def test_page_gather_decode_launch_compiles_for_v5e(one_chip, arch, maxp):
    """The served decode launch: 128 rows over tables of MAXP pages (gpt3:
    12 heads of 64, 128 pages; StarCoder2: GQA 24/2 of 128, 256 pages),
    its grid bounded at run time by the live-step total."""
    def attend(q, pool, bt, kv_len, cb):
        return page_gather_attention(q, pool, bt, kv_len, "bcq4", CFG, cb, interpret=False)

    s = lambda shape, dt: _spec(one_chip, shape, dt)  # noqa: E731
    lowered = jax.jit(attend).lower(
        s((128, 1, arch.n_heads, arch.head_dim), jnp.bfloat16),
        _pool_specs(one_chip, "bcq4", arch), s((128, maxp), jnp.int32), s((128,), jnp.int32),
        s((CFG.n_codebooks, CFG.n_entries), jnp.float32),
    )
    txt = lowered.compile().as_text()
    assert "tpu_custom_call" in txt and "page_gather_attention" in txt
