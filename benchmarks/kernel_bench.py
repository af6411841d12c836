"""§3 on-the-fly quantization cost: kernel + reference micro-benchmarks.

CPU timings (interpret-mode Pallas is a correctness vehicle, not perf) —
the derived columns report work sizes and an *analytic* HBM-bytes-per-GEMM
model so TPU projections can be made from the roofline constants.  The
fused-vs-two-launch comparison, the per-stream HBM breakdown, and the
paged-kernel smoke (lane-gather page dequant **bit-identical** to the
reference flat-gather + live-page-grid attention vs oracle, with the
analytic NULL-page HBM credit) are written to ``BENCH_kernels.json``.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import codebooks_for, emit, llm_like_operand, timeit
from repro.core import bcq
from repro.core.bcq import BCQConfig
from repro.kernels import ops


def hbm_bytes_per_linear(
    m: int, k: int, n: int, cfg: BCQConfig,
    tile_m: int = 128, tile_n: int = 128, tile_k: int = 512, act_bytes: int = 4,
) -> dict:
    """Analytic HBM traffic of one (M, K)·(N, K)ᵀ W4A4 linear, per path.

    Counts every stream with its grid re-fetch multiplicity (a tile is
    DMA'd again whenever its block index changes between consecutive grid
    steps).  Packed operands carry idx (4 bit) + sel (4/2Lb bit) + f32
    per-array inv scales.
    """
    nt_m, nt_n = -(-m // tile_m), -(-n // tile_n)

    def packed_bytes(rows):
        return rows * (k // 2 + k // (2 * cfg.block_len) + 4 * (k // cfg.array_len))

    out = m * n * 4
    two = {
        "raw_act_read": m * k * act_bytes,            # quantize launch, 1×
        "packed_act": packed_bytes(m) * (1 + nt_n),   # write + N-tile re-reads
        "packed_weight": packed_bytes(n) * nt_m,      # M-tile re-reads
        "out": out,
    }
    fused = {
        # full-K slab, block index = M tile only: fetched once per linear
        # when M is a single tile (serving decode); multi-M-tile prefill
        # re-streams the slab per N tile like any GEMM operand
        "raw_act_read": m * k * act_bytes * (1 if nt_m == 1 else nt_n),
        "packed_act": 0,                              # never leaves VMEM
        "packed_weight": packed_bytes(n) * nt_m,
        "out": out,
    }
    for d in (two, fused):
        d["total"] = sum(d.values())
    return {"two_launch": two, "fused": fused}


def paged_kernel_smoke(cfg: BCQConfig, cb) -> dict:
    """Live-page-grid paged kernels: lane-gather dequant bit-identity vs
    the reference flat-gather (on the pool's own packed codes), decode +
    chunked-prefill attention vs their oracles in interpret mode, and the
    analytic HBM bytes the live-page schedule skips for NULL table slots.
    """
    from repro.kernels import ref as kref
    from repro.kernels.chunked_prefill import chunked_prefill
    from repro.kernels.common import codebook_lookup, flat_codebook
    from repro.kernels.paged_attention import paged_attention
    from repro.models import layers as mlayers

    p_pages, ps, hkv, d = 6, 8, 2, 32
    pool = mlayers.cache_init(p_pages, ps, hkv, d, "bcq4", cfg)
    kk = jax.random.normal(jax.random.PRNGKey(0), (p_pages, ps, hkv, d))
    vv = jax.random.normal(jax.random.PRNGKey(1), (p_pages, ps, hkv, d))
    pool = mlayers.cache_write(pool, kk, vv, 0, "bcq4", cfg, cb)

    # 1) the lane-gather codeword lookup is an exact table lookup: decode
    # the pool's own packed K codes both ways, compare BITWISE
    ccfg = dataclasses.replace(cfg, array_len=min(cfg.array_len, d))
    idx = bcq.unpack_nibbles(pool["k_idx"]).astype(jnp.int32)
    sel = bcq.unpack_nibbles(pool["k_sel"]).astype(jnp.int32)[..., : d // ccfg.block_len]
    code = (jnp.repeat(sel, ccfg.block_len, -1) * ccfg.n_entries + idx).reshape(-1, d)
    got = codebook_lookup(code, flat_codebook(cb))
    ref_gather = cb.astype(jnp.float32).reshape(-1)[code]
    bit_identical = bool(jnp.all(got == ref_gather))
    emit(
        "kernel_paged_gather_dequant", 0.0,
        f"lane-gather codebook lookup bit_identical_vs_ref_gather={bit_identical} "
        f"({code.shape[0]}x{d} page codes)",
    )

    # 2) attention kernels vs oracles, interpret mode (correctness vehicle)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 0]], jnp.int32)
    lengths = jnp.asarray([19, 9], jnp.int32)
    q = jax.random.normal(jax.random.PRNGKey(2), (2, 2 * hkv, d))
    us_d, out_d = timeit(
        lambda: paged_attention(q, pool, bt, lengths, "bcq4", cfg, cb, interpret=True),
        warmup=1, iters=2,
    )
    decode_ok = bool(jnp.allclose(
        out_d, kref.paged_attention_ref(q, pool, bt, lengths, "bcq4", cfg, cb),
        atol=2e-5, rtol=2e-5,
    ))
    emit("kernel_paged_decode_interp", us_d,
         f"live-page grid, GQA 2x, matches_ref={decode_ok}")

    qc = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 2 * hkv, d))
    n_past = jnp.asarray([8, 3], jnp.int32)
    us_c, out_c = timeit(
        lambda: chunked_prefill(qc, pool, bt, n_past, "bcq4", cfg, cb, interpret=True),
        warmup=1, iters=2,
    )
    chunk_ok = bool(jnp.allclose(
        out_c, kref.chunked_prefill_ref(qc, pool, bt, n_past, "bcq4", cfg, cb),
        atol=2e-5, rtol=2e-5,
    ))
    emit("kernel_chunked_prefill_interp", us_c,
         f"shared page-gather core, matches_ref={chunk_ok}")

    # 3) analytic HBM per decode tick: live pages vs the old (B, MAXP)
    # masked grid that DMA'd NULL padding too (bcq4 page bytes)
    page_b = ps * hkv * (d // 2 + d // (2 * ccfg.block_len) + d // ccfg.array_len) * 2
    live_pages = int(np.sum(np.ceil(np.asarray(lengths) / ps)))
    masked_pages = bt.shape[0] * bt.shape[1]
    emit(
        "kernel_paged_hbm_analytic", 0.0,
        f"live={live_pages * page_b}B masked_grid={masked_pages * page_b}B "
        f"null_skip={(masked_pages - live_pages) * page_b}B per decode tick",
    )
    return {
        "gather_dequant_bit_identical": bit_identical,
        "decode_matches_ref": decode_ok,
        "chunked_matches_ref": chunk_ok,
        "timings_us": {"decode_interp": us_d, "chunked_interp": us_c},
        "hbm_per_tick_bytes": {
            "live": live_pages * page_b,
            "masked_grid": masked_pages * page_b,
            "null_page_bytes_skipped": (masked_pages - live_pages) * page_b,
        },
    }


def run(fast=False):
    cfg = BCQConfig()
    cb = codebooks_for(cfg).as_jnp()
    m, k, n = 256, 4096, 1024
    x = llm_like_operand(jax.random.PRNGKey(0), (m, k))
    w = llm_like_operand(jax.random.PRNGKey(1), (n, k))
    report = {"shape": {"m": m, "k": k, "n": n}, "cfg": cfg.tag()}

    fq = jax.jit(lambda v: bcq.fake_quant(v, cb, cfg))
    us, _ = timeit(fq, x)
    emit("kernel_fake_quant_jnp", us, f"shape={m}x{k} {m*k/us:.0f} scalars/us")

    qz = jax.jit(lambda v: ops.quantize(v, cb, cfg, impl="ref"))
    us, pa = timeit(qz, x)
    emit("kernel_quantize_ref", us, f"shape={m}x{k} packed_bits={cfg.bitwidth():.3f}")

    pw = ops.quantize(w, cb, cfg, impl="ref")
    mm = jax.jit(lambda a: ops.matmul(a, pw, cb, cfg, impl="ref"))
    us, _ = timeit(mm, pa)
    emit("kernel_w4a4_matmul_ref", us, f"{m}x{n}x{k} {2*m*n*k/us/1e6:.2f} GFLOP/s-cpu")

    # --- fused single-launch linear vs the two-launch pipeline ------------
    two = jax.jit(lambda v: ops.w4a4_linear(v, pw, cb, cfg, impl="ref"))
    us_two, o_two = timeit(two, x)
    emit("kernel_w4a4_two_launch_ref", us_two, f"{m}x{n}x{k} quantize+matmul launches")
    fused = jax.jit(lambda v: ops.w4a4_linear_fused(v, pw, cb, cfg, impl="ref"))
    us_fused, o_fused = timeit(fused, x)
    bitexact = bool(jnp.all(o_two == o_fused))
    emit(
        "kernel_w4a4_fused_ref", us_fused,
        f"{m}x{n}x{k} single launch bitexact_vs_two_launch={bitexact}",
    )
    report["timings_us"] = {"two_launch_ref": us_two, "fused_ref": us_fused}
    report["fused_bitexact_vs_two_launch"] = bitexact

    # analytic HBM traffic per linear (serving decode + prefill shapes)
    report["hbm_bytes_per_linear"] = {}
    for tag, (bm, bk, bn) in (("decode_128", (128, k, n)), (f"prefill_{m}", (m, k, n))):
        hbm = hbm_bytes_per_linear(bm, bk, bn, cfg)
        report["hbm_bytes_per_linear"][tag] = hbm
        emit(
            f"kernel_hbm_analytic_{tag}", 0.0,
            f"two_launch={hbm['two_launch']['total']}B fused={hbm['fused']['total']}B "
            f"fused_packed_act=0B w_stream={hbm['fused']['packed_weight']}B",
        )

    if not fast:
        us, _ = timeit(
            lambda: ops.quantize(x[:128, :2048], cb, cfg, impl="pallas", tile_m=64, tile_k=512),
            warmup=1, iters=2,
        )
        emit("kernel_quantize_pallas_interp", us, "128x2048 interpret-mode (correctness vehicle)")
        pw_s = ops.quantize(w[:128, :1024], cb, cfg, impl="pallas", tile_m=64, tile_k=512)
        us, _ = timeit(
            lambda: ops.w4a4_linear_fused(
                x[:128, :1024], pw_s, cb, cfg, impl="pallas",
                tile_m=64, tile_n=64, tile_k=512,
            ),
            warmup=1, iters=2,
        )
        emit("kernel_fused_pallas_interp", us, "128x128x1024 interpret-mode (correctness vehicle)")
    bf = jax.jit(lambda a, b: a @ b.T)
    us, _ = timeit(bf, x, w)
    emit("kernel_bf16_matmul_xla", us, f"{m}x{n}x{k} baseline")
    report["timings_us"]["bf16_matmul_xla"] = us

    paged = paged_kernel_smoke(cfg, cb)
    report["paged_kernels"] = paged

    with open("BENCH_kernels.json", "w") as f:
        json.dump(report, f, indent=1, default=float)
    emit("kernel_bench_json", 0.0, "wrote BENCH_kernels.json")
    if not (
        paged["gather_dequant_bit_identical"]
        and paged["decode_matches_ref"]
        and paged["chunked_matches_ref"]
    ):
        raise SystemExit("paged kernels diverged from their refs")


if __name__ == "__main__":
    np.set_printoptions(suppress=True)
    print("name,us_per_call,derived")
    run(fast=True)
