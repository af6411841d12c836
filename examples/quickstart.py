"""Quickstart: LO-BCQ in five minutes.

1. Fit LO-BCQ codebooks on a heavy-tailed operand (k-means++ init +
   alternating block-clustering / Lloyd-Max — paper §2.2).
2. Show the non-increasing MSE trajectory (§A.2 invariant).
3. Encode → packed 4.5-bit buffers → decode; compare NMSE against the
   MX4 / MXFP4 / VSQ baselines at matched bitwidth (Fig. 4/9 analogue).
4. Run the fused W4A4 linear (one Pallas kernel: activation encode in
   VMEM, weight decode, GEMM; interpret mode on CPU) against the
   fake-quant reference.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import baselines, bcq
from repro.core.bcq import BCQConfig, fit_lobcq
from repro.kernels import ops

def main():
    key = jax.random.PRNGKey(0)
    # LLM-activation-like operand: gaussian bulk + rare large outliers
    x = jax.random.normal(key, (512, 1024))
    mask = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.005, x.shape)
    x = jnp.where(mask, x * 20.0, x)

    cfg = BCQConfig(block_len=8, array_len=64, n_codebooks=8)  # 4.5 bits
    print(f"config {cfg.tag()}  bitwidth {cfg.bitwidth():.4f} bits/scalar")

    cbs = fit_lobcq(x, cfg, iters=20)
    print("MSE trajectory (non-increasing):",
          " ".join(f"{h:.4f}" for h in cbs.history[:8]), "...")
    assert all(b <= a + 1e-9 for a, b in zip(cbs.history, cbs.history[1:]))
    print(f"codebooks: {cfg.n_codebooks}×{cfg.n_entries} INT6 entries "
          f"({cbs.nbytes():.0f} bytes total — fits in any cache)")

    cb = cbs.as_jnp()
    xq = bcq.fake_quant(x, cb, cfg)
    print(f"\nNMSE  LO-BCQ(4.5b)  : {float(bcq.quantization_nmse(x, xq)):.5f}")
    for name, (fn, bits) in baselines.BASELINES.items():
        print(f"NMSE  {name:14s}({bits}b): {float(bcq.quantization_nmse(x, fn(x))):.5f}")

    # W4A4 linear through the fused Pallas kernel (interpret on CPU):
    # raw activations in, weights pre-packed
    w = jax.random.normal(jax.random.fold_in(key, 2), (256, 1024))
    pw = ops.quantize(w, cb, cfg)
    out = ops.w4a4_linear_fused(
        x[:64], pw, cb, cfg, impl="pallas", tile_m=64, tile_n=64, tile_k=512
    )
    ref = bcq.fake_quant(x[:64], cb, cfg) @ bcq.fake_quant(w, cb, cfg).T
    err = float(jnp.max(jnp.abs(out - ref)))
    print(f"\nfused W4A4 linear vs fake-quant reference: max |Δ| = {err:.2e}")
    storage = (pw.idx_packed.size + pw.sel_packed.size + pw.inv_scale.size) / w.size
    print(f"packed weight storage: {storage*8:.2f} bits/scalar (incl. f32 staging scales)")

if __name__ == "__main__":
    main()
