"""Decode-step component profiler (run on real TPU).

Times each piece of the decode step separately to localize the gap vs the
HBM roofline: weight-streaming matmul floor, paged-attention kernel,
sampler, full K=1 step, fused K-step scan, and host batch prep.

Usage: python benchmarks/profile_decode.py [--size 7b] [--bs 16]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _sync(out):
    """JAX returns before the device finishes; a timing that does not end
    here measures the enqueue."""
    jax.block_until_ready(out)


def timeit(fn, *args, n=10, warmup=2, **kw):
    for _ in range(warmup):
        _sync(fn(*args, **kw))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    _sync(out)
    return (time.perf_counter() - t0) / n


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="7b")
    ap.add_argument("--bs", type=int, default=16)
    ap.add_argument("--ctx", type=int, default=256)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--trace", default=None,
                    help="dir for jax.profiler trace of one fused step")
    ap.add_argument("--kv", default="auto",
                    help="cache dtype (e.g. fp8_e5m2; default bf16)")
    ap.add_argument("--blocks", type=int, default=None,
                    help="KV pool size in blocks (default: size-class)")
    args = ap.parse_args()

    import bench
    default_blocks = {"7b": 512, "1b": 2048, "tiny": 4096}[args.size]
    engine = bench.build_engine(args.size, args.bs, 512,
                                args.blocks if args.blocks is not None
                                else default_blocks,
                                quantization="int8" if args.size == "7b"
                                else None,
                                cache_dtype=args.kv)
    runner = engine.worker.model_runner
    caches = engine.worker.cache_engine.device_cache
    model_config = engine.model_config
    hidden = model_config.get_hidden_size()
    vocab = model_config.get_vocab_size()
    nl = model_config.get_num_layers()
    hq = model_config.hf_config.num_attention_heads
    hkv = model_config.get_total_num_kv_heads()
    d = model_config.get_head_size()
    bs_blk = engine.cache_config.block_size
    b = args.bs
    w = max(32, (args.ctx + bs_blk - 1) // bs_blk)

    params = runner.params
    rng = np.random.default_rng(0)

    # --- A. weight-streaming floor: all decode matmuls, no attention ----
    from intellillm_tpu.layers.quantization import qmatmul

    def matmul_chain(params, x):
        for layer in params["layers"]:
            x = x + qmatmul(qmatmul(x, layer["attn"]["qkv"]),
                            layer["attn"]["o"])[..., :hidden] * 0.0 + x * 1e-9
        return x

    # Inspect actual param tree names first
    names = list(params.keys())
    print("param tree top-level keys:", names)
    lay0 = jax.tree.map(lambda x: (x.shape, str(x.dtype)),
                        params["layers"][0] if "layers" in params else None,
                        is_leaf=lambda x: hasattr(x, "shape"))
    print("layer0:", lay0)

    # --- B. paged attention kernel alone --------------------------------
    from intellillm_tpu.ops.pallas.paged_attention import paged_attention
    nb = caches[0][0].shape[0]
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)), jnp.bfloat16)
    tables = jnp.asarray(
        rng.integers(0, nb, (b, w)).astype(np.int32))
    ctx = jnp.full((b,), args.ctx, jnp.int32)
    k_cache, v_cache = caches[0]
    t = timeit(lambda: paged_attention(q, k_cache, v_cache, tables, ctx,
                                       d**-0.5), n=20)
    print(f"paged_attention 1 layer [{b=} {hq=} ctx={args.ctx}]: "
          f"{t*1e6:.0f} us  (x{nl} layers = {t*nl*1e3:.1f} ms)")

    # --- C. sampler alone ------------------------------------------------
    from intellillm_tpu.layers.sampler import sample

    hrow = jnp.asarray(rng.normal(size=(b, hidden)), jnp.bfloat16)

    @jax.jit
    def logits_and_sample(params, hrow, seeds):
        logits = runner.model.compute_logits(params, hrow).astype(jnp.float32)
        return sample(logits, jnp.ones((b,), jnp.float32) * 0.0,
                      jnp.full((b,), -1, jnp.int32),
                      jnp.ones((b,), jnp.float32),
                      jnp.zeros((b,), jnp.float32), seeds,
                      logprob_k=8, num_samples=1,
                      do_topk=False, do_topp=False, do_minp=False)

    seeds = jnp.zeros((b,), jnp.uint32)
    t = timeit(logits_and_sample, params, hrow, seeds, n=20)
    print(f"logits+sample [{b=} vocab={vocab}]: {t*1e3:.2f} ms")

    # --- D. full K=1 decode step (device only) ---------------------------
    token_ids = jnp.asarray(rng.integers(0, vocab, (b, 1)), jnp.int32)
    positions = jnp.full((b, 1), args.ctx - 1, jnp.int32)
    zeros = jnp.zeros((b,), jnp.float32)
    ones = jnp.ones((b,), jnp.float32)
    common = dict(logprob_k=8, do_topk=False, do_topp=False, do_minp=False,
                  do_penalties=False)
    dargs = (params, caches, token_ids, positions, tables, ctx,
             zeros, jnp.full((b,), -1, jnp.int32), ones, zeros, seeds,
             zeros, zeros, ones, None, None)

    packed, caches = runner._jit_decode_single(*dargs, **common)
    jax.block_until_ready(packed)
    # re-make args with fresh caches each call (donation!)
    def run_single():
        nonlocal caches
        p, caches = runner._jit_decode_single(
            params, caches, token_ids, positions, tables, ctx,
            zeros, jnp.full((b,), -1, jnp.int32), ones, zeros, seeds,
            zeros, zeros, ones, None, None, **common)
        return p
    t1 = timeit(run_single, n=10)
    print(f"K=1 decode step: {t1*1e3:.1f} ms -> {b/t1:.0f} tok/s")

    # --- E. fused K-step decode ------------------------------------------
    def run_fused():
        nonlocal caches
        p, caches = runner._jit_decode(
            params, caches, token_ids, positions, tables, ctx,
            zeros, jnp.full((b,), -1, jnp.int32), ones, zeros, seeds,
            zeros, zeros, ones, None, None, num_steps=args.k, **common)
        return p
    tk = timeit(run_fused, n=5)
    print(f"K={args.k} fused decode: {tk*1e3:.1f} ms "
          f"({tk/args.k*1e3:.1f} ms/substep) -> {b*args.k/tk:.0f} tok/s")

    if args.trace:
        with jax.profiler.trace(args.trace):
            p = run_fused()
            jax.block_until_ready(p)
        print("trace written to", args.trace)


if __name__ == "__main__":
    main()
