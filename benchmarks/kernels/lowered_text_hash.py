"""sha256 of the per-head paged kernels' lowered programs at three cells'
shapes, for a described v5e (no chip): the check that a change to shared
kernel code (`ops/pallas/paged_attention.py` `_page_walker`, `_row_update`)
leaves the programs of the cells that run it as they were.

    JAX_PLATFORMS=cpu python benchmarks/kernels/lowered_text_hash.py \
        [--root chipscratch/parent]

Prints, for `paged_attention` (128 rows; SmallThinker 16) and
`ragged_paged_attention` (512 rows) at Mistral's 8x4 fp8, LFM2's 8x4 bf16
and SmallThinker's windowed 4x7 shapes, the hash of
`jax.jit(...).lower(...).as_text()` with each Mosaic kernel's body decoded
from its bytecode and printed WITHOUT source locations: the bytecode holds
every operation's file path and line number, so the raw text differs
between two checkouts of the same code and after any edit above a kernel.
Run it on the parent's tree and on the change, and compare.
"""
from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CASES = {
    "mistral_8x4_fp8": dict(heads=(8, 4), pool="float8_e5m2", width=64,
                            rows=128, blocks=6535, window=False),
    "lfm2_8x4_bf16": dict(heads=(8, 4), pool="bfloat16", width=64,
                          rows=128, blocks=6535, window=False),
    "smallthinker_4x7_window": dict(heads=(4, 7), pool="bfloat16", width=512,
                                    rows=16, blocks=8300, window=True),
}


def without_locations(text: str) -> str:
    """`text` with every `tpu_custom_call` body as MLIR assembly without
    debug info."""
    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    def body(match):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return module.operation.get_asm(enable_debug_info=False)
    out, n = re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, text)
    if not n:
        raise ValueError("no Mosaic kernel body in the lowered text")
    return out


def main(args) -> None:
    sys.path.insert(0, os.path.abspath(args.root))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from intellillm_tpu.ops.pallas import paged_attention as pa
    from intellillm_tpu.ops.pallas import ragged_paged_attention as rpa
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    i32 = jnp.int32
    for name, c in CASES.items():
        (hkv, g), w, dt = c["heads"], c["width"], jnp.dtype(c["pool"])
        pool = spec((c["blocks"], hkv, 16, 128), dt)
        windowed = c["window"]

        def decode(q, k, v, tables, ctx, lo):
            return pa.paged_attention(q, k, v, tables, ctx, 0.088,
                                      return_lse=True,
                                      lo=lo if windowed else None)

        def ragged(q, k_new, v_new, k, v, slots, tables, ctx, starts, lo):
            return rpa.ragged_paged_attention(
                q, k_new, v_new, k, v, slots, tables, ctx, 0.088,
                lo=lo if windowed else None, run_starts=starts)
        b = c["rows"]
        texts = {"paged": jax.jit(decode).lower(
            spec((b, 1, hkv * g, 128), jnp.bfloat16), pool, pool,
            spec((b, w), i32), spec((b, ), i32), spec((b, ), i32)).as_text()}
        b = 512
        texts["ragged"] = jax.jit(ragged, donate_argnums=(3, 4)).lower(
            spec((b, 1, hkv * g, 128), jnp.bfloat16), spec((b, hkv, 128), dt),
            spec((b, hkv, 128), dt), pool, pool, spec((b, ), i32),
            spec((b, w), i32), spec((b, ), i32), spec((b, ), i32),
            spec((b, ), i32)).as_text()
        for kernel, text in texts.items():
            digest = hashlib.sha256(
                without_locations(text).encode()).hexdigest()
            print(name, kernel, digest[:16])


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--root", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", ".."),
        help="checkout to import intellillm_tpu from")
    main(parser.parse_args())
