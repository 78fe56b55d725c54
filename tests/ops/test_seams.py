"""Every Pallas seam answers one question (`ops/dispatch.use_kernel`) and
records the path it *took*.

A seam is a function that owns one kernel and its jnp oracle. For each:
forced on, a shape its kernel supports runs the kernel (interpret mode
here) under `pallas:<name>`, and a shape it does not (a head size of 64,
an adapter or group width off the lane tiling) runs the oracle under
`reference:<name>` and returns the oracle's answer; forced off, the oracle
under `reference:<name>`. The environment variables that used to move
single seams are set throughout and move nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from intellillm_tpu.obs import get_compile_tracker

NB, BS, W = 32, 16, 4
CTX = (1, 17, 40, 33)


def _tables(rng):
    b = len(CTX)
    tables = rng.permutation(NB)[:b * W].reshape(b, W).astype(np.int32)
    slots = [int(tables[i, (c - 1) // BS]) * BS + (c - 1) % BS
             for i, c in enumerate(CTX)]
    return (jnp.asarray(np.asarray(slots, np.int32)), jnp.asarray(tables),
            jnp.asarray(np.asarray(CTX, np.int32)))


def _normal(rng, *shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


def _per_head(rng, supported):
    d, hq, hkv, b = (128 if supported else 64), 4, 2, len(CTX)
    return d, (_normal(rng, b, 1, hq, d), _normal(rng, b, hkv, d),
               _normal(rng, b, hkv, d), _normal(rng, NB, hkv, BS, d),
               _normal(rng, NB, hkv, BS, d)) + _tables(rng)


def paged(rng, supported):
    from intellillm_tpu.ops.ragged_attention import paged_pool_attention
    d, (q, _, _, kc, vc, _, tables, ctx) = _per_head(rng, supported)
    return lambda: paged_pool_attention(q, kc, vc, tables, ctx, d**-0.5)


def ragged(rng, supported):
    from intellillm_tpu.ops.ragged_attention import ragged_fused_attention
    d, args = _per_head(rng, supported)
    return lambda: ragged_fused_attention(*args, d**-0.5)


def _latent(rng, supported):
    # Stored width 256, of which the value is the first 128 lanes (or 64:
    # not whole tiles).
    dk, dv, h, b = 256, (128 if supported else 64), 4, len(CTX)
    return dv, (_normal(rng, b, h, dk), _normal(rng, b, dk),
                _normal(rng, NB, 1, BS, dk)) + _tables(rng)


def mla_fused(rng, supported):
    from intellillm_tpu.ops.mla_attention import mla_fused_attention
    dv, args = _latent(rng, supported)
    return lambda: mla_fused_attention(*args, 0.1, dv)


def mla_pool(rng, supported):
    from intellillm_tpu.ops.mla_attention import mla_pool_attention
    dv, (q, _, pool, _, tables, ctx) = _latent(rng, supported)
    return lambda: mla_pool_attention(q, pool, tables, ctx, 0.1, dv)


def bgmv(rng, supported):
    from intellillm_tpu.lora.layers import lora_delta
    din, rank, dout, s = (256 if supported else 64), 16, 128, 4
    a = rng.normal(size=(s, din, rank)).astype(np.float32)
    b = rng.normal(size=(s, rank, dout)).astype(np.float32)
    a[0], b[0] = 0.0, 0.0
    x = _normal(rng, 8, 1, din)
    slots = jnp.asarray(np.asarray([0, 1, 2, 3, 0, 2, 1, 0], np.int32))
    return lambda: lora_delta(x, jnp.asarray(a), jnp.asarray(b), slots)


def int4(rng, supported):
    from intellillm_tpu.layers.quantization import qmatmul, quantize_int4
    # Groups of 32 rows tile the kernel's K block; groups of 96 do not.
    in_, gs = (256, 32) if supported else (192, 96)
    w = {k: jnp.asarray(v) for k, v in quantize_int4(
        rng.standard_normal((in_, 384)).astype(np.float32), gs).items()}
    x = _normal(rng, 3, in_).astype(jnp.bfloat16)
    return lambda: qmatmul(x, w)


def int4_lut(rng, supported):
    from intellillm_tpu.layers.quantization import qmatmul
    # A 16-entry codebook a channel is the kernel's; 8 entries (3-bit
    # indices in the same nibbles) are not.
    entries, in2, out = (16 if supported else 8), 128, 256
    idx = rng.integers(0, entries, (2 * in2, out)).astype(np.uint8)
    w = {"q4lut": jnp.asarray(idx[0::2] | (idx[1::2] << 4)),
         "lut": _normal(rng, entries, out)}
    x = _normal(rng, 3, 2 * in2).astype(jnp.bfloat16)
    return lambda: qmatmul(x, w)


def grouped_ffn(rng, supported):
    from intellillm_tpu.ops.grouped_ffn import grouped_expert_ffn
    # Whole 128-lane tiles of hidden width are the kernels'; 64 lanes are
    # not. Four experts over 64 sorted rows in tiles of 16: one with no
    # row, groups that start inside a tile, 8 rows past the last group
    # that are nobody's (the two paths need not agree on them).
    d, i, n, m = (128 if supported else 64), 128, 4, 64
    xs = _normal(rng, m, d)
    w1, w2, w3 = (_normal(rng, n, i, d) * 0.1 for _ in range(3))
    sizes = jnp.asarray(np.asarray([20, 0, 30, 6], np.int32))
    return lambda: grouped_expert_ffn(xs, w1, w2, w3, sizes, 16)[:56]


# seam -> (the label's name, (rtol, atol) of the kernel against the oracle;
# the int4 pair's is tests/kernels/test_quant_matmul.py's, bf16 products)
SEAMS = {paged: ("paged", (2e-3, 2e-3)), ragged: ("ragged", (2e-3, 2e-3)),
         mla_fused: ("mla", (2e-3, 2e-3)), mla_pool: ("mla", (2e-3, 2e-3)),
         bgmv: ("bgmv", (2e-4, 2e-4)), int4: ("int4", (0.02, 0.15)),
         int4_lut: ("int4_lut", (0.02, 0.15)),
         grouped_ffn: ("grouped_ffn", (2e-4, 2e-4))}
CASES = {"forced_on_supported": (True, True, "pallas"),
         "forced_on_unsupported": (True, False, "reference"),
         "forced_off": (False, True, "reference")}


def _counts(name):
    seen = get_compile_tracker().snapshot()["kernel_dispatch"]
    return {p: seen.get(f"{p}:{name}", 0) for p in ("pallas", "reference")}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seam", SEAMS, ids=lambda s: s.__name__)
def test_seam_records_the_path_it_takes(seam, case, tpu_interpret,
                                        force_pallas, monkeypatch):
    # The per-seam switches and the heads-per-block knob that are gone.
    for gone, value in (("PALLAS_RAGGED", "0"), ("PALLAS_BGMV", "0"),
                        ("PAGED_V4", "0"), ("PAGED_HP", "8")):
        monkeypatch.setenv("INTELLILLM_" + gone, value)
    name, (rtol, atol) = SEAMS[seam]
    forced, supported, path = CASES[case]
    call = seam(np.random.default_rng(5), supported)

    force_pallas(forced)
    before = _counts(name)
    got = jax.block_until_ready(call())
    after = _counts(name)
    other = "reference" if path == "pallas" else "pallas"
    assert after[path] - before[path] == 1
    assert after[other] == before[other]

    force_pallas(False)
    oracle = call()
    for g, o in zip(jax.tree.leaves(got), jax.tree.leaves(oracle)):
        g, o = np.asarray(g, np.float32), np.asarray(o, np.float32)
        if path == "reference":
            np.testing.assert_array_equal(g, o)
        else:
            np.testing.assert_allclose(g, o, rtol=rtol, atol=atol)


# --- who may know a kernel -------------------------------------------------

def _package_sources():
    import pathlib

    import intellillm_tpu
    root = pathlib.Path(intellillm_tpu.__file__).parent
    for path in sorted(root.rglob("*.py")):
        yield path.relative_to(root).as_posix(), path.read_text()


def _imported_modules(source):
    import ast
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_only_seams_import_the_kernels():
    """`ops.pallas` is imported under `ops/` and by the two seams that
    live beside their layers; `layers/attention.py` asks `ops/dispatch`
    nothing and the runner names no kernel module."""
    seams_elsewhere = {"lora/layers.py", "layers/quantization.py"}
    importers = set()
    for rel, source in _package_sources():
        modules = set(_imported_modules(source))
        if any(m.startswith("intellillm_tpu.ops.pallas") for m in modules):
            importers.add(rel)
        if rel == "layers/attention.py":
            assert not any(m.startswith("intellillm_tpu.ops.dispatch")
                           for m in modules)
    assert importers, "the walk found no kernel import at all"
    outside = {r for r in importers if not r.startswith("ops/")}
    assert outside <= seams_elsewhere, outside - seams_elsewhere


def test_one_selector_writes_the_dispatch_record():
    callers = {rel for rel, source in _package_sources()
               if "record_kernel_dispatch(" in source
               and not rel.startswith("obs/")}
    assert callers == {"ops/dispatch.py"}


def test_attention_metadata_has_no_phase():
    """The static (retrace-on-change) fields are the staged switch and
    the mesh: no prompt phase, no prefix flag, no sequence-parallel mode."""
    import dataclasses

    from intellillm_tpu.layers.attention import AttentionMetadata
    static = {f.name for f in dataclasses.fields(AttentionMetadata)
              if not f.metadata.get("pytree_node", True)}
    assert static == {"staged", "mesh"}
