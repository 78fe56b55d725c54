"""Operations and bytes of `ling-3.0-flash-ep8-l12`'s kernels at its cell's
shapes, from their shapes: the delta rule's two forms at 32 heads of 128
(`_kda_chunk_call` over a 512-row chunk of one prompt, `_kda_step_call` over
16 decode rows; `costs_kda.py` counts them), the latent kernels at 32 heads
over a context of `c` tokens (`_ragged_call_mla` over a chunk's rows,
`_paged_attention_call_mla` over the decode rows; `costs_mla.py`), and a
whole chunk and a whole token-step of the configuration. Kept with the
benchmark (as costs.py is) so that no PR that claims a gain can change the
yardstick. No kernel is new here: the configuration runs the kernels Solar
Open 2 and A.X-K1 brought, at shapes no cell ran them at.

No reader reports a `<kernel>_roofline` metric yet (costs.py says why; a
`benchmark` issue adds the reader). Until then the builder of a PR divides
these by the kernel's time in a `--trace 1` run by hand and writes the share
into PERF.md (`roofline_share`): kernel time from `breakdown.device_ops`,
calls = state or latent layers x steps.

    python3 benchmarks/chip/harness/costs_ling3.py

prints the cell's arithmetic (bytes, operations and the time each bound
gives at the v5e's peaks).
"""
from __future__ import annotations

import json
import os

try:                                   # as a module of the harness package
    from . import costs_kda, costs_mla
except ImportError:                    # as a script
    import costs_kda
    import costs_mla

# The configuration as the cell runs it.
HEADS, HEAD_DIM = 32, 128
KV_LORA_RANK, ROPE_DIM, NOPE_DIM, V_DIM = 512, 64, 128, 128
HIDDEN, DENSE_INTER, MOE_INTER, VOCAB = 2560, 6144, 768, 19648
KDA_LAYERS, MLA_LAYERS, DENSE_LAYERS, MOE_LAYERS = 10, 2, 2, 10
HELD, ROUTER, TOP_K = 64, 512, 8
CHUNK_ROWS, DECODE_ROWS = 512, 16
ACT_BYTES = 2                          # bf16 weights, activations, latent rows


def peaks(device_kind: str = "TPU v5 lite") -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        return json.load(f)["devices"][device_kind]


# --- the four kernels, one call each ----------------------------------------

def kda_chunk(rows: int = CHUNK_ROWS, runs: int = 1, from_zero: int = 0):
    """(operations, bytes) of `_kda_chunk_call` over `rows` rows in `runs`
    runs (a prompt's chunk is one run; its first chunk starts from zero):
    the state read from its slot at the chunk's start and written at its
    end, carried in VMEM between the 16-row sub-chunks."""
    return (costs_kda.kda_flops(rows, HEADS, HEAD_DIM, HEAD_DIM),
            costs_kda.kda_chunk_bytes(rows, runs, HEADS, HEAD_DIM, HEAD_DIM,
                                      from_zero))


def kda_step(rows: int = DECODE_ROWS):
    """(operations, bytes) of `_kda_step_call` over `rows` live rows."""
    return (costs_kda.kda_flops(rows, HEADS, HEAD_DIM, HEAD_DIM),
            costs_kda.kda_step_bytes(rows, HEADS, HEAD_DIM, HEAD_DIM))


def mla_ragged(context: int, rows: int = CHUNK_ROWS):
    """(operations, bytes) of `_ragged_call_mla` over a chunk of `rows`
    rows that ends at `context` tokens: row i attends to `context - rows +
    i + 1` tokens. Bytes as the kernel needs them at the least: the run's
    context read ONCE for all its rows (the q-tiled walk of PR 36 / 46
    reads it once a tile of rows; what is over this is the kernel's to
    lose), the rows' slots written, the queries in and the attended
    latents out."""
    lens = [context - rows + i + 1 for i in range(rows)]
    flops = costs_mla.latent_walk_flops(lens, HEADS, KV_LORA_RANK, ROPE_DIM)
    nbytes = (costs_mla.latent_walk_bytes([context], KV_LORA_RANK, ROPE_DIM,
                                          ACT_BYTES)
              + rows * costs_mla.stored_width(KV_LORA_RANK, ROPE_DIM)
              * ACT_BYTES
              + costs_mla.query_bytes(rows, HEADS, KV_LORA_RANK, ROPE_DIM,
                                      ACT_BYTES))
    return flops, nbytes


def mla_paged(context: int, rows: int = DECODE_ROWS):
    """(operations, bytes) of `_paged_attention_call_mla` over `rows`
    decode rows of `context` tokens each: every row walks its own latent
    rows once, at their stored 640 lanes."""
    lens = [context] * rows
    return (costs_mla.latent_walk_flops(lens, HEADS, KV_LORA_RANK, ROPE_DIM),
            costs_mla.latent_walk_bytes(lens, KV_LORA_RANK, ROPE_DIM,
                                        ACT_BYTES)
            + costs_mla.query_bytes(rows, HEADS, KV_LORA_RANK, ROPE_DIM,
                                    ACT_BYTES))


def roofline_s(flops: float, nbytes: float, device_kind="TPU v5 lite"):
    """The time the slower of the two bounds gives."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])


def roofline_share(flops: float, nbytes: float, measured_s: float,
                   device_kind="TPU v5 lite") -> float:
    """% of its roofline a kernel reached: the bound's time over the
    measured time a call."""
    return 100.0 * roofline_s(flops, nbytes, device_kind) / measured_s


# --- the weights --------------------------------------------------------------

def weight_params() -> dict:
    """Parameters by part, as the cell holds them."""
    hd = HEADS * HEAD_DIM
    kda = (3 * HIDDEN * hd + 2 * HIDDEN * hd + hd * HIDDEN       # qkv, f, g, o
           + HIDDEN * HEADS + 4 * 3 * hd + hd + HEADS + HEAD_DIM)
    mla = (HIDDEN * HEADS * (NOPE_DIM + ROPE_DIM)
           + HIDDEN * (KV_LORA_RANK + ROPE_DIM) + KV_LORA_RANK
           + HEADS * KV_LORA_RANK * (NOPE_DIM + V_DIM)
           + HEADS * V_DIM * HIDDEN + HIDDEN * HEADS)
    expert = 3 * HIDDEN * MOE_INTER
    return {"kda_mixer": kda, "mla_mixer": mla,
            "dense_mlp": 3 * HIDDEN * DENSE_INTER, "expert": expert,
            "moe_layer_outside_experts": expert + HIDDEN * ROUTER + ROUTER,
            "embedding": VOCAB * HIDDEN, "head": VOCAB * HIDDEN,
            "norms": (2 * (KDA_LAYERS + MLA_LAYERS) + 1) * HIDDEN}


def weights_total_bytes() -> int:
    w = weight_params()
    return ACT_BYTES * (
        KDA_LAYERS * w["kda_mixer"] + MLA_LAYERS * w["mla_mixer"]
        + DENSE_LAYERS * w["dense_mlp"]
        + MOE_LAYERS * (HELD * w["expert"] + w["moe_layer_outside_experts"])
        + w["embedding"] + w["head"] + w["norms"])


def expected_visited(rows: int) -> float:
    """Held experts that `rows` live rows are expected to choose between
    them, were the choices uniform over the router."""
    return HELD * (1.0 - (1.0 - TOP_K / ROUTER) ** rows)


# --- a whole chunk and a whole token-step -------------------------------------

def chunk_step(context: int, rows: int = CHUNK_ROWS) -> dict:
    """A `mixed` step that is one 512-row chunk of a prompt ending at
    `context` tokens: bytes and operations by part. Every weight is read
    once (every held expert has a row at 512 rows x 8 choices)."""
    w = weight_params()
    weights = weights_total_bytes() - ACT_BYTES * w["embedding"]
    dense_flops = 2 * rows * (
        KDA_LAYERS * w["kda_mixer"] + MLA_LAYERS * w["mla_mixer"]
        + DENSE_LAYERS * w["dense_mlp"]
        + MOE_LAYERS * (w["moe_layer_outside_experts"]
                        + TOP_K * HELD / ROUTER * w["expert"]))
    kf, kb = kda_chunk(rows)
    mf, mb = mla_ragged(context, rows)
    return {"weights_bytes": weights, "matmul_flops": dense_flops,
            "kda_flops": KDA_LAYERS * kf, "kda_bytes": KDA_LAYERS * kb,
            "mla_flops": MLA_LAYERS * mf, "mla_bytes": MLA_LAYERS * mb}


def token_step(context: int, rows: int = DECODE_ROWS) -> dict:
    """One substep of a decode program over `rows` live rows of `context`
    tokens each: what it moves. The non-expert weights and the head once,
    the experts the rows chose between them, every row's state in and out,
    every row's latent rows."""
    w = weight_params()
    visited = expected_visited(rows)
    non_expert = ACT_BYTES * (
        KDA_LAYERS * w["kda_mixer"] + MLA_LAYERS * w["mla_mixer"]
        + DENSE_LAYERS * w["dense_mlp"]
        + MOE_LAYERS * w["moe_layer_outside_experts"] + w["head"]
        + w["norms"])
    _, kb = kda_step(rows)
    _, mb = mla_paged(context, rows)
    return {"non_expert_weight_bytes": non_expert,
            "visited_experts_a_layer": visited,
            "expert_weight_bytes": ACT_BYTES * MOE_LAYERS * visited
            * w["expert"],
            "state_bytes": KDA_LAYERS * kb, "latent_bytes": MLA_LAYERS * mb}


def main() -> None:
    p = peaks()
    hbm = p["hbm_bytes_per_s"]
    print(json.dumps({"weights_total_gb": weights_total_bytes() / 1e9}))
    for context in (512, 3072, 5632, 7168):
        c = chunk_step(context)
        print(json.dumps({
            "chunk_ending_at": context,
            "weights_ms": 1e3 * c["weights_bytes"] / hbm,
            "matmul_ms_at_peak": 1e3 * c["matmul_flops"] / p["bf16_flops"],
            "kda_chunk_call_roofline_us": 1e6 * roofline_s(*kda_chunk()),
            "ragged_call_mla_roofline_us": 1e6 * roofline_s(
                *mla_ragged(context))}))
    for context in (340, 6144):
        t = token_step(context)
        total = sum(v for k, v in t.items() if k.endswith("_bytes"))
        print(json.dumps({
            "token_step_at_context": context, **t, "total_bytes": total,
            "ms_at_hbm_rate": 1e3 * total / hbm,
            "state_share": t["state_bytes"] / total,
            "latent_share": t["latent_bytes"] / total,
            "kda_step_call_roofline_us": 1e6 * roofline_s(*kda_step()),
            "paged_attention_call_mla_roofline_us": 1e6 * roofline_s(
                *mla_paged(context))}))


if __name__ == "__main__":
    main()
