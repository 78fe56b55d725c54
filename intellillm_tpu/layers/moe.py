"""Mixture-of-Experts feed-forward.

Role parity: reference `vllm/model_executor/layers/fused_moe.py` (Triton
grouped-GEMM over experts + CUDA `moe_align_block_size`,
`csrc/moe_align_block_size_kernels.cu`). TPU redesign, two forms of one
sum (every routed choice of a live row computed, none dropped or clipped;
bf16 products, float32 accumulation and combine):

- `moe_ffn_dense` — every held expert over every row, combined by the
  routing weights, in chunks of at most 256 rows. Its arithmetic
  intensity is one FLOP a weight byte a row, and the v5e's ridge is
  197 TFLOP/s / 819 GB/s = 240: a step of up to 256 rows is bound by
  reading the stacks, which no form avoids once every expert is chosen
  by some row, and dense does it with no sort and no gather.
- `moe_ffn_grouped` — the routed rows only, and of them the rows that
  are a sequence's (`live`: a step's pad rows choose nothing). The
  token-choices are placed in expert order (a count and a prefix sum: the
  keys are expert ids), the rows gathered once, and
  `ops/grouped_ffn.grouped_expert_ffn` runs gate, up, activation and down
  over tiles of 128 sorted rows, each tile multiplied by the one or two
  experts whose rows it holds: no padding block an expert, an expert's
  weights read about once. The choice's
  weight is applied to the float32 output row and a token's rows summed
  in float32.

`moe_ffn` picks the form by the shapes it is handed (`grouped_rows`): the
grouped form for more rows than one dense chunk holds (`DENSE_ROWS`: a
second chunk reads every stack a second time and multiplies N/K times the
routed rows), and for so few rows that a good share of the experts is
chosen by none of them (`CHOSEN_SHARE_CUT`: the grouped kernels never copy
the weights of an expert without a row, the dense form reads every stack
whole); the dense form between the two.

Stored layout of the expert stacks: all three are `[N, I, D]` (experts,
inner, hidden), so `w1` (gate) and `w3` (up) are the checkpoint's own
`[out, in]` matrices stacked, and their matmuls contract on the last axis.
That is the layout XLA's TPU layout assignment wants for them whenever the
expert einsums sit inside a loop (the chunk `while` of a prefill step, the
K-loop of `decode_fused`): handed `[N, D, I]` it copied both stacks, in
every `mixed` step and for the life of the decode programs
(tests/test_tpu_compile.py `test_moe_dense_copies_no_expert_stack` holds
the compiled text to no such copy).

The gate's activation is a parameter of all three (`activation`: "silu",
the default and what every family but one uses, or "relu": a ReGLU expert,
`relu(gate) * up`), static, so that "silu" traces what it always did.

Routing is a parameter (`Routing`): the default matches HF Mixtral
(softmax over ALL experts → top-k → renormalize the selected weights);
sigmoid scores, group-limited selection, a scaling factor and an
expert-parallel share (a stack that holds fewer experts than the router
has outputs) are the other settings.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from intellillm_tpu.ops.grouped_ffn import (ROW_TILE, gate_activation,
                                            grouped_expert_ffn)
from intellillm_tpu.utils import cdiv

# Rows of one bf16 sublane tile: a chunk of the dense path is padded to a
# multiple of it and by no more.
_ROW_TILE = 16

# The most rows a step may have and stay dense: one dense chunk. Up to here
# the dense form stands at its weight read (the chip's ridge is 240 rows); a
# second chunk reads every stack again and multiplies N/K times the routed
# rows. On the chip (benchmarks/kernels/benchmark_grouped_ffn.py, PERF.md
# section 6): at 256 rows dense is ahead by 8 % (32 experts top-4) and 14 %
# (12 held of 192, top-8) and 3 % behind at 64 experts top-6; at 384 and
# 512 rows grouped is ahead by 27 to 44 % at all three.
DENSE_ROWS = 256

# The expected share of the router's experts that some row of a step
# chooses, under which a step of up to DENSE_ROWS rows takes the grouped
# form all the same: the dense form reads every held stack, the grouped
# kernels only the experts that have a row, at 1.09 to 1.12 times those
# bytes' time at 819 GB/s plus ~16 us of sort, gather and combine. On the
# chip (benchmarks/kernels/benchmark_grouped_ffn.py --rows
# 1,4,8,16,32,64,128; PERF.md section 6, PR 38), us a layer-step dense |
# grouped (held experts chosen):
#   64 ReGLU experts of 768 x 2560, top-6: 1 row 1009 | 118 (6), 4 rows
#     1005 | 289 (17), 8 rows 1005 | 616 (38), 16 rows 1005 | 790 (49),
#     32 rows 1007 | 992 (61), 64 rows 1010 | 1065, 128 rows 1013 | 1114;
#   64 experts of 1408 x 2048, top-6: 1 row 1473 | 165, 16 rows 1471 |
#     1038 (44), 32 rows 1472 | 1470 (62), 128 rows 1480 | 1605;
#   32 experts of 1792 x 2048, top-4: 8 rows 937 | 668 (22), 16 rows 937 |
#     814 (27), 32 rows 938 | 962 (32), 128 rows 944 | 1053;
#   12 held of 192, 2048 x 7168, top-8 in 4 of 8 groups: 16 rows 1415 |
#     759 (6), 32 rows 1424 | 1212 (10), 64 rows 1433 | 1412 (11), 128
#     rows 1454 | 1598 (12).
# The forms cross where ~0.96 of the held experts are in fact chosen
# (grouped / dense = the share chosen + 0.02 to 0.04); the cut stands under
# that by what a step's count spreads about its expectation, so that the
# grouped form is taken where it is ahead by 7 % and more.
CHOSEN_SHARE_CUT = 0.90


def chosen_share(t: int, top_k: int, router_width: int) -> float:
    """The share of the router's experts that `t` rows choosing `top_k`
    of `router_width` each are expected to choose between them, were the
    choices uniform and independent (a held share sees the same share of
    its own experts)."""
    return 1.0 - (1.0 - top_k / router_width) ** t


def grouped_rows(t: int, top_k: int, router_width: int) -> bool:
    """Whether a step of `t` rows takes the grouped form: the rule of
    `moe_ffn`, by the shapes alone (the runner's counter asks it too)."""
    return (t > DENSE_ROWS
            or chosen_share(t, top_k, router_width) < CHOSEN_SHARE_CUT)


@dataclasses.dataclass(frozen=True)
class Routing:
    """How the router's scores become (weights, experts), and which of the
    router's experts the stack handed to `moe_ffn` holds.

    - `scoring`: "softmax" over all experts (Mixtral, DeepSeek-MoE) or
      "sigmoid" of each expert's logit alone.
    - `n_group` / `topk_group`: group-limited selection. The experts are
      `n_group` equal consecutive groups, a group's score is its largest
      expert score (`group_score_top` 1) or the sum of its
      `group_score_top` largest (2: DeepSeek-V3's `noaux_tc`), only
      experts of the best `topk_group` groups may be chosen. 1 / 1 = no
      limit.
    - `renormalize`: chosen weights divided by their sum (floored at
      1e-9), or by their sum plus `sum_eps` where a family adds an epsilon
      to it; `scale`: then multiplied by it (`routed_scaling_factor`).
    - `select_bias`: the layer passes a per-expert bias to `_route`; it is
      added to the scores for the CHOICE of experts (after the group
      limit's own scores, which use it too) and is no part of their
      weights.
    - `held_start`: expert-parallel share. The stack's N experts are the
      router's outputs `[held_start, held_start + N)`. Routing, the choice
      of top-k and the normalisation are over ALL of the router's outputs;
      only held experts are computed, and what the absent ones would add
      is left out (it is another chip's part of the sum).
    """
    scoring: str = "softmax"
    renormalize: bool = True
    n_group: int = 1
    topk_group: int = 1
    scale: float = 1.0
    held_start: int = 0
    select_bias: bool = False
    sum_eps: float = 0.0
    group_score_top: int = 1


_SOFTMAX_TOP_K = Routing()


def _route(x: jnp.ndarray, gate_w: jnp.ndarray, top_k: int,
           routing: Routing = _SOFTMAX_TOP_K, select_bias=None):
    """(weights [T, K] f32, experts [T, K] i32) over the router's whole
    width. `select_bias` [N] is the selection bias of a routing that has
    one (`routing.select_bias`)."""
    router_logits = (x.astype(jnp.float32) @ gate_w.astype(jnp.float32))
    if routing.scoring == "softmax":
        weights = jax.nn.softmax(router_logits, axis=-1)      # [T, N]
    elif routing.scoring == "sigmoid":
        weights = jax.nn.sigmoid(router_logits)
    else:
        raise ValueError(f"unknown router scoring {routing.scoring!r}")
    choose_from = weights
    if routing.select_bias:
        choose_from = weights + select_bias.astype(jnp.float32)
    if routing.n_group > 1:
        t, n = weights.shape
        per = n // routing.n_group
        grouped = choose_from.reshape(t, routing.n_group, per)
        if routing.group_score_top == 1:
            group_score = grouped.max(-1)
        else:
            group_score = jax.lax.top_k(
                grouped, routing.group_score_top)[0].sum(-1)
        _, best = jax.lax.top_k(group_score, routing.topk_group)
        keep = jnp.zeros((t, routing.n_group), bool).at[
            jnp.arange(t)[:, None], best].set(True)
        # Scores are positive (sigmoid, softmax): -1 never wins a top-k
        # against an expert of a kept group.
        choose_from = jnp.where(jnp.repeat(keep, per, axis=1), choose_from,
                                -jnp.inf if routing.select_bias else -1.0)
    _, topi = jax.lax.top_k(choose_from, top_k)               # [T, K]
    topw = jnp.take_along_axis(weights, topi, axis=1)
    if routing.renormalize:
        total = topw.sum(-1, keepdims=True)
        topw = topw / (total + routing.sum_eps if routing.sum_eps
                       else jnp.maximum(total, 1e-9))
    if routing.scale != 1.0:
        topw = topw * routing.scale
    return topw, topi


def held_assignments(topi: jnp.ndarray, n_held: int,
                     held_start: int) -> jnp.ndarray:
    """[T] i32: how many of each token's chosen experts this stack holds
    (the numerator of intellillm_moe_assignments_total{held="here"})."""
    local = topi - held_start
    return ((local >= 0) & (local < n_held)).sum(-1).astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class ExpertShare:
    """The routed experts one chip holds, in `n_moe_layers` layers of
    `n_held` each, of a router `ep_size` times as wide: the experts
    `[ep_rank * n_held, (ep_rank + 1) * n_held)`. A whole model is the
    share 0 of 1. A family builds its `Routing` from `held_start` and its
    own scoring fields."""
    n_held: int
    top_k: int
    n_moe_layers: int
    ep_size: int = 1
    ep_rank: int = 0

    def __post_init__(self):
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank {self.ep_rank} outside ep_size "
                             f"{self.ep_size}")

    @classmethod
    def from_config(cls, cfg, n_held: int, top_k: int, n_moe_layers: int):
        """The share a config's `ep_size` / `ep_rank` name, for every
        family that has one (their config shims and model files point
        here): `ep_size` chips share each layer's experts (default 1: the
        whole model), this chip is rank `ep_rank` (default 0) and holds the
        `n_held` experts `[ep_rank * n_held, (ep_rank + 1) * n_held)`; the
        family's own expert-count key then counts the experts held HERE
        and the router keeps its published width `n_held * ep_size`."""
        return cls(n_held, top_k, n_moe_layers,
                   ep_size=int(getattr(cfg, "ep_size", 1) or 1),
                   ep_rank=int(getattr(cfg, "ep_rank", 0) or 0))

    @property
    def n_router(self) -> int:
        return self.n_held * self.ep_size

    @property
    def held_start(self) -> int:
        return self.ep_rank * self.n_held

    @property
    def held(self) -> range:
        """The router's outputs held here: what a loader takes of a
        checkpoint's experts."""
        return range(self.held_start, self.held_start + self.n_held)

    @property
    def experts_held(self) -> int:
        return self.n_held * self.n_moe_layers

    @property
    def moe_choices_per_token(self) -> int:
        """A share reports how many of each token's choices it holds
        (worker/model_runner.py `_pack`); the whole model has nothing to
        report: 0."""
        return self.top_k * self.n_moe_layers if self.ep_size > 1 else 0

    def report_through(self, model) -> None:
        """Sets on `model` what the runner reads of one whose calls return
        `ExpertCounts.counters()` beside their result
        (worker/model_runner.py `_finalize`)."""
        model.counts_expert_visits = True
        model.moe_choices_per_token = self.moe_choices_per_token

    def param_shapes(self, hidden: int, inter: int, select_bias: bool = False,
                     shared_inter: int = 0) -> dict:
        """A routed layer's entries of a model's `_param_shapes`, in the
        order the families' seeded draws walk them."""
        stack = (self.n_held, inter, hidden)
        shapes = {"gate_router": (hidden, self.n_router)}
        if select_bias:
            shapes["expert_bias"] = (self.n_router, )
        shapes.update({"w1": stack, "w2": stack, "w3": stack})
        if shared_inter:
            shapes.update({"shared_gate": (hidden, shared_inter),
                           "shared_up": (hidden, shared_inter),
                           "shared_down": (shared_inter, hidden)})
        return shapes


# The counts of a layer without routed experts (A.X-K1's first, LFM2's
# first two), for `ExpertCounts.add`: no expert read, nothing held.
DENSE_LAYER = (0, None)


class ExpertCounts:
    """One call's counters, summed over its layers."""

    def __init__(self, share: ExpertShare):
        self.share = share
        self.visited = jnp.int32(0)
        self.held = None

    def add(self, counts) -> None:
        """`counts`: what `routed_experts` returned beside its output."""
        visited, held = counts
        self.visited += visited
        if held is not None:
            self.held = held if self.held is None else self.held + held

    def counters(self) -> dict:
        """What a model's `__call__` returns beside its result, for the
        runner's `_pack`: "expert_visits" [2] i32 (held experts whose
        weights a layer read, held experts) and, from a share, "held"
        [B, L] i32 (how many of a token's choices are held here)."""
        counters = {"expert_visits": jnp.stack(
            [self.visited, jnp.int32(self.share.experts_held)])}
        if self.share.moe_choices_per_token:
            counters["held"] = self.held
        return counters


def route(h: jnp.ndarray, lp, share: ExpertShare, routing: Routing):
    """`_route` of a layer's params `lp` over the tokens of `h` [..., D],
    for `routed_experts`: a family whose router reads another input than
    its experts (SmallThinker: the layer's normalised input) calls it
    there."""
    with jax.named_scope("moe_router"):
        return _route(h.reshape(-1, h.shape[-1]), lp["gate_router"],
                      share.top_k, routing=routing,
                      select_bias=lp.get("expert_bias"))


def live_tokens(live_rows, l: int):
    """`AttentionMetadata.live_rows` [B] as `moe_ffn`'s `live` over the
    [B * L] tokens of a flattened [B, L, D] (None stays None)."""
    return None if live_rows is None else jnp.repeat(live_rows, l)


def routed_experts(h: jnp.ndarray, lp, share: ExpertShare, routing: Routing,
                   mesh=None, routed=None, activation: str = "silu",
                   stacks=None, live=None):
    """A layer's routed-experts block over `h` [B, L, D] (the post-mixer
    norm's output): router, held experts, and the shared expert where `lp`
    has one ("shared_gate"), all with the gate `activation`. Returns the
    block's output [B, L, D] and the layer's counts for
    `ExpertCounts.add`: (held experts read, and from a share how many of
    each token's choices are held here [B, L], else None). `stacks`: for
    a model that walks its layers in a `lax.scan`, (`w1`, `w2`, `w3`, the
    layer's place among the routed layers): the stacks of EVERY routed
    layer, `[layers * n_held, I, D]`, in place of `lp`'s own (`moe_ffn`
    `held_at`). `live` [B] bool: the step's rows that are a sequence's
    (`AttentionMetadata.live_rows`; `moe_ffn` says what it decides)."""
    b, l, e = h.shape
    flat = h.reshape(b * l, e)
    if routed is None:
        routed = route(flat, lp, share, routing)
    if stacks is None:
        w1, w2, w3, held_at = lp["w1"], lp["w2"], lp["w3"], None
    else:
        w1, w2, w3, place = stacks
        held_at = (place * share.n_held, share.n_held)
    out, visited = moe_ffn(
        flat, lp["gate_router"], w1, w2, w3, share.top_k,
        routing=routing, routed=routed, mesh=mesh, activation=activation,
        count_visited=True, held_at=held_at, live=live_tokens(live, l))
    held = None
    if share.moe_choices_per_token:
        held = held_assignments(routed[1], share.n_held,
                                share.held_start).reshape(b, l)
    if "shared_gate" in lp:
        act = gate_activation(activation)
        with jax.named_scope("moe_shared"):
            out = out + (act(flat @ lp["shared_gate"]) *
                         (flat @ lp["shared_up"])) @ lp["shared_down"]
    return out.reshape(b, l, e), (visited, held)


def random_gate_up_stack(key, n: int, d: int, i: int, dtype) -> jnp.ndarray:
    """A random w1 or w3 for `load_format="dummy"`: drawn as [N, D, I], so
    that a seed keeps giving the model it gave when that was the stored
    layout, and returned as the stored [N, I, D]. Waits for the result:
    the host otherwise runs layers ahead of the device and the draws'
    float32 and untransposed intermediates (five times the stack) pile up
    beside the weights."""
    w = (jax.random.normal(key, (n, d, i), jnp.float32) * 0.02).astype(dtype)
    return jax.block_until_ready(w.swapaxes(1, 2))


def dense_chunking(t: int, chunk_size: int) -> tuple[int, int]:
    """(number of chunks, rows a chunk) the dense path cuts `t` rows into:
    as few chunks as `chunk_size` allows, of equal size, each rounded up
    to the sublane tile — so a 128-row decode step is one chunk of 128 and
    not 256 rows of which half are zeros. The pad is under one tile a
    chunk."""
    most = max(chunk_size // _ROW_TILE, 1) * _ROW_TILE
    num_chunks = cdiv(t, most)
    chunk = cdiv(cdiv(t, num_chunks), _ROW_TILE) * _ROW_TILE
    return num_chunks, chunk


def moe_ffn_dense(
    x: jnp.ndarray,        # [T, D]
    gate_w: jnp.ndarray,   # [D, N] router
    w1: jnp.ndarray,       # [N, I, D]  (gate proj per expert)
    w2: jnp.ndarray,       # [N, I, D]  (down proj per expert)
    w3: jnp.ndarray,       # [N, I, D]  (up proj per expert)
    top_k: int,
    chunk_size: int = DENSE_ROWS,
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
    activation: str = "silu",
) -> jnp.ndarray:
    """`chunk_size` is the most rows a chunk may hold (it bounds the
    [N, C, I] activations); the rows present decide the rest. `routed` is
    a (weights, experts) pair the caller already has from `_route`."""
    t, d = x.shape
    n = w1.shape[0]
    act = gate_activation(activation)

    if routed is None:
        with jax.named_scope("moe_router"):
            routed = _route(x, gate_w, top_k, routing)
    topw, topi = routed
    if routing.held_start:
        # one_hot gives a zero row to an index outside [0, N): a choice
        # of an expert held elsewhere adds nothing here.
        topi = topi - routing.held_start
    onehot = jax.nn.one_hot(topi, n, dtype=jnp.float32)       # [T, K, N]
    combine = (topw[..., None] * onehot).sum(axis=1)          # [T, N]

    num_chunks, chunk = dense_chunking(t, chunk_size)
    pad_t = num_chunks * chunk
    x_pad = jnp.pad(x, ((0, pad_t - t), (0, 0)))
    comb_pad = jnp.pad(combine, ((0, pad_t - t), (0, 0)))
    x_chunks = x_pad.reshape(num_chunks, chunk, d)
    c_chunks = comb_pad.reshape(num_chunks, chunk, n)

    def chunk_fn(carry, inp):
        xc, cc = inp
        h1 = jnp.einsum("td,nid->nti", xc, w1,
                        preferred_element_type=jnp.float32)
        h3 = jnp.einsum("td,nid->nti", xc, w3,
                        preferred_element_type=jnp.float32)
        h = act(h1) * h3
        out = jnp.einsum("nti,nid->ntd", h.astype(x.dtype), w2,
                         preferred_element_type=jnp.float32)   # [N, C, D]
        mixed = jnp.einsum("ntd,tn->td", out, cc)
        return carry, mixed.astype(x.dtype)

    # named_scope: metadata only, so that the trace's device operations
    # carry their layer (obs/kernels.py, docs/observability.md).
    with jax.named_scope("moe_experts"):
        _, outs = jax.lax.scan(chunk_fn, None, (x_chunks, c_chunks))
    return outs.reshape(pad_t, d)[:t]


def moe_ffn_grouped(
    x: jnp.ndarray,        # [T, D]
    gate_w: jnp.ndarray,   # [D, N] router
    w1: jnp.ndarray,       # [N, I, D]
    w2: jnp.ndarray,       # [N, I, D]
    w3: jnp.ndarray,       # [N, I, D]
    top_k: int,
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
    tile: int = ROW_TILE,
    mesh=None,
    activation: str = "silu",
    count_visited: bool = False,
    held_at=None,
    live=None,
):
    """`tile`: rows a tile of the grouped kernels' walk (the sorted rows
    are padded to a whole number of them, once, not an expert). `mesh`:
    the runner's device mesh; where it spans chips each runs the kernels
    on its share of the stacks' inner dimension (`_over_inner_shards`).
    `count_visited`: also return how many held experts have a row (i32
    scalar): the experts whose weights the kernels read, since an expert
    without a row gets no visit (`visit_metadata`). `held_at`: as
    `moe_ffn`'s: the kernels get the stacks whole and this layer's group
    sizes at its experts' places among zeros, so no expert of another
    layer gets a visit and no slice of a stack is copied. `live` [T]
    bool: a row that is not live (a step's pad row) has no choice: no
    expert gets a row or a visit for it, it counts towards no visit, and
    its output is zeros; a live row's output is what it is without the
    mask, bit for bit. None: every row is live."""
    t, d = x.shape
    n = w1.shape[0] if held_at is None else held_at[1]
    tk = t * top_k

    if routed is None:
        with jax.named_scope("moe_router"):
            routed = _route(x, gate_w, top_k, routing)
    topw, topi = routed

    # A choice of an expert this stack does not hold (a share: the router
    # is wider than the stack) goes to a group of its own past the last
    # held expert, index n, which sorts last, which nothing computes and
    # whose rows the combine leaves out. A stack that holds every expert has
    # no such row. The choices of a row that is not live go the same way.
    local = topi.reshape(-1) - routing.held_start              # [T*K]
    here = (local >= 0) & (local < n)
    if live is not None:
        here &= jnp.repeat(live, top_k)
    flat_e = jnp.where(here, local, n)
    flat_w = topw.reshape(-1)

    # Place the choices in expert order, stably, by counting: a choice's
    # place is where its expert's group starts plus how many earlier
    # choices went to the same expert.
    earlier, counts = _earlier_choices_of_the_same(flat_e, n + 1)
    place = (jnp.cumsum(counts) - counts)[flat_e] + earlier    # [T*K]
    m = cdiv(tk, tile) * tile
    token_of = jnp.zeros((m, ), jnp.int32).at[place].set(
        jnp.arange(tk, dtype=jnp.int32) // top_k)
    xs = x[token_of]                                           # [M, D]

    def experts(xs, w1, w2, w3, sizes, place, flat_w, here):
        out = grouped_expert_ffn(xs, w1, w2, w3, sizes, tile,
                                 activation=activation)    # [M, D] f32
        # Rows of the last group (and of the pad) are as the device found
        # them: select, do not multiply by 0.
        rows = jnp.where(here[:, None], out[place] * flat_w[:, None], 0.0)
        return rows.reshape(t, top_k, d).sum(1)

    sizes = counts[:n]
    if held_at is not None:
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((w1.shape[0], ), sizes.dtype), sizes, (held_at[0], ))
    with jax.named_scope("moe_experts"):
        y = _over_inner_shards(experts, mesh, w1.shape[1])(
            xs, w1, w2, w3, sizes, place, flat_w, here)
    y = y.astype(x.dtype)
    if count_visited:
        return y, (counts[:n] > 0).sum().astype(jnp.int32)
    return y


_COUNT_BLOCK = 128


def _earlier_choices_of_the_same(flat_e: jnp.ndarray, groups: int):
    """([C] i32: how many choices before choice c went to c's own group,
    [groups] i32: choices a group). A running count in two levels, since
    XLA's own cumsum over [C, groups] is a reduce-window that costs a tenth
    of the kernels' time: inside a block of 128 choices a strictly lower
    triangular matmul (0/1 operands, float32 sums: exact), across blocks
    the running sum of the blocks' totals."""
    c = flat_e.shape[0]
    blocks = cdiv(c, _COUNT_BLOCK)
    chose = (flat_e[:, None] == jnp.arange(groups)).astype(jnp.bfloat16)
    chose = jnp.pad(chose, ((0, blocks * _COUNT_BLOCK - c), (0, 0))).reshape(
        blocks, _COUNT_BLOCK, groups)
    at = jnp.arange(_COUNT_BLOCK)
    before_in_block = (at[:, None] > at[None, :]).astype(jnp.bfloat16)
    within = jnp.einsum("rc,bcg->brg", before_in_block, chose,
                        preferred_element_type=jnp.float32)
    totals = chose.astype(jnp.float32).sum(1)                  # [B, G]
    before = jnp.cumsum(totals, axis=0) - totals
    earlier = ((within + before[:, None, :]) * chose).sum(-1)
    return (earlier.reshape(-1)[:c].astype(jnp.int32),
            totals.sum(0).astype(jnp.int32))


def _over_inner_shards(experts, mesh, inner: int):
    """Mosaic kernels cannot be partitioned by the compiler
    (`ops/dispatch.shard_over_heads` says the same of attention): on a
    mesh that spans chips each chip runs `experts` on its share of the
    stacks' inner dimension, the axis the models shard them on, and the
    float32 partial sums are added across the "model" axis. An inner
    width the axis does not divide runs whole on every chip."""
    if mesh is None or mesh.devices.size == 1:
        return experts
    from jax.sharding import PartitionSpec as P
    axis = "model" if inner % mesh.shape["model"] == 0 else None
    stack = P(None, axis, None)

    def body(*args):
        y = experts(*args)
        return jax.lax.psum(y, axis) if axis else y

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), stack, stack, stack, P(), P(), P(), P()),
        out_specs=P(), check_vma=False)


def moe_ffn(
    x: jnp.ndarray,
    gate_w: jnp.ndarray,
    w1: jnp.ndarray,
    w2: jnp.ndarray,
    w3: jnp.ndarray,
    top_k: int,
    routing: Routing = _SOFTMAX_TOP_K,
    routed=None,
    mesh=None,
    activation: str = "silu",
    count_visited: bool = False,
    held_at=None,
    live=None,
):
    """The expert FFN in the form `grouped_rows` picks for these shapes.
    `count_visited`: also return how many held experts the step read (i32
    scalar): those with a row in the grouped form, all of them in the
    dense one (intellillm_moe_expert_visits_total). `held_at`: (index of
    this layer's first expert in the stacks (may be traced), experts a
    layer) where the stacks are every routed layer's, `[layers * n, I,
    D]`, of a model that walks its layers in a `lax.scan`; None where
    they are this layer's own. `live` [T] bool: the rows that are a
    sequence's, None for all of them. The grouped form leaves the others
    out (`moe_ffn_grouped`); the dense form reads every stack whatever the
    rows say and computes them like any other: their output is the
    caller's to throw away in either form."""
    # All three stacks are [N, I, D] (module docstring): w1/w3 contract on
    # their last axis, so no loop around this call makes XLA re-lay them.
    # QuantMixtral (reference mixtral_quant.py): expert stacks arrive as
    # packed int4 — HBM holds only the packed bytes, [N, in/2, out] as the
    # checkpoint packs them; the dense stack is a transient dequant feeding
    # the expert matmuls of either form (XLA fuses the affine into the dot
    # producers where profitable), which for w1/w3 ([N, D, I] unpacked) is
    # handed over with its last two axes swapped.
    from intellillm_tpu.layers.quantization import (dequant_int4_stack,
                                                    is_quantized)
    if is_quantized(w1):
        w1 = dequant_int4_stack(w1, x.dtype).swapaxes(1, 2)
    if is_quantized(w2):
        w2 = dequant_int4_stack(w2, x.dtype)
    if is_quantized(w3):
        w3 = dequant_int4_stack(w3, x.dtype).swapaxes(1, 2)
    if grouped_rows(x.shape[0], top_k, gate_w.shape[1]):
        return moe_ffn_grouped(x, gate_w, w1, w2, w3, top_k,
                               routing=routing, routed=routed, mesh=mesh,
                               activation=activation,
                               count_visited=count_visited,
                               held_at=held_at, live=live)
    if held_at is not None:
        # A slice of the stacks that XLA reads inside the dots' fusions.
        w1, w2, w3 = (jax.lax.dynamic_slice_in_dim(w, *held_at)
                      for w in (w1, w2, w3))
    y = moe_ffn_dense(x, gate_w, w1, w2, w3, top_k, routing=routing,
                      routed=routed, activation=activation)
    return (y, jnp.int32(w1.shape[0])) if count_visited else y
