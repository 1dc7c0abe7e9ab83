"""Mixture-of-experts ops: top-k gating + two MXU-friendly compute paths.

Role parity: the reference stack serves Mixtral-class MoE models through
vLLM's fused-MoE CUDA kernels (grouped GEMM over expert-sorted tokens).
The TPU-native equivalents here are einsum formulations XLA tiles onto
the MXU, chosen per batch regime:

- `moe_dense` — "dropless dense": every token runs every expert as ONE
  batched einsum [n,d]x[E,d,f], weighted by the sparse gate matrix.
  Exact (no token dropping), no gather/scatter, no load-balance concern.
  FLOP cost is E/k x the routed ideal, which is the right trade at
  serving batch sizes: decode batches (n <= max_num_seqs) and prefill
  chunks are far too small to amortize a dispatch permutation, while the
  single dense einsum keeps the MXU at full tilt (MaxText makes the same
  call for small batches via capacity_factor=-1).

- `moe_capacity` — GShard-style static dispatch for LARGE token counts:
  each expert gets a fixed-capacity [E, C, d] slice gathered by one-hot
  einsums (static shapes; no dynamic control flow under jit). Tokens
  over an expert's capacity are dropped (classic GShard semantics) —
  callers pick the capacity factor; `capacity_needed` reports the
  no-drop bound for a gate matrix. With expert weights sharded over the
  mesh ("ep"), XLA lowers dispatch/combine into all_to_alls over ICI —
  expert parallelism without a single hand-written collective.

Gating follows Mixtral semantics (HF MixtralSparseMoeBlock): softmax over
the top-k logits only, renormalized.

- `routed_experts` — the dropless ROUTED layer over the experts HELD
  HERE (an expert-parallel rank's contiguous slice of a wider router):
  `route` scores every expert of the deployment, the (row, expert) pairs
  whose expert is local are kept and sorted by expert, and each local
  expert runs on its own rows only, so FLOPs follow the routed rows and
  bytes the experts that have rows. The experts themselves are
  `ops/expert_ffn.py`: on a TPU ONE Mosaic kernel, `expert_ffn`, over
  the whole weight stacks, which visits the experts with rows and passes
  each one's gate, up and down weights through VMEM once; elsewhere
  three `jax.lax.ragged_dot`s (the same mathematics; CPU tests and
  rehearsals). What the absent experts would add is left out: the
  partial sum is what an all-reduce over the ranks would complete. One
  form at every row count (its docstring has the measurement against
  the experts' bytes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from production_stack_tpu.ops.expert_ffn import MAX_ROWS, expert_ffn


def top_k_gating(x: jax.Array, gate_w: jax.Array, k: int) -> jax.Array:
    """x [n,d] @ gate_w [d,E] -> sparse gates [n,E] f32, rows sum to 1
    over each token's top-k experts, zero elsewhere."""
    n = x.shape[0]
    logits = jnp.dot(x, gate_w, preferred_element_type=jnp.float32)
    top_v, top_i = lax.top_k(logits, k)  # [n,k]
    probs = jax.nn.softmax(top_v, axis=-1)
    gates = jnp.zeros_like(logits)
    return gates.at[jnp.arange(n)[:, None], top_i].set(probs)


def moe_dense(
    x: jax.Array,
    gates: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
) -> jax.Array:
    """Exact all-experts path. x [n,d]; w_gate/w_up [E,d,f]; w_down
    [E,f,d]; gates [n,E]. Returns [n,d] f32."""
    g = jnp.einsum("nd,edf->nef", x, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nd,edf->nef", x, w_up,
                   preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("nef,efd->ned", a, w_down,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("ned,ne->nd", y, gates)


def capacity_needed(gates: jax.Array) -> jax.Array:
    """Max tokens routed to any one expert (the no-drop capacity)."""
    return (gates > 0).sum(axis=0).max()


def moe_capacity(
    x: jax.Array,
    gates: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    capacity: int,
    valid: jax.Array | None = None,
) -> jax.Array:
    """GShard static-capacity path; tokens beyond `capacity` per expert
    are dropped (their combine weight is zero, so they contribute their
    residual stream unchanged). Shapes as in moe_dense; capacity static.

    `valid` ([n] bool): rows that are real tokens. Padding/idle-lane rows
    MUST be masked out here — unlike the dense path (where garbage rows
    only produce garbage outputs that the caller discards), a padded row
    would otherwise consume expert capacity slots ahead of real tokens
    and silently drop their expert outputs."""
    n, E = gates.shape
    if valid is not None:
        gates = gates * valid[:, None].astype(gates.dtype)
    mask = gates > 0
    # rank of each token within its expert's arrival order
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=0) - 1  # [n,E]
    keep = mask & (pos < capacity)
    # dispatch [n,E,C]: one-hot of pos where kept
    disp = keep[..., None] & (
        pos[..., None] == jnp.arange(capacity)[None, None, :]
    )
    disp_f = disp.astype(x.dtype)
    xe = jnp.einsum("nec,nd->ecd", disp_f, x)  # [E,C,d]
    g = jnp.einsum("ecd,edf->ecf", xe, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("ecd,edf->ecf", xe, w_up,
                   preferred_element_type=jnp.float32)
    a = (jax.nn.silu(g) * u).astype(x.dtype)
    ye = jnp.einsum("ecf,efd->ecd", a, w_down,
                    preferred_element_type=jnp.float32)
    comb = disp_f * gates[..., None]  # [n,E,C]
    return jnp.einsum("nec,ecd->nd", comb, ye)


def route(
    x: jax.Array, router_w: jax.Array, router_b: jax.Array | None,
    top_k: int, scoring: str = "softmax", renorm: bool = True,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """Scores over ALL experts of the router -> (idx [n,k] int32, w
    [n,k] f32): the chosen experts and their combine weights.

    Logits in float32 at `highest` precision whatever the model's
    dtype: a few hundred kFLOP a row, and what keeps a near-tie between
    the k-th and the next expert from flipping against a float32
    reference. Selection is by score + `router_b` (a learned
    load-balancing bias that never enters the weights); weights are the
    chosen scores, divided by their sum when `renorm`, times `scale` (a
    model's routed scaling factor)."""
    logits = jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    scores = (
        jax.nn.sigmoid(logits) if scoring == "sigmoid"
        else jax.nn.softmax(logits, axis=-1)
    )
    sel = scores if router_b is None else scores + router_b.astype(
        jnp.float32)
    _, idx = lax.top_k(sel, top_k)
    w = jnp.take_along_axis(scores, idx, axis=1)
    if renorm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    if scale != 1.0:
        w = w * scale
    return idx.astype(jnp.int32), w


def routed_experts(
    x: jax.Array,            # [n, d]
    router_w: jax.Array,     # [d, E_all]
    router_b: jax.Array | None,  # [E_all]
    w_gate: jax.Array | None,  # [E_loc, d, f] — the experts held here;
                               # None: experts without a gate matrix
    w_up: jax.Array,
    w_down: jax.Array,       # [E_loc, f, d]
    *,
    top_k: int,
    first_expert: int,       # global id of local expert 0
    scoring: str = "softmax",
    renorm: bool = True,
    scale: float = 1.0,      # on the combine weights (`route`)
    valid: jax.Array | None = None,  # [n] bool: real rows
    stack_index: jax.Array | None = None,
    interpret: bool | None = None,  # `expert_ffn`'s
    act: str = "silu",       # the experts' activation (`expert_ffn`)
    expert_x: jax.Array | None = None,  # [n, d_e]: what the experts
    # read where that is not the router's input (a latent width d_e;
    # the result is then [n, d_e] too)
) -> tuple[jax.Array, jax.Array]:
    """The local experts' share of a routed layer -> ([n, d] f32,
    stats [3] int32 = pairs routed, pairs whose expert is here, local
    experts with at least one row; real rows only).

    `stack_index` (a traced scalar): the expert weights are STACKS over
    the layers of a scanned run, [S, E_loc, ...], and this is the layer
    to use. The experts get the whole stacks, flattened to S * E_loc
    groups, and the layer's offset into them: a dynamic slice of a stack
    would be copied first (805 MB a layer at 16 experts of 4096 x 2048;
    compile-only v5e, PR 28).

    Dropless: every kept pair is computed. A padded or idle row
    (`valid` false) keeps no pair, so it takes no expert's time and
    changes no other row; its output is zero.

    The experts alone (`ops/expert_ffn.py`), on a v5e, against the least
    their bytes allow (experts with rows x 3 d f x 2 B at 819 GB/s); us
    a layer, and the floor's share of it (my chip run, PR 42, call 3:
    `scripts/bench_expert_ffn.py --seed 42`; "ragged_dot" is the three
    `jax.lax.ragged_dot`s over all S * E_loc groups that ran here until
    PR 42, "gmm" megablox's grouped matmul three times):

        xing4 (64 of 64, top-4,         experts      floor  ragged_dot       gmm    kernel
        3584 x 1024, stack of 6)       with rows
          decode 128 pairs, 12 real       11 of 384    296  439 .67   423 .70   347 .85
          decode 128 pairs, 40 real       36           968 1327 .73  1261 .77  1074 .90
          decode 128 pairs, all real      57          1533 2069 .74  1966 .78  1688 .91
          a round of 1,152 pairs          64          1721 2670 .64  2506 .69  1985 .87
          a round of 2,176 pairs          64          1721 3023 .57  2804 .61  2194 .78
        mimo (16 of 256, top-8,
        4096 x 2048; m = 256 pairs)
          140 local, stack of 5           16 of 80     983 1654 .59  1328 .74  1102 .89
          115 local, stack of 1           16 of 16     983 1721 .57  1368 .72  1250 .79
          11 local, stack of 5             9 of 80     553  954 .58   739 .75   634 .87
        laguna (256 of 256, top-8,
        2048 x 512; PR 43, --seed 43)
          decode 256 pairs, 48 real       42 of 768    323  593 .54   455 .71   385 .84
          the same, stack of 1            43 of 256    330  636 .52   520 .64   452 .73
          decode 256 pairs, all real     160 of 768   1229 2042 .60  1516 .81  1369 .90
          a chunk's 2,048 pairs          256 of 768   1967 5117 .38  2586 .76  2230 .88
          2,304 pairs (chunk + lanes)    256 of 768   1967 3383 .58  2606 .76  2251 .87

    What is left to the kernel's floor: the first expert's tiles, which
    nothing overlaps (13 us a call at an f tile of 512; the stack of 1
    shows it most, every call alone in its loop), the rows in and the
    float32 result out, a third of a microsecond a grid step, and in a
    round of more than `MAX_ROWS` pairs the expert astride two passes,
    read in both (2,176 pairs: five passes). An f tile of 256 read 6-9%
    slower, 1,024 the same; row tiles of 32, 64 and 256 within 1.5%; a
    static grid of min(E_loc, m) steps with the steps past the list doing
    nothing 5% slower at 11 experts of 64 (call 1, same seed)."""
    n, _ = x.shape
    stacked = stack_index is not None
    e_loc = w_up.shape[1 if stacked else 0]
    if valid is not None:
        # whatever such a row holds (not a number, even) must not reach
        # the others through the row matrices below: 0 x NaN is NaN
        x = jnp.where(valid[:, None], x, 0)
    idx, w = route(x, router_w, router_b, top_k, scoring, renorm, scale)
    if expert_x is not None:
        x = expert_x if valid is None else jnp.where(
            valid[:, None], expert_x, 0)
    local = idx - first_expert
    keep = (local >= 0) & (local < e_loc)
    if valid is not None:
        keep &= valid[:, None]
        n_real = jnp.sum(valid.astype(jnp.int32))
    else:
        n_real = jnp.int32(n)
    w = jnp.where(keep, w, 0.0)
    local = jnp.where(keep, local, e_loc)   # e_loc = "not here"
    counts = jnp.zeros((e_loc + 1,), jnp.int32).at[local.reshape(-1)].add(1)
    sizes = counts[:e_loc]
    stats = jnp.stack([
        n_real * top_k, jnp.sum(sizes), jnp.sum((sizes > 0).astype(jnp.int32))
    ])

    base = 0
    if stacked:
        w_gate, w_up, w_down = (
            a if a is None else a.reshape(-1, *a.shape[2:])
            for a in (w_gate, w_up, w_down))
        base = stack_index * e_loc

    # pairs sorted by local expert, the ones not here last; the experts
    # walk the sorted local pairs `m` at a time. m covers twice the
    # pairs an even routing sends here (all of them where every expert
    # is local) up to what `expert_ffn` holds at once, so one pass is
    # the rule of a decode step; a routing more skewed than that, or a
    # prefill round's pairs, take another pass over the next m, and
    # nothing is dropped
    pairs = n * top_k
    share = e_loc / router_w.shape[1]
    m = min(MAX_ROWS, pairs if share >= 0.5 else min(
        pairs, -(-int(2 * pairs * share) // 128) * 128 + 128))
    order = jnp.argsort(local.reshape(-1), stable=True)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    w_flat = w.reshape(-1)

    def one_pass(carry):
        p0, out = carry
        # the m sorted pairs from `lo`; where the last pass's slice was
        # pulled back to fit, its first `skip` pairs are done already:
        # they are no expert's rows and get no weight
        lo = jnp.minimum(p0, pairs - m)
        skip = p0 - lo
        sel = lax.dynamic_slice(order, (lo,), (m,))
        group = jnp.clip(ends - p0, 0, m - skip) - jnp.clip(
            starts - p0, 0, m - skip)
        rows = sel // top_k
        # rows in and results out through one-hot matrices, not a
        # gather and a scatter-add: XLA's pair is 2.4 MB of program
        # where these are 0.3 MB (compile-only v5e, PR 28), and a cell
        # has forty such programs in a compile cache of bounded size.
        # Picking a row is exact in any precision; the combine carries
        # float32 results and weights, so it runs at `highest`
        pick = rows[:, None] == jnp.arange(n)[None, :]      # (m, n)
        xs = jnp.dot(pick.astype(x.dtype), x,
                     preferred_element_type=jnp.float32).astype(x.dtype)
        # zero in the rows that are no local expert's
        y = expert_ffn(xs, w_gate, w_up, w_down, group, skip, base,
                       interpret=interpret, act=act)
        live = (jnp.arange(m) >= skip) & (lo + jnp.arange(m) < ends[-1])
        wt = jnp.where(live, w_flat[sel], 0.0)
        combine = jnp.where(pick, wt[:, None], 0.0).T         # (n, m)
        return p0 + m - skip, out + jnp.dot(
            combine, y, precision=lax.Precision.HIGHEST)

    _, out = lax.while_loop(
        lambda c: c[0] < ends[-1], one_pass,
        (jnp.int32(0), jnp.zeros((n, x.shape[1]), jnp.float32)))
    return out, stats


def moe_block(
    x: jax.Array,
    gate_w: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    num_experts_per_tok: int,
    capacity_factor: float = 0.0,
    valid: jax.Array | None = None,
) -> jax.Array:
    """Full MoE MLP block: gate + compute. capacity_factor 0 selects the
    exact dense path (serving default); > 0 selects GShard dispatch with
    C = ceil(k * n * factor / E) — bulk/offline callers only, and they
    must pass `valid` when rows include padding (see moe_capacity)."""
    gates = top_k_gating(x, gate_w, num_experts_per_tok)
    if capacity_factor <= 0:
        out = moe_dense(x, gates, w_gate, w_up, w_down)
    else:
        n, E = gates.shape
        cap = max(1, int(-(-num_experts_per_tok * n * capacity_factor // E)))
        out = moe_capacity(x, gates, w_gate, w_up, w_down, cap, valid)
    return out
