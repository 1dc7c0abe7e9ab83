"""Model runner: owns params + KV cache on device, dispatches jitted steps.

XLA-first batching contract (the piece that makes continuous batching work on
TPU without per-step recompilation):

- every device program has a **static shape**, selected from a small set of
  buckets; jit traces each bucket once and the compile cache does the rest;
- prefill packs chunks from up to max_prefill_seqs sequences into one
  dispatch (prefill_batch; group size bucketed to a power of two), each
  chunk padded to a power-of-two length bucket and the context padded to
  a whole-block bucket; single-sequence prefill keeps its own buckets;
- decode runs a fixed number of lanes (max_num_seqs) with the context padded
  to the max bucket needed this step; a lane that holds no sequence ships
  context length 0, which the step programs read as "no row this step": the
  paged kernels walk nothing for it and give it a zero row, and its writes
  name the reserved trash slot 0 of the null block: the XLA path's scatters
  land there, the kernel path's cache write (ops/cache_write.py) skips the
  row (a lane a device stop froze mid-round is handed on the same way);
- KV caches are donated into every step, so the layers' writes happen in
  place in HBM (no cache copies).

The attention inner op is chosen at construction: the XLA gather path
(ops/attention.py) everywhere, or the Pallas kernel on TPU.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.models import layer_groups, llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.ops import attention as xla_attn
from production_stack_tpu.ops import cache_write, ssm
from production_stack_tpu.parallel import sharding as sharding_rules
from production_stack_tpu.tracing import phases
from production_stack_tpu.utils import init_logger

logger = init_logger(__name__)


def next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# lane-type codes in the ragged pack's lane-meta header (lane_types /
# lane_lens / lane_budgets — the per-lane fields extending
# _decode_pack_layout to a lane-typed prefill+decode round). The device
# reads lane_types to pin idle prefill lanes' sampled slot to
# sampler.RAGGED_IDLE_TOKEN; lens/budgets make the buffer
# self-describing (chunk length / this round's K, remaining prompt /
# remaining token budget).
RAGGED_LANE_IDLE = 0
RAGGED_LANE_PREFILL = 1
RAGGED_LANE_DECODE = 2

# Row-block height of the unified ragged kernel's flattened query-row
# space (ops/pallas_attention.RAGGED_TQ). Prefill lanes pack their
# chunk rows RAGGED_TQ-aligned; decode lanes contribute one row each
# and share blocks.
RAGGED_TQ = 8


def _ceil_tq(n: int) -> int:
    return -(-n // RAGGED_TQ) * RAGGED_TQ


# The kinds of program the runner builds, one list for three uses: the
# name a builder's function is jitted under (`jit_<kind>` on a profiler
# trace's `XLA Modules` line and in the HLO module's name, instead of
# twelve programs all called `jit_step`), the `kind` label of
# tpu:compile_events_total, and the `kind` of an `engine.build` span.
PROGRAM_KINDS = (
    "decode", "decode_multi", "ragged", "ragged_rows", "prefill",
    "prefill_rows", "prefill_batch", "verify", "embed", "kv_import",
)


def shared_runs(tables: np.ndarray, ctx: np.ndarray, block_size: int
                ) -> np.ndarray:
    """(row blocks, 2) int32: per row block of RAGGED_TQ decode lanes,
    the leading keys that EVERY lane of it with a sequence (context > 0)
    reads from the same physical pages, and the first such lane, whose
    table row addresses them for all. That is the longest common
    leading run of those lanes' rows of the packed (b, P) table, cut to
    the smallest of their contexts less one: a prefix that prefix
    caching holds once. The walk streams it once for the block
    (`pallas_attention._ragged_kernel`, which cuts it down to its KV
    block) and not once a lane. 0 where fewer than two lanes hold a
    sequence."""
    tq = RAGGED_TQ
    b, n_pages = tables.shape
    n_blk = _ceil_tq(b) // tq
    rows = np.zeros((n_blk * tq, n_pages), np.int32)
    rows[:b] = tables
    rows = rows.reshape(n_blk, tq, n_pages)
    lens = np.zeros((n_blk * tq,), np.int64)
    lens[:b] = ctx
    lens = lens.reshape(n_blk, tq)
    live = lens > 0
    first = live.argmax(axis=1)
    blk = np.arange(n_blk)
    same = ((rows == rows[blk, first][:, None]) | ~live[:, :, None]
            ).all(axis=1)
    run = np.where(same.all(axis=1), n_pages, same.argmin(axis=1))
    least = np.where(live, lens, np.iinfo(np.int32).max).min(axis=1)
    keys = np.maximum(np.minimum(run * block_size, least - 1), 0)
    runs = np.zeros((n_blk, 2), np.int32)
    runs[:, 0] = np.where(live.sum(axis=1) >= 2, keys, 0)
    runs[:, 1] = blk * tq + first
    return runs


def seat_least(latent: bool) -> int:
    """The fewest sequences on one leading page that `place_lanes`
    seats in row blocks of their own. Two, where a shared pass streams
    once what two walks stream twice. Three where the walk is a latent
    kind's: its shared pass, a tall tile of RAGGED_TQ x 32 query rows
    over a 512-key block, is bound by its arithmetic and costs 2.9
    lanes' walks of the same keys, so a pair seated together LOSES 12%
    of the walk (290.8 -> 325.6 us a layer for 5 live lanes over 4
    documents of 16.5k keys) where three gain 5% (346.5 -> 327.8, 6
    live over 2 documents) and eight on one document 53% (441.5 ->
    205.3): scripts/bench_attention_walk.py on the chip, PERF.md
    Findings PR 47."""
    return 3 if latent else 2


def place_lanes(first_pages, b: int, least: int = 2) -> np.ndarray:
    """(n,) int32: the lane, of a round's `b`, that each of its n decode
    sequences takes, from the physical id of the first page of its
    table. `shared_runs` finds a run only where EVERY lane of a row
    block holds the same leading pages, so the sequences on one first
    page sit together: the groups of `least` or more, largest first
    (ties by first appearance), take whole row blocks of RAGGED_TQ
    lanes from the lowest that is free, `ceil(n / RAGGED_TQ)` each, and
    everybody else fills the blocks that are left in the order given. A
    group whose blocks would leave the others too few lanes stays with
    the others. The lanes between hold no sequence (context 0: a zero-row
    segment of the walk, frozen from iteration 0). One group of
    everybody, or no group, is the identity: lane i for sequence i.
    A pure function of its arguments: the same sequences in the same
    order take the same lanes in every round, which is what lets a
    staged round chain on the tokens the last one left on the device
    (`LLMEngine._stage_fingerprint`)."""
    n, tq = len(first_pages), RAGGED_TQ
    lanes = np.arange(n, dtype=np.int32)
    groups: dict[int, list[int]] = {}
    for i, page in enumerate(first_pages):
        groups.setdefault(page, []).append(i)
    shared = sorted((g for g in groups.values() if len(g) >= least),
                    key=lambda g: -len(g))
    if not shared or len(shared[0]) == n:
        return lanes
    free = list(range(-(-b // tq)))
    rest = set(range(n))

    def room(blocks: list[int]) -> int:
        return sum(min(tq, b - blk * tq) for blk in blocks)

    for g in shared:
        need = -(-len(g) // tq)
        take, left = free[:need], free[need:]
        if room(take) < len(g) or room(left) < len(rest) - len(g):
            continue
        for j, i in enumerate(g):
            lanes[i] = take[j // tq] * tq + j % tq
        free = left
        rest.difference_update(g)
    spare = (blk * tq + r for blk in free
             for r in range(min(tq, b - blk * tq)))
    for lane, i in zip(spare, sorted(rest)):
        lanes[i] = lane
    return lanes


def _seats(lanes: np.ndarray | None, n: int) -> np.ndarray:
    """A round's map from sequence to lane (`place_lanes`), or lane i
    for sequence i where the caller gave none."""
    return np.arange(n, dtype=np.int32) if lanes is None else lanes


def jit_program(kind: str, fn, **jit_kw):
    """`jax.jit(fn)` under the name of its kind."""
    assert kind in PROGRAM_KINDS, kind
    fn.__name__ = fn.__qualname__ = kind
    return jax.jit(fn, **jit_kw)


class ModelRunner:
    def __init__(
        self,
        config: EngineConfig,
        params: dict | None = None,
        mesh: jax.sharding.Mesh | None = None,
    ):
        self.config = config
        self.model_config: ModelConfig = config.model_config()
        self.dtype = jnp.dtype(config.dtype)
        self.cache_dtype = jnp.dtype(config.cache_dtype)
        self.max_model_len = config.resolved_max_model_len()

        mc = self.model_config
        if mc.layer_groups:
            self._refuse_for_layer_groups(config, mc)
        if mc.ut_steps > 1:
            self._refuse_for_looped_stack(config, mc)
        if mc.is_moe and mc.moe_capacity_factor > 0:
            # serving steps pad decode lanes / prefill buckets, and the
            # GShard capacity path has no per-row validity inside
            # llama.forward — padded rows would steal expert capacity
            # from real tokens (ops/moe.py:moe_capacity). Serving always
            # uses the exact dense path; the capacity path is for
            # offline/bulk callers that manage their own padding.
            raise ValueError(
                f"model {mc.name}: moe_capacity_factor="
                f"{mc.moe_capacity_factor} is not servable; the engine "
                "requires the exact dense MoE path (capacity_factor=0)"
            )
        if not (1 <= config.num_scheduler_steps <= config.block_size):
            # validate at boot: a mid-serving ValueError from decode_multi
            # would kill the engine step-loop thread and hang every
            # in-flight request instead of failing fast here
            raise ValueError(
                f"num_scheduler_steps={config.num_scheduler_steps} must "
                f"be in [1, block_size={config.block_size}] (idle decode "
                "lanes park inside the trash block)"
            )
        tp = config.tensor_parallel_size
        pp = config.pipeline_parallel_size
        if mesh is None and (tp > 1 or pp > 1):
            mesh = sharding_rules.make_serving_mesh(tp, pp)
        self.mesh = mesh
        if self.mesh is not None:
            sharding_rules.validate_tp(mc, tp if pp > 1 else self.mesh.size)
        # forward implementation: the plain layer scan, or the
        # pipeline-staged phase loop when layers shard over pp
        if pp > 1:
            from production_stack_tpu.parallel import pp_serving

            pp_serving.validate_pp_serving(mc, pp, config)
            if config.attention_impl == "pallas":
                raise ValueError(
                    "attention_impl=pallas does not compose with "
                    "pipeline_parallel_size>1 yet (the kernels' own "
                    "shard_map cannot nest in the pp manual region); "
                    "use auto or xla"
                )
            self._forward = functools.partial(
                pp_serving.forward_pp, mesh=self.mesh
            )
        elif mc.layer_groups:
            self._forward = functools.partial(
                layer_groups.forward, block_size=config.block_size,
                write_kv=self._write_kv,
            )
        else:
            self._forward = functools.partial(
                llama.forward, write_kv=self._write_kv)

        if params is None:
            # real checkpoints load from disk (local dir or HF cache);
            # preset/debug names fall through to random init
            from production_stack_tpu.models import weights as weight_loader

            # the mesh-sharding elif below handles TP placement for
            # loaded params, same as caller-supplied ones
            params = weight_loader.maybe_load(config.model, mc, self.dtype)
        if params is None:
            logger.info(
                "initializing random %s params (%.2fB params, %s, "
                "tp=%d, pp=%d)",
                mc.name, mc.num_params() / 1e9, config.dtype, tp, pp,
            )
            init = (layer_groups if mc.layer_groups else llama).init_params
            init_fn = lambda key: init(mc, key, self.dtype)
            if self.mesh is not None:
                # init directly into the TP layout: no transient replicated
                # copy of the full weights on any single chip
                init_fn = jax.jit(
                    init_fn,
                    out_shardings=sharding_rules.param_shardings(
                        self.mesh, mc
                    ),
                )
            params = init_fn(jax.random.key(config.seed))
        elif self.mesh is not None:
            params = sharding_rules.shard_params(params, self.mesh, mc)
        self.params = params

        self.block_size = config.block_size
        # the block manager of a model with a windowed cache group
        # (WindowedBlockManager; the engine sets it): where the k_cache
        # property reads the block map when it changed
        self.block_map_source = None
        self._map_version = None
        # the same for a model with recurrent state
        # (StateBlockManager): its maps from a block to state slots,
        # and the slots of the state group (`_allocate_cache_groups`)
        self.state_map_source = None
        self._state_map_version = None
        self.num_state_slots = self.num_snapshots = 0
        self.snapshot_interval_blocks = 0
        # counters a step program sums on the device and returns beside
        # its K cache ("stats": the routed layers' of a layer-group
        # model, ints; a looped stack's exit distribution, a float a
        # pass): each program's own, on their way to the host, and the
        # host's totals
        self._stats_pending: collections.deque = collections.deque()
        self._stats_total = (
            [0] * layer_groups.N_STATS if mc.layer_groups
            else [0.0] * mc.ut_steps)
        if mc.layer_groups:
            self._allocate_cache_groups()
        else:
            self.num_blocks = self._resolve_num_blocks()
            self.num_window_blocks = 0
            num_slots = self.num_blocks * self.block_size
            # head-major (L, nkv, slots, d): the layout the Pallas
            # kernels and the MXU want (see ops/pallas_attention.py
            # docstring)
            cache_shape = (
                mc.cache_layers, mc.num_kv_heads, num_slots, mc.head_dim
            )
            logger.info(
                "allocating KV cache: %d blocks x %d slots (%.2f GiB)",
                self.num_blocks, self.block_size,
                2 * math.prod(cache_shape) * self.cache_dtype.itemsize
                / 2**30,
            )
            zeros = lambda: jnp.zeros(cache_shape, self.cache_dtype)
            if self.mesh is not None:
                zeros = jax.jit(
                    zeros,
                    out_shardings=sharding_rules.cache_sharding(self.mesh),
                )
            self.k_cache = zeros()
            self.v_cache = zeros()

        self._scale = mc.attn_scale
        # attention impl: pallas paged kernel on TPU; under TP the kernel
        # is shard_mapped over the kv-head-sharded cache (each chip's GQA
        # groups are local, so the kernel body needs no collectives)
        impl = config.attention_impl
        if impl == "auto":
            impl = "pallas" if jax.default_backend() == "tpu" else "xla"
        if config.pipeline_parallel_size > 1:
            impl = "xla"  # see the pp validation above
        if impl not in ("xla", "pallas"):
            raise ValueError(
                f"attention_impl must be auto|xla|pallas, got {impl!r}"
            )
        on_tpu = jax.default_backend() == "tpu"
        if impl == "pallas" and on_tpu and self._unaligned_heads(mc):
            # Mosaic requires DMA slices aligned to the (8, 128) lane
            # tiling: a head_dim below 128 (e.g. Llama-3.2-1B's 64) pads
            # the cache's lane dim and every page slice becomes a partial
            # tile ("must be aligned to tiling (128)" compile error).
            # `auto` serves these on the XLA path (visible in /version);
            # an explicit pallas request is refused, not overridden.
            if config.attention_impl == "pallas":
                raise ValueError(
                    "attention_impl=pallas requires head_dim % 128 == 0 "
                    f"on TPU (model {mc.name} has head_dim {mc.head_dim});"
                    " use auto or xla"
                )
            logger.warning(
                "pallas attention requires head_dim %% 128 == 0 (got %d); "
                "attention_impl=auto selects the XLA gather path",
                mc.head_dim,
            )
            impl = "xla"
        self.attention_impl = impl
        # wherever Pallas runs, EVERY batched attention call — decode
        # rounds, packed prefill groups, mixed lane-typed rounds — is
        # the one batched-grid ragged_paged_attention kernel
        # (ops/pallas_attention.py): any lane mix is one launch, and
        # on one device the packed-prefill/ragged program variants key
        # on padded ROW-count buckets instead of the (s_pad, t_pad)
        # lane-mix grid. A derived fact, kept as an attribute for its
        # readers (/version, chip_smoke.py, the benchmark's warm-up)
        self.ragged_kernel = impl == "pallas"
        if impl == "pallas" and on_tpu:
            # compile the exact kernel variants serving will use —
            # sliding-window page walk and shard_map wrappers included —
            # on tiny shapes now: a kernel Mosaic refuses fails start-up
            # with the compiler's message instead of the first request,
            # and never selects another attention path
            self._pallas_smoke_test(mc)
            self._ragged_smoke_test(mc)
        logger.info(
            "device: platform=%s device_kind=%s count=%d "
            "attention_impl=%s ragged_kernel=%s",
            jax.devices()[0].platform, jax.devices()[0].device_kind,
            len(jax.devices()), impl, self.ragged_kernel,
        )

        # multi-LoRA: stacked adapter buffers applied inside the jitted
        # steps (engine/lora.py); None when --enable-lora is off so the
        # step functions trace without the adapter math
        self.lora_manager = None
        if config.enable_lora:
            from production_stack_tpu.engine.lora import LoraManager

            self.lora_manager = LoraManager(
                mc, config.max_loras, config.max_lora_rank, self.dtype
            )
        # multi-host SPMD: logits must come back fully replicated so host 0
        # can pull them to the host for sampling (shards on follower hosts
        # are not addressable from host 0)
        self.replicate_logits = bool(config.multihost)

        # pipelined prefill (one packed h2d buffer per dispatch +
        # staged uploads): program variants take the fused buffer.
        # Single-device only: under a pp/tp mesh the packed operand's
        # inferred sharding trips SPMD partitioning (observed:
        # "PartitionId instruction is not supported" under pp x tp) —
        # meshed engines keep the per-array upload path
        self.prefill_pipeline = (
            bool(config.prefill_pipeline) and self.mesh is None
        )
        # the round's phase spans (tracing/phases.py): wall seconds and
        # counts per phase, fed to /metrics (tpu:engine_phase_*) and
        # the request timeline, and written into the profiler's trace
        # while one is taken. The runner times
        # pack = host array build, h2d = upload enqueue (staged uploads
        # overlap compute but still count — they are real link work) and
        # dispatch = the jitted call's enqueue, once per step program
        # dispatched; the engine times schedule, fetch and apply on the
        # same timer. The phases that are host work also keep the
        # seconds the step thread stood in them without running
        # (tpu:engine_phase_*_offcpu_seconds): the interpreter was
        # another thread's
        self.phases = phases.PhaseTimer(
            phases.ENGINE_PHASES, "engine.", offcpu=phases.HOST_PHASES)
        # context tokens the attention calls of the dispatched rounds
        # had to read once, and the rounds counted (tpu:attn_context_
        # tokens): host integers the dispatch already holds
        self.attn_context_tokens = [0, 0]
        # the decode rows of the dispatched rounds: lanes x fused steps,
        # and those of them the pack shipped with context 0, zero-row
        # segments of the attention walk (tpu:decode_lane_steps,
        # tpu:decode_idle_lane_steps)
        self.decode_lane_steps = [0, 0]
        # the state layers' one-token updates by those rows: lanes
        # that hold a sequence x fused steps x such layers
        # (tpu:ssm_lane_layer_steps), and the calls of the update
        # kernel that made them: fused steps x such layers, whatever
        # the lanes hold (tpu:state_update_calls)
        self.ssm_lane_layer_steps = 0
        self.state_update_calls = 0
        self._ssm_layers = mc.ssm_layers
        # evaluations of sampler.sample_tokens by the dispatched rounds
        # (a fused decode step is one, a round's first-token rows one
        # more), and those of them whose rows held a temperature > 0:
        # the only ones that build the candidate window
        # (tpu:sampler_steps, tpu:sampler_window_steps)
        self.sampler_steps = [0, 0]
        # passes of the layer stack by the dispatched programs: their
        # forwards x `ut_steps` (tpu:loop_passes; a device stop may end
        # a round before its last fused step, as for the sampler's)
        self.loop_passes = 0
        self._passes_a_forward = mc.ut_steps
        # the decode lanes' page-table rows as the last pack left them
        # (_page_table_rows): id(table) -> (table, row, ids copied)
        self._kept_rows: dict[int, tuple] = {}
        # the same per attention kind of a layer-group model, tokens a
        # LAYER of that kind read (tpu:attn_context_tokens_<kind>)
        self._kind_windows = [ak.window for ak in mc.attn_kinds]
        self.attn_context_by_kind = [[0] for _ in mc.attn_kinds]
        # lanes that share a prefix (a row block's shared run, see
        # `_shared_runs`): the context tokens the lanes attended, each
        # lane's own count, and those of them that a shared pass served
        # (tpu:attn_lane_context_tokens, tpu:attn_shared_context_
        # tokens). tpu:attn_context_tokens and its per-kind siblings
        # count what the walk STREAMS: a shared run once a row block
        self.attn_lane_tokens = [0, 0]
        # keys of the walk's KV block, which a shared run is cut down
        # to a multiple of: per attention kind, and the model's own (a
        # layer-group model's: its kind 0, the pool every token takes);
        # 0 where the walk has a window, and so no leading run
        self._kind_run_keys = [
            0 if ak.window else self._kv_block_keys(
                ak.num_kv_heads, self._k_store_dim(i),
                0 if ak.latent_dim else mc.v_dim)
            for i, ak in enumerate(mc.attn_kinds)]
        self._run_keys = (
            self._kind_run_keys[0] if mc.attn_kinds
            else 0 if mc.sliding_window else self._kv_block_keys(
                mc.num_kv_heads, mc.head_dim, mc.head_dim))
        # how many sequences on one leading page `decode_lanes` seats
        # together: by the kind whose table `shared_runs` reads
        self._seat_least = seat_least(
            bool(mc.attn_kinds and mc.attn_kinds[0].latent_dim))
        # the shared runs of the decode pack filled last
        # (`_fill_decode_pack`), for the dispatch's counters or the
        # staged handle that carries them to it
        self._packed_runs: np.ndarray | None = None
        phases.install_program_listeners()

        # jit caches keyed by bucket tuple
        self._prefill_fns: dict[tuple[int, int], object] = {}
        self._verify_batch_fns: dict[tuple[int, int, int], object] = {}
        self._prefill_batch_fns: dict[tuple[int, int, int], object] = {}
        self._decode_fns: dict[tuple[int, int], object] = {}
        self._decode_multi_fns: dict[tuple[int, int, int], object] = {}
        # unified ragged rounds, keyed by (s_pad, t_pad, pc_pad, b,
        # c_pad, k, flags...) — see ragged_dispatch
        self._ragged_fns: dict[tuple, object] = {}
        self._embed_fns: dict[tuple[int, int], object] = {}
        # donated in-place KV block scatter (offload restore / PD
        # import), keyed by (n_src_pad, n_dst_pad) pow2 buckets
        self._import_fns: dict[tuple[int, int], object] = {}

        # compile-count observability: every program-variant build (a
        # jit-cache miss on one of the builders above) is counted per
        # kind — the cold-start compile cost and the ragged-kernel
        # variant-space shrink become measurable
        # (tpu:compile_events_total) instead of inferred from logs
        self.compile_events: dict[str, int] = {}
        self.compile_events_total = 0

        self.max_ctx_bucket = self._ctx_bucket(self.max_model_len)

    def _kv_block_keys(self, nkv: int, d_k: int, d_v: int) -> int:
        from production_stack_tpu.ops.pallas_attention import (
            _kv_block_pages,
        )

        return self.block_size * _kv_block_pages(
            nkv, d_k, self.cache_dtype.itemsize, self.block_size, d_v)

    @staticmethod
    def _unaligned_heads(mc: ModelConfig) -> bool:
        """True where the paged kernels cannot take the model's head
        widths: a page slice must be whole (8, 128) tiles. A layer-group
        model stores K padded to the next 128 lanes (`_k_store_dim`), so
        only its V width has to be aligned: `v_head_dim`, or for a
        latent kind the latent dims its rows give as the value."""
        if mc.layer_groups:
            return any((ak.latent_dim or mc.v_dim) % 128
                       for ak in mc.attn_kinds)
        return bool(mc.head_dim % 128)

    def _k_store_dim(self, kind: int = 0) -> int:
        """Lanes a K row of attention kind `kind` takes in the cache:
        the q/k head width, or a latent kind's row (latent + rotary
        dims). On the chip's kernel path a width that is no multiple of
        the 128-lane tile is stored with zero lanes up to the next one
        (192 -> 256, 576 -> 640): HBM tiles the minor dim in 128s
        whatever the logical width, so the bytes are the same as
        stored, and a page slice stays whole tiles for the DMA
        (measured: PERF.md, Findings PR 28). q is padded to match where
        the kernel is called (`_attn`)."""
        mc, cfg = self.model_config, self.config
        width = mc.head_dim
        if mc.attn_kinds and mc.attn_kinds[kind].latent_dim:
            ak = mc.kinds[kind]
            width = ak.latent_dim + ak.rotary_dim
        on_kernel_path = (
            jax.default_backend() == "tpu"
            and cfg.attention_impl in ("auto", "pallas")
            and not self._unaligned_heads(mc)
        )
        if on_kernel_path and width % 128:
            return -(-width // 128) * 128
        return width

    @staticmethod
    def _refuse_for_layer_groups(config: EngineConfig, mc) -> None:
        """A model of layer groups runs on one device through the
        chunked-prefill and fused-decode programs. What has no code
        path for it is refused here, by name, rather than run wrongly."""
        refused = {
            "--enable-lora": config.enable_lora,
            "--tensor-parallel-size > 1": config.tensor_parallel_size > 1,
            "--pipeline-parallel-size > 1":
                config.pipeline_parallel_size > 1,
            "multihost serving": bool(config.multihost),
            "--num-speculative-tokens": config.num_speculative_tokens > 0,
            "--long-prefill-threshold (the ring prefill lane)":
                config.long_prefill_threshold is not None,
            "KV offload tiers (--cpu-offload-gb, --disk-offload-dir, "
            "--remote-cache-url)": bool(
                config.cpu_offload_bytes or config.disk_offload_dir
                or config.remote_cache_url),
            "PD transfer (--kv-role / --kv-transfer-listen / --kv-peer)":
                bool(config.kv_role) or any(
                    (config.kv_transfer_config or {}).values()),
        }
        on = [name for name, flag in refused.items() if flag]
        if on:
            raise ValueError(
                f"model {mc.name} is a stack of layer groups "
                "(models/layer_groups.py: a KV cache per attention "
                "kind" + (
                    ", and recurrent state a sequence that is not pages "
                    "and has no export path" if mc.ssm_layers else "")
                + "), which does not serve with: " + "; ".join(on)
            )
        if mc.ssm_layers and any(ak.window for ak in mc.attn_kinds):
            raise ValueError(
                f"model {mc.name}: state-space layers beside a windowed "
                "attention kind are not served (one map a block manager)")

    @staticmethod
    def _refuse_for_looped_stack(config: EngineConfig, mc) -> None:
        """A looped stack (`ut_steps` > 1) runs on one device through
        the chunked-prefill and fused-decode programs, and its cache
        moves through the KV tiers and PD transfer at `cache_layers`
        layers a block. What walks the layers by another loop than
        llama.forward's, or sizes by `num_layers` what the passes would
        multiply, is refused here, by name."""
        refused = {
            "--enable-lora (adapter stacks a weight layer, applied in "
            "every pass: not tested)": config.enable_lora,
            "--tensor-parallel-size > 1": config.tensor_parallel_size > 1,
            "--pipeline-parallel-size > 1 (the phase loop of "
            "parallel/pp_serving.py runs the stages once)":
                config.pipeline_parallel_size > 1,
            "multihost serving": bool(config.multihost),
            "--num-speculative-tokens": config.num_speculative_tokens > 0,
            "--long-prefill-threshold (the ring prefill lane runs "
            "the layers once)":
                config.long_prefill_threshold is not None,
        }
        on = [name for name, flag in refused.items() if flag]
        if on:
            raise ValueError(
                f"model {mc.name} is a looped stack (ut_steps="
                f"{mc.ut_steps}: models/llama.py runs its layers that "
                "many times a token, each pass on cache layers of its "
                "own), which does not serve with: " + "; ".join(on)
            )

    # snapshots of the recurrent state the pool keeps, as a multiple of
    # the lanes: a stand-in for the sessions a deployment keeps warm
    # (twice the lanes) and the shared prefixes and slack beside them;
    # tpu:prefix_state_cutback_tokens says when the pool is too small.
    # Four a lane would lose fewer returning sessions' snapshots to the
    # sessions that have just left (2.9% of hit tokens cut back against
    # 7.6% in a replay of a chat cell's plan at 4.7 req/s), but at 161
    # slots of 20 MiB beside 9.3 GB of weights XLA rematerialised the
    # convolution's pool in every layer step, 11.5% of the device's busy
    # time (my chip run, PR 45, call 4; PERF.md, Findings PR 45)
    SNAPSHOTS_A_LANE = 3

    # cached sequences whose last window the windowed pool keeps for a
    # prefix hit to end in, as a multiple of the lanes (`_window_blocks_
    # needed`)
    CACHED_ENDS_A_LANE = 4

    def _window_blocks_needed(self) -> int:
        """Blocks of the windowed cache group: for every lane the
        window behind its next query and the chunk (or fused decode
        steps) ahead of it, and the last window of CACHED_ENDS_A_LANE
        times as many cached sequences: where a returning session's
        prefix hit has to end, or it is cut back to nothing
        (`WindowedBlockManager`). The lanes are a stand-in for the
        sessions a deployment keeps warm, which nothing here knows; one
        end a lane (the size until PR 43) lost 48 sessions' ends over
        four 16.5k documents to the documents' own prefill (PERF.md,
        Findings PR 43); tpu:prefix_window_cutback_blocks says when the
        pool is too small."""
        cfg = self.config
        kind = layer_groups.mapped_kind(self.model_config)
        if kind is None:
            return 0
        bs = cfg.block_size
        win = -(-self.model_config.attn_kinds[kind].window // bs)
        ahead = -(-max(cfg.max_prefill_chunk,
                       2 * cfg.num_scheduler_steps) // bs)
        lanes = max(1, cfg.max_num_seqs)
        return (lanes * (win + ahead + 2)
                + self.CACHED_ENDS_A_LANE * lanes * (win + 1) + 1)

    def _allocate_cache_groups(self) -> None:
        """One K and one V array per attention kind, (L_kind, nkv_kind,
        slots, d): kind 0's pool takes every token and is sized from
        free HBM after the windowed kind's pool, which is sized by what
        the lanes can hold at once (`_window_blocks_needed`). A latent
        kind has ONE array a layer: its rows are keys and values, and
        its place among the V arrays holds None."""
        mc, bs = self.model_config, self.block_size
        item = self.cache_dtype.itemsize
        kinds = range(len(mc.attn_kinds))
        dk = [self._k_store_dim(i) for i in kinds]
        dv = [0 if ak.latent_dim else mc.v_dim for ak in mc.attn_kinds]
        mapped = layer_groups.mapped_kind(mc)
        layers = [mc.layer_kinds.count(i) for i in kinds]

        def block_bytes(kind):
            return (layers[kind] * mc.attn_kinds[kind].num_kv_heads
                    * (dk[kind] + dv[kind]) * bs * item)

        self.num_window_blocks = self._window_blocks_needed()
        window_bytes = (
            self.num_window_blocks * block_bytes(mapped)
            if mapped is not None else 0
        )
        state_bytes = 0
        if mc.ssm_layers:
            # a slot a lane, and SNAPSHOTS_A_LANE times as many
            # snapshots where prefixes are cached; slot 0 is nobody's
            self.num_state_slots = max(1, self.config.max_num_seqs)
            self.num_snapshots = (
                self.SNAPSHOTS_A_LANE * self.num_state_slots
                if self.config.enable_prefix_caching else 0)
            self.snapshot_interval_blocks = max(
                1, self.config.max_prefill_chunk // bs)
            state_bytes = (
                1 + self.num_state_slots + self.num_snapshots
            ) * mc.state_bytes_per_seq(self.dtype.itemsize)
        self.num_blocks = self._resolve_num_blocks(
            bytes_per_block=sum(
                block_bytes(i) for i in range(len(mc.attn_kinds))
                if i != mapped),
            reserve=window_bytes + state_bytes,
        )
        kg, vg = [], []
        for i, ak in enumerate(mc.attn_kinds):
            n = self.num_window_blocks if i == mapped else self.num_blocks
            kg.append(jnp.zeros(
                (layers[i], ak.num_kv_heads, n * bs, dk[i]),
                self.cache_dtype))
            vg.append(None if ak.latent_dim else jnp.zeros(
                (layers[i], ak.num_kv_heads, n * bs, dv[i]),
                self.cache_dtype))
        logger.info(
            "allocating KV cache groups: %s (%.2f GiB)",
            ", ".join(
                f"kind {i}: {layers[i]} layers x {ak.num_kv_heads} kv "
                f"heads x {a.shape[2] // bs} blocks, K stored at "
                f"{dk[i]} lanes, V at {dv[i]}"
                for i, (ak, a) in enumerate(zip(mc.attn_kinds, kg))),
            sum(a.nbytes for a in kg + vg if a is not None) / 2**30,
        )
        self._k_cache = {
            "g": tuple(kg),
            "map": jnp.zeros((self.num_blocks,), jnp.int32),
        }
        if mc.ssm_layers:
            slots = 1 + self.num_state_slots + self.num_snapshots
            logger.info(
                "allocating recurrent state: %d layers x (%d lanes + %d "
                "snapshots + 1) slots of %.2f MiB a sequence (%.2f GiB)",
                mc.ssm_layers, self.num_state_slots, self.num_snapshots,
                mc.state_bytes_per_seq(self.dtype.itemsize) / 2**20,
                state_bytes / 2**30,
            )
            self._k_cache |= {
                "ssm": {
                    "s": jnp.zeros(
                        (mc.ssm_layers, slots, *ssm.packed_shape(mc)),
                        jnp.float32),
                    "conv": jnp.zeros(
                        (mc.ssm_layers, slots, mc.ssm_conv - 1,
                         mc.ssm_conv_dim), self.dtype),
                },
                "smap": jnp.zeros((3, self.num_blocks), jnp.int32),
            }
        self.v_cache = {"g": tuple(vg)}

    @property
    def k_cache(self):
        """The K side of the cache as the step programs take it. For a
        model with a windowed cache group it carries the block map,
        uploaded here when the block manager changed it since the last
        dispatch (one small h2d, started before the program that needs
        it and ordered before it on the device's stream)."""
        src = self.block_map_source
        if src is not None and src.map_version != self._map_version:
            self._map_version = src.map_version
            self._k_cache = {
                **self._k_cache,
                # a copy: the transfer may still read the host buffer
                # while the scheduler plans the next round
                "map": jnp.asarray(src.block_map.copy()),
            }
        src = self.state_map_source
        if src is not None and src.map_version != self._state_map_version:
            self._state_map_version = src.map_version
            self._k_cache = {
                **self._k_cache, "smap": jnp.asarray(src.maps.copy())}
        return self._k_cache

    @k_cache.setter
    def k_cache(self, value) -> None:
        """What a step program returned. A layer-group program's K side
        carries that program's routed-layer counters (`_enter_caches`
        starts them at zero): they are taken off here, before the next
        dispatch donates the rest, and start their way to the host
        beside the round's tokens."""
        if isinstance(value, dict) and "stats" in value:
            value = dict(value)
            stats = value.pop("stats")
            stats.copy_to_host_async()
            self._stats_pending.append(stats)
            self._drain_stats()
            # a looped stack's cache is one array: {"c": it} without
            # its counters is it
            value = value.get("c", value)
        self._k_cache = value

    def _drain_stats(self) -> None:
        """Add the counters of every program that has finished to the
        host's totals (Python ints: nothing wraps); never waits for one
        that has not."""
        pending = self._stats_pending
        while pending and pending[0].is_ready():
            for i, x in enumerate(np.asarray(pending.popleft())):
                self._stats_total[i] += x.item()

    def moe_stats(self) -> tuple[int, ...]:
        """The routed layers' counters (layer_groups.N_STATS) of every
        finished program since start-up. Reads host memory: a scrape
        waits for no round. The caller holds the engine's step lock."""
        self._drain_stats()
        return tuple(self._stats_total)

    def loop_exit_mass(self) -> tuple[float, ...]:
        """A looped stack's exit distribution summed over the sampled
        rows of every finished program since start-up, a float a pass
        (empty for a model without the gate). Host memory, as
        `moe_stats`."""
        if not self.model_config.exit_gate:
            return ()
        self._drain_stats()
        return tuple(self._stats_total)

    def device_report(self) -> dict:
        """What this runner runs on, as jax reports it (served by
        /version so a client in another process can assert it)."""
        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "attention_impl": self.attention_impl,
            "ragged_kernel": self.ragged_kernel,
            # per local device; None where the backend has no stats (CPU)
            "bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.local_devices()
            ],
        }

    def _note_compile(self, kind: str, key=None):
        """Count one program-variant build (jit cache miss). Returns the
        `engine.build` annotation (kind + cache key) to hold around the
        program's first call, where jax traces, lowers and compiles it:
        a child of that call's `engine.dispatch` in a profiler trace,
        and nothing outside a profiler session."""
        assert kind in PROGRAM_KINDS, kind
        self.compile_events[kind] = self.compile_events.get(kind, 0) + 1
        self.compile_events_total += 1
        return phases.annotation("engine.build", kind=kind, key=repr(key))

    # -- sizing -----------------------------------------------------------
    def _resolve_num_blocks(self, bytes_per_block: int | None = None,
                            reserve: int = 0) -> int:
        """Blocks of the (primary) pool: `--num-kv-blocks`, or what the
        free HBM holds at `bytes_per_block` (a model of alike layers:
        K and V of every layer) after `reserve` bytes for another
        cache group."""
        cfg, mc = self.config, self.model_config
        if cfg.num_kv_blocks is not None:
            return cfg.num_kv_blocks
        if bytes_per_block is None:
            bytes_per_block = (
                2
                * mc.cache_layers
                * cfg.block_size
                * mc.num_kv_heads
                * mc.head_dim
                * self.cache_dtype.itemsize
            )
        tp = self.mesh.size if self.mesh is not None else 1
        # per-chip view: weights and KV blocks are both split ~1/tp.
        param_bytes = mc.num_params() * self.dtype.itemsize // tp
        stats = jax.devices()[0].memory_stats() or {}
        if "bytes_limit" in stats:
            limit = stats["bytes_limit"]
            # caller-supplied params may still be host arrays at this point
            # (server.py passes numpy); bytes_in_use then misses them, so
            # reserve at least the weight estimate either way.
            reserved = max(stats.get("bytes_in_use", 0), param_bytes)
        elif jax.default_backend() == "cpu":
            # the CPU backend reports no memory stats: size the pool as
            # if for one 16 GiB chip (tests pass num_kv_blocks anyway)
            limit = 16 * 2**30
            reserved = param_bytes
        else:
            raise RuntimeError(
                f"{jax.devices()[0]} reports no bytes_limit in "
                "memory_stats(); cannot size the KV cache from HBM — "
                "pass --num-kv-blocks"
            )
        budget = int(limit * cfg.hbm_utilization) - reserved - reserve
        num = max(2, budget // (bytes_per_block // tp))
        # cap: no point holding more than max_model_len * max_num_seqs * 2
        cap = (
            2
            * (self.max_model_len // cfg.block_size + 1)
            * max(1, cfg.max_num_seqs)
        )
        return int(min(num, max(cap, 2)))

    def _smoke_caches(self, mc: ModelConfig):
        """(k, v, spec, query heads) of a four-block cache for each
        kernel variant serving will compile: the model's one, or one per
        layer kind (its kv and query heads, window and sink; K at its
        stored width; no V for a latent kind)."""
        bs = self.block_size
        kinds = (
            [(ak.num_kv_heads, layer_groups.AttnSpec(
                window=ak.window,
                sink=(jnp.zeros((ak.num_heads,), jnp.float32)
                      if ak.sink else None),
                mapped=bool(ak.window),
                latent_v=ak.latent_dim or None,
            ), ak.num_heads) for ak in mc.kinds]
            if mc.layer_groups else [(mc.num_kv_heads, None, mc.num_heads)]
        )
        for i, (nkv, spec, nq) in enumerate(kinds):
            kc = jnp.zeros(
                (1, nkv, 4 * bs, self._k_store_dim(i)), self.cache_dtype)
            vc = None
            if spec is None or not spec.latent_v:
                vc = jnp.zeros(
                    (1, nkv, 4 * bs, mc.v_dim), self.cache_dtype)
            if self.mesh is not None:
                # exercise the exact shard_map paths serving will take
                cs = sharding_rules.cache_sharding(self.mesh)
                kc, vc = jax.device_put(kc, cs), jax.device_put(vc, cs)
            yield kc, vc, spec, nq

    def _pallas_smoke_test(self, mc: ModelConfig) -> None:
        # probe the exact kernel variants serving will compile — the
        # windowed page walk included (traced loop start + guarded
        # DMA); `_attn` routes through the shard_map TP wrappers under
        # a mesh, exactly as the step builders do. q is as wide as the
        # kind's rows (`_attn` pads a head of 192 to 256 itself)
        table1 = jnp.zeros((2,), jnp.int32)
        for kc, vc, spec, nq in self._smoke_caches(mc):
            qp = jnp.zeros(
                (8, nq, self._smoke_q_dim(kc, vc)), self.dtype)
            out = self._attn("prefill", qp, jnp.int32(0), kc, vc,
                             table1, jnp.int32(0), spec=spec,
                             mapped=table1)
            jax.block_until_ready(out)

    def _smoke_q_dim(self, kc, vc) -> int:
        """A smoke query's width: the head's, or a latent kind's rows'."""
        return kc.shape[-1] if vc is None else self.model_config.head_dim

    def _ragged_smoke_test(self, mc: ModelConfig) -> None:
        """Compile the unified ragged kernel in the grid shape serving
        dispatches: one prefill q-tile beside one decode row."""
        blk_seg = jnp.asarray([0, 1, 2], jnp.int32)
        seg_meta = jnp.asarray(
            [[0, 0, RAGGED_TQ, 0], [1, 0, 1, 0]], jnp.int32
        )
        tables = jnp.zeros((2, 2), jnp.int32)
        for kc, vc, spec, nq in self._smoke_caches(mc):
            qr = jnp.zeros(
                (2 * RAGGED_TQ, nq, self._smoke_q_dim(kc, vc)),
                self.dtype)
            out = self._attn(
                "ragged", qr, jnp.int32(0), kc, vc, tables, blk_seg,
                seg_meta, spec=spec, mapped=tables,
            )
            jax.block_until_ready(out)

    def _step_jit_kwargs(self, n_host_outs: int = 1) -> dict:
        """Extra jit options for the prefill/decode step builders.
        `n_host_outs` = leading outputs host 0 may fetch (replicated
        under multihost so followers' shards are never addressed)."""
        if not (self.replicate_logits and self.mesh is not None):
            return {}
        from jax.sharding import NamedSharding, PartitionSpec

        rep = NamedSharding(self.mesh, PartitionSpec())
        cs = sharding_rules.cache_sharding(self.mesh)
        return {"out_shardings": (rep,) * n_host_outs + (cs, cs)}

    # -- buckets ----------------------------------------------------------
    def _ctx_bucket(self, num_tokens: int) -> int:
        """Context bucket in tokens: whole blocks, pow2 block count."""
        blocks = max(1, -(-num_tokens // self.block_size))
        blocks = next_pow2(blocks)
        max_blocks = -(-self.max_model_len // self.block_size)
        return min(blocks, next_pow2(max_blocks)) * self.block_size

    def _prefill_bucket(self, chunk_len: int) -> int:
        return min(
            next_pow2(max(chunk_len, 8)),
            next_pow2(self.config.max_prefill_chunk),
        )

    def _enter_caches(self, kc, vc, counters: bool = True):
        """What every step program does to the caches it was handed. A
        layer-group cache gets the program's routed-layer counters, from
        zero, where a step composed of steps has not yet given it them
        (`k_cache`'s setter takes them off what the program returns);
        the one K array of a looped stack with an exit gate becomes
        {"c": it, "stats": the program's exit distribution}, which
        llama.forward adds to (`counters=False`: a program that runs no
        forward). The caches are then pinned to the row-major physical
        layout the Pallas custom calls constrain their operands to.

        Without the pin, XLA may pick a different layout for the scan body's
        scatter (observed on v5e: {3,1,2,0} vs the kernel's {3,2,1,0})
        and insert a FULL-CACHE layout-conversion copy per step — 2 x
        3.8 GiB per step for the 3B model, which OOMed HBM outright."""
        if isinstance(kc, dict) and "stats" not in kc:
            kc = {**kc, "stats": jnp.zeros(
                (layer_groups.N_STATS,), jnp.int32)}
        mc = self.model_config
        if counters and mc.exit_gate and not isinstance(kc, dict):
            kc = {"c": kc,
                  "stats": jnp.zeros((mc.ut_steps,), jnp.float32)}
        if self.attention_impl != "pallas" or (
            jax.default_backend() != "tpu"
        ):
            return kc, vc
        from jax.experimental.layout import Layout, with_layout_constraint

        fmt = Layout((0, 1, 2, 3))

        def pin(c):
            if isinstance(c, dict) and "c" in c:  # a looped stack's
                return {**c, "c": with_layout_constraint(c["c"], fmt)}
            if isinstance(c, dict):  # cache groups: the arrays under "g"
                return {**c, "g": tuple(
                    a if a is None else with_layout_constraint(a, fmt)
                    for a in c["g"])}
            return with_layout_constraint(c, fmt)

        return pin(kc), pin(vc)

    def _rows_valid_kw(self, valid) -> dict:
        """`rows_valid=` for llama.forward where the model has an exit
        gate: which of a program's logits rows hold a token (padding
        lanes and lanes that hold no sequence are sampled and thrown
        away, and must not count into tpu:loop_exit_mass). Nothing for
        any other model: their forwards take no such argument."""
        return {"rows_valid": valid} if self.model_config.exit_gate else {}

    def _state_kw(self, lanes: int, lane_rows: int, tail: int) -> dict:
        """`state_rows=` for layer_groups.forward where the model has
        recurrent state: the program's rows as `ssm.plan_rows` takes
        them (prefill lanes, rows a lane at most, trailing one-token
        rows). Nothing for any other model."""
        if not self.model_config.ssm_layers:
            return {}
        return {"state_rows": (lanes, lane_rows, tail)}

    # -- jitted step builders ---------------------------------------------
    # stackcheck: hot-path — the ONE dispatch seam every pallas
    # attention call goes through (trace-time only: closed over by the
    # jitted step builders); collapses the former per-site
    # `mesh is not None -> *_tp else *` call ladders
    def _attn(self, kind: str, q, layer, kc, vc, *args, spec=None,
              shared=None, mapped=None):
        """Route one attention call to the pallas kernel for `kind`
        ("prefill": a lone chunk | "ragged": every batched call),
        picking the shard_map TP variant under a mesh and filling the
        static block-size/scale/interpret/window arguments from the
        runner's config. All kernel call sites dispatch through
        here. `shared`: the row blocks' shared runs of a ragged call
        (`_shared_runs`). `mapped`: the program's tables as the windowed
        cache group holds them (`_map_tables`)."""
        from production_stack_tpu.ops import pallas_attention

        fns = {
            "prefill": (
                pallas_attention.paged_prefill_attention,
                pallas_attention.paged_prefill_attention_tp,
            ),
            "ragged": (
                pallas_attention.ragged_paged_attention,
                pallas_attention.ragged_paged_attention_tp,
            ),
        }[kind]
        pallas_attention._note_trace(kind)  # launch accounting
        kw = dict(
            block_size=self.block_size,
            scale=self._scale,
            # not a fallback: the engine refuses the CPU backend unless
            # JAX_PLATFORMS names it (engine/__main__.py), so interpret
            # mode runs only where the CPU was asked for (tests)
            interpret=jax.default_backend() != "tpu",
            window=self.model_config.sliding_window,
        )
        if spec is not None:
            # a layer kind of a layer-group model (layer_groups.AttnSpec):
            # its own window and sink, and, for the windowed cache group,
            # the lanes' tables (args[0] for every kernel) as the program
            # mapped them into that group's pool
            kw["window"] = spec.window
            if spec.sink is not None:
                kw["sink"] = spec.sink
            if spec.mapped:
                args = (mapped, *args[1:])
            if spec.latent_v:
                kw["latent_v"] = spec.latent_v
        if kc.shape[-1] > q.shape[-1]:
            # K stored wider than d_k (`_k_store_dim`): zero lanes on
            # both sides of the product
            q = jnp.pad(
                q, ((0, 0), (0, 0), (0, kc.shape[-1] - q.shape[-1])))
        if self.mesh is not None:
            # no shared run under a mesh (the pack ships zeros there,
            # `_shared_runs`): the shard_map wrappers do not take one,
            # and no cell runs them
            return fns[1](q, kc, vc, layer, *args, mesh=self.mesh, **kw)
        if shared is not None:
            kw["shared"] = shared
        return fns[0](q, kc, vc, layer, *args, **kw)

    def _write_kv(self, kc, vc, l, write_slots, k, v):
        """A layer's cache write (ops/cache_write.py), routed as `_attn`
        routes its attention: the tile kernel where the walk runs, the
        per-head scatters where the XLA path does (and under pipeline
        parallelism, whose forward never comes here)."""
        return cache_write.write_kv(
            kc, vc, l, write_slots, k, v,
            # under a tensor-parallel mesh the scatters stay: GSPMD
            # partitions them over the kv-head-sharded cache itself, a
            # pallas_call would want `_over_heads`' shard_map (no cell
            # runs a mesh)
            kernel=self.attention_impl == "pallas" and self.mesh is None,
            interpret=jax.default_backend() != "tpu",
        )

    def _map_tables(self, kc, tables):
        """A program's lane tables as its windowed kind walks them: the
        primary pool's block ids (the XLA path: gather slots) mapped
        into the windowed cache group's pool through the block map the
        caches carry; None for a model without such a group. A
        program's tables and map are fixed at its dispatch, so its
        builder calls this ONCE, where it unpacks its constants, before
        the layers and before a fused round's loop (inside the attention
        call it was a gather of lanes x pages scalars a windowed layer
        and step: 4.3% of the laguna cell's busy time; ledger, PR 52),
        and hands the result to its attention closure as `mapped`."""
        if not isinstance(kc, dict) or "map" not in kc or not any(
                ak.window for ak in self.model_config.attn_kinds):
            return None
        block_map = kc["map"]
        if self.attention_impl == "pallas":
            return block_map[tables]
        bs = self.block_size
        return block_map[tables // bs] * bs + tables % bs

    def _xla_ctx(self, kc, vc, l, slots, spec, mapped):
        """The XLA path's gathered context of one layer, and the window
        and sink its attention call takes: the model's one window, or
        what the layer kind's `spec` says (the windowed cache group's
        slots are `mapped`: the primary pool's, block by block)."""
        window, sink = self.model_config.sliding_window, None
        if spec is not None:
            window, sink = spec.window, spec.sink
            if spec.mapped:
                slots = mapped
        # head-major cache + traced `l`: [l, :, slots] has two advanced
        # indices split by a slice, so numpy hoists them to the front —
        # the result is ALREADY (..., c, nkv, d)
        k_ctx = kc[l, :, slots]
        if spec is not None and spec.latent_v:
            # a latent kind's rows are keys and, in part, values
            v_ctx = k_ctx[..., :spec.latent_v]
        else:
            v_ctx = vc[l, :, slots]
        return k_ctx, v_ctx, {"window": window, "sink": sink}

    def _prefill_attn_closure(self):
        """The per-layer attention callback shared by the prefill and
        verify step builders (pallas paged kernel or XLA gather path).

        `gather_slots` = this sequence's padded block table (P,) on the
        pallas path (the kernel streams context pages from HBM once per
        chunk — the per-layer (ctx, nkv, d) gathered copy is never
        built; q row 0 is always a real token, so positions[0] is the
        chunk's absolute start position), or the flat slot gather on the
        XLA path; `mapped` = the same as the windowed cache group holds
        it (`_map_tables`)."""
        scale = self._scale
        if self.attention_impl == "pallas":

            def attn(q, l, kc, vc, gather_slots, q_positions, total_len,
                     spec=None, mapped=None):
                return self._attn(
                    "prefill", q, l, kc, vc, gather_slots,
                    q_positions[0], spec=spec, mapped=mapped,
                )
        else:

            def attn(q, l, kc, vc, gather_slots, q_positions, total_len,
                     spec=None, mapped=None):
                k_ctx, v_ctx, kw = self._xla_ctx(
                    kc, vc, l, gather_slots, spec, mapped)
                return xla_attn.context_attention_prefill(
                    q, k_ctx, v_ctx, q_positions, total_len, scale, **kw
                )

        return attn

    def _prefill_host_prep(
        self, token_ids: list[int], block_table: list[int],
        start_pos: int, total_len: int,
    ):
        """Shared host-side argument prep for prefill/verify dispatches:
        (tokens, positions_dev, write_slots, gather_slots, t_pad, c_pad).
        Padded rows carry position -1 -> rope of 0, write to trash."""
        t = len(token_ids)
        t_pad = self._prefill_bucket(t)
        c_pad = self._ctx_bucket(total_len)
        tokens = np.zeros((t_pad,), dtype=np.int32)
        tokens[:t] = token_ids
        positions = np.full((t_pad,), -1, dtype=np.int32)
        positions[:t] = np.arange(start_pos, start_pos + t)
        write_slots = self._slots_for_positions(block_table, positions)
        positions_dev = np.where(positions < 0, 0, positions).astype(
            np.int32
        )
        if self.attention_impl == "pallas":
            gather_slots = self._padded_block_table(
                block_table, c_pad // self.block_size
            )
        else:
            gather_slots = self._gather_slots_for_table(block_table, c_pad)
        return tokens, positions_dev, write_slots, gather_slots, t_pad, c_pad

    # -- pipelined prefill: fused h2d buffer --------------------------------
    def _note_attn_context(
        self, decode_lens=(), steps: int = 0, prefill_lens=(),
        forwards: int | None = None, runs: np.ndarray | None = None,
        lanes: np.ndarray | None = None,
    ) -> None:
        """Count one dispatched round's attention reads: each decode
        lane's context at each of its `steps` fused steps (context + i
        at step i) and each prefill chunk's END context once, both cut
        to the sliding window where the model has one. A lane that a
        device stop freezes mid-round is counted to the round's end.
        Where the round's pack found shared runs (`runs`, with the
        lanes its sequences took, `lanes`: lane i for sequence i where
        None), a run counts ONCE a row block and
        step into what the walk streams (tpu:attn_context_tokens, and
        each kind's at its own KV block), every lane's into the
        lane-tokens attended and, of those, served by a shared pass.
        And the decode rows' lane-steps, with those of lanes that hold
        no sequence (a frozen lane is no idle one here: the host packed
        it live). And the passes of the layer stack: `forwards` calls
        of the model (the fused steps, a prefill beside them riding
        step 0; one for a prefill alone) x `ut_steps`."""
        k, n = steps, len(decode_lens)
        self.loop_passes += (
            (max(k, 1) if forwards is None else forwards)
            * self._passes_a_forward)
        b = self.config.max_num_seqs
        self.decode_lane_steps[0] += k * b
        self.decode_lane_steps[1] += k * (b - n)
        self.ssm_lane_layer_steps += k * n * self._ssm_layers
        self.state_update_calls += k * self._ssm_layers
        w = self.model_config.sliding_window
        if w is None:
            tokens = (k * sum(decode_lens) + n * (k * (k - 1) // 2)
                      + sum(prefill_lens))
        else:
            tokens = sum(
                min(c + i, w) for c in decode_lens for i in range(k)
            ) + sum(min(c, w) for c in prefill_lens)

        live: list[int] = []
        if runs is not None:
            live = np.bincount(_seats(lanes, n) // RAGGED_TQ,
                               minlength=len(runs)).tolist()

        def run_tokens(kv_block: int) -> tuple[int, int]:
            """(lane-tokens a step that a shared pass serves, tokens a
            step that it spares the walk) at a KV block of that many
            keys: a row block's run, times its lanes with a sequence."""
            served = spared = 0
            if runs is not None and kv_block:
                for keys, lanes_in in zip(runs[:, 0].tolist(), live):
                    cut = keys // kv_block * kv_block
                    if cut and lanes_in > 1:
                        served += cut * lanes_in
                        spared += cut * (lanes_in - 1)
            return served, spared

        served, spared = run_tokens(self._run_keys)
        cell = self.attn_context_tokens
        cell[0] += tokens - k * spared
        cell[1] += 1
        self.attn_lane_tokens[0] += tokens
        self.attn_lane_tokens[1] += k * served
        for window, by_kind, kv_block in zip(
                self._kind_windows, self.attn_context_by_kind,
                self._kind_run_keys):
            # per layer of that kind: the window kind cut to its window
            cut = window or (1 << 62)
            by_kind[0] += sum(
                min(c + i, cut) for c in decode_lens for i in range(k)
            ) + sum(min(c, cut) for c in prefill_lens) - (
                k * run_tokens(kv_block)[1])

    def note_sampler(self, steps: int, temps) -> None:
        """Count `steps` evaluations of `sample_tokens` over rows of
        these temperatures (None: the greedy defaults). The device
        reads the same vector and builds the candidate window only
        where a row samples; this is the host's count of how often."""
        self.sampler_steps[0] += steps
        if temps is not None and np.greater(temps, 0.0).any():
            self.sampler_steps[1] += steps

    @staticmethod
    def _layout_of(fields: list[tuple[str, tuple[int, ...]]]):
        layout: dict[str, tuple[int, tuple[int, ...]]] = {}
        off = 0
        for name, shape in fields:
            layout[name] = (off, shape)
            off += int(np.prod(shape))
        return layout, off

    def _prefill_pack_layout(self, t_pad: int, c_pad: int,
                             want_plp: bool = False):
        """Static layout of the ONE int32 host->device buffer a
        single-sequence prefill dispatch ships (mirror of
        _decode_pack_layout: the ~8 small per-dispatch arrays fuse
        into one transfer instead of eight; f32/u32 fields travel
        bitcast. Cost of either on an attached chip: not measured)."""
        g_shape = (
            (c_pad // self.block_size,)
            if self.attention_impl == "pallas" else (c_pad,)
        )
        fields = [
            ("tokens", (t_pad,)),
            ("positions", (t_pad,)),
            ("write_slots", (t_pad,)),
            ("gather_slots", g_shape),
            ("total_len", (1,)),
            ("last_row", (1,)),
            ("temps", (1,)),
            ("top_ps", (1,)),
            ("top_ks", (1,)),
            ("min_ps", (1,)),
            ("keys", (1, 2)),
        ]
        if want_plp:
            fields.append(("targets", (t_pad,)))
        return self._layout_of(fields)

    def _packed_prefill_pack_layout(self, s_pad: int, t_pad: int,
                                    c_pad: int):
        """Packed cross-sequence variant of _prefill_pack_layout."""
        tab_shape = (
            (s_pad, c_pad // self.block_size)
            if self.attention_impl == "pallas" else (s_pad, c_pad)
        )
        fields = [
            ("tokens", (s_pad * t_pad,)),
            ("positions", (s_pad * t_pad,)),
            ("write_slots", (s_pad * t_pad,)),
            ("tables", tab_shape),
            ("q_starts", (s_pad,)),
            ("total_lens", (s_pad,)),
            ("last_rows", (s_pad,)),
            ("temps", (s_pad,)),
            ("top_ps", (s_pad,)),
            ("top_ks", (s_pad,)),
            ("min_ps", (s_pad,)),
            ("keys", (s_pad, 2)),
        ]
        return self._layout_of(fields)

    # -- ragged-rows prefill pack (single-kernel mode) ---------------------
    # Under the unified ragged kernel the packed-prefill token axis is
    # RAGGED: each lane's chunk rows pack back-to-back (RAGGED_TQ-
    # aligned) with lane offsets riding per-lane metadata instead of a
    # per-lane t_pad shape — so the program variant keys on the padded
    # ROW bucket (r_pad, pc_pad), not the (s_pad, t_pad) lane-mix
    # pair, and the precompile grid collapses accordingly.
    def _rows_lane_cap(self) -> int:
        """Static prefill-lane capacity of the ragged-rows programs
        (config-derived, NOT part of the program key)."""
        return next_pow2(max(self.config.max_prefill_seqs, 1))

    def _rows_lanes(self, r_pad: int) -> tuple[int, int]:
        """(prefill lanes, rows a lane at most) of a ragged-rows
        program of `r_pad` rows."""
        return self._rows_lane_cap(), min(
            r_pad, self._prefill_bucket(self.config.max_prefill_chunk))

    def _rows_bucket(self, n_rows: int) -> int:
        return next_pow2(max(n_rows, RAGGED_TQ))

    def _rows_dims(
        self, chunks: list[list[int]], total_lens: list[int]
    ) -> tuple[int, int]:
        """(r_pad, pc_pad) row/context buckets for a ragged-rows
        prefill group."""
        r_pad = self._rows_bucket(
            sum(_ceil_tq(len(c)) for c in chunks)
        )
        pc_pad = max(self._ctx_bucket(tl) for tl in total_lens)
        return r_pad, pc_pad

    def _rows_prefill_pack_layout(self, r_pad: int, pc_pad: int):
        """Ragged-rows variant of _packed_prefill_pack_layout: flat
        row-axis fields + per-lane metadata at the static lane cap."""
        s_cap = self._rows_lane_cap()
        fields = [
            ("tokens", (r_pad,)),
            ("positions", (r_pad,)),
            ("write_slots", (r_pad,)),
            ("tables", (s_cap, pc_pad // self.block_size)),
            ("lane_row0", (s_cap,)),
            ("lane_rows", (s_cap,)),
            ("q_starts", (s_cap,)),
            ("last_rows", (s_cap,)),
            ("temps", (s_cap,)),
            ("top_ps", (s_cap,)),
            ("top_ks", (s_cap,)),
            ("min_ps", (s_cap,)),
            ("keys", (s_cap, 2)),
        ]
        return self._layout_of(fields)

    # stackcheck: hot-path — host build of the ragged-rows prefill
    # pack (dispatch + staging prefetch); one pass over the lanes, no
    # device fetch
    def _fill_rows_prefill_pack(
        self,
        chunks: list[list[int]],
        start_positions: list[int],
        block_tables: list[list[int]],
        total_lens: list[int],
        sampling=None,
    ) -> tuple[int, int, np.ndarray]:
        """Host-side build of the ragged-rows prefill pack; returns
        (r_pad, pc_pad, packed). Lane i's chunk occupies rows
        [lane_row0[i], lane_row0[i] + len(chunk)) of the flat axis;
        the RAGGED_TQ-alignment tail rows and the bucket tail carry
        position -1 -> rope 0, write the trash slot, and are never
        stored by the kernel's causal rows (same padded-row contract
        as the composed pack)."""
        n = len(chunks)
        s_cap = self._rows_lane_cap()
        r_pad, pc_pad = self._rows_dims(chunks, total_lens)
        n_pages = pc_pad // self.block_size
        tokens = np.zeros((r_pad,), np.int32)
        positions = np.full((r_pad,), -1, np.int32)
        write_slots = np.zeros((r_pad,), np.int32)
        tables = np.zeros((s_cap, n_pages), np.int32)
        lane_row0 = np.zeros((s_cap,), np.int32)
        lane_rows = np.zeros((s_cap,), np.int32)
        q_starts = np.zeros((s_cap,), np.int32)
        last_rows = np.zeros((s_cap,), np.int32)
        row = 0
        for i, (ids, start) in enumerate(zip(chunks, start_positions)):
            t = len(ids)
            tokens[row: row + t] = ids
            pos = np.arange(start, start + t, dtype=np.int32)
            positions[row: row + t] = pos
            write_slots[row: row + t] = self._slots_for_positions(
                block_tables[i], pos
            )
            tables[i] = self._padded_block_table(
                block_tables[i], n_pages
            )
            lane_row0[i] = row
            lane_rows[i] = _ceil_tq(t)
            q_starts[i] = start
            last_rows[i] = row + t - 1
            row += _ceil_tq(t)
        # idle lanes: empty row ranges past the packed region (cover
        # nothing in the in-trace block map), last row 0 (sampled slot
        # pinned to the idle sentinel by the step)
        lane_row0[n:] = row
        positions_dev = np.where(positions < 0, 0, positions).astype(
            np.int32
        )
        layout, size = self._rows_prefill_pack_layout(r_pad, pc_pad)
        packed = np.zeros((size,), np.int32)
        put = functools.partial(self._pack_put, packed, layout)
        put("tokens", tokens)
        put("positions", positions_dev)
        put("write_slots", write_slots)
        put("tables", tables)
        put("lane_row0", lane_row0)
        put("lane_rows", lane_rows)
        put("q_starts", q_starts)
        put("last_rows", last_rows)
        temps, top_ps, top_ks, min_ps, keys = self._sampling_args(
            s_cap, sampling
        )
        put("temps", temps)
        put("top_ps", top_ps)
        put("top_ks", top_ks)
        put("min_ps", min_ps)
        put("keys", keys)
        return r_pad, pc_pad, packed

    def _rows_pf_seg_meta(self, r_pad, lane_row0, lane_rows, q_starts):
        """In-trace per-block segment metadata for the ragged-rows
        prefill region: every RAGGED_TQ block belongs to at most one
        lane (lanes pack TQ-aligned), so each block carries one
        segment — [lane, 0, TQ, q_pos of the block's first row] — and
        blocks outside every lane carry a zero-row segment the kernel
        walks past for free."""
        tq = RAGGED_TQ
        n_blk = r_pad // tq
        blk0 = jnp.arange(n_blk, dtype=jnp.int32) * tq
        ends = lane_row0 + lane_rows
        cover = (
            (blk0[:, None] >= lane_row0[None, :])
            & (blk0[:, None] < ends[None, :])
        )
        has = jnp.any(cover, axis=1)
        lane_of = jnp.argmax(cover, axis=1).astype(jnp.int32)
        rows = jnp.where(has, tq, 0).astype(jnp.int32)
        qpos0 = jnp.where(
            has, q_starts[lane_of] + (blk0 - lane_row0[lane_of]), 0
        )
        return jnp.stack(
            [lane_of, jnp.zeros_like(blk0), rows, qpos0], axis=1
        )

    @staticmethod
    def _rows_slot_vector(
        chunks: list[list[int]], slots, r_pad: int
    ) -> np.ndarray:
        """Per-row LoRA slot vector over the ragged-rows flat axis —
        the ONE copy of the lane->row expansion, kept in lockstep with
        _fill_rows_prefill_pack's row packing (RAGGED_TQ-aligned lane
        starts)."""
        slots = slots if slots is not None else [0] * len(chunks)
        per_row = np.zeros((r_pad,), np.int32)
        row = 0
        for ids, slot in zip(chunks, slots):
            per_row[row: row + len(ids)] = slot
            row += _ceil_tq(len(ids))
        return per_row

    def _rows_lora_kwargs(
        self, lora_slots, chunks: list[list[int]], r_pad: int
    ) -> dict:
        """Ragged-rows mirror of _packed_lora_kwargs: uniform-adapter
        fast path, else a per-row slot vector over the flat axis."""
        if self.lora_manager is None:
            return {}
        slots = (
            lora_slots if lora_slots is not None else [0] * len(chunks)
        )
        if len(set(slots)) <= 1:
            slots_arg = jnp.int32(slots[0] if slots else 0)
        else:
            slots_arg = jnp.asarray(
                self._rows_slot_vector(chunks, slots, r_pad)
            )
        return {
            "lora": self.lora_manager.buffers,
            "lora_slots": slots_arg,
        }

    def _make_prefill_rows_step(self, r_pad: int, pc_pad: int):
        """Ragged-rows packed prefill step: chunks from up to
        max_prefill_seqs sequences pack back-to-back on ONE flat row
        axis and the whole group's chunk attention is ONE
        ragged_paged_attention launch — the un-jitted core shared by
        _build_prefill_rows (split prefill path) and the fused
        lane-typed round builder (_build_ragged_rows)."""
        mc = self.model_config
        from production_stack_tpu.engine.sampler import sample_tokens

        s_cap = self._rows_lane_cap()
        layout, _size = self._rows_prefill_pack_layout(r_pad, pc_pad)

        def _seg(packed, name, _lo=layout):
            return self._pack_seg(packed, _lo, name)

        def unpack(packed):
            def f32(name):
                return jax.lax.bitcast_convert_type(
                    _seg(packed, name), jnp.float32
                )

            return {
                "tokens": _seg(packed, "tokens"),
                "positions": _seg(packed, "positions"),
                "write_slots": _seg(packed, "write_slots"),
                "tables": _seg(packed, "tables"),
                "lane_row0": _seg(packed, "lane_row0"),
                "lane_rows": _seg(packed, "lane_rows"),
                "q_starts": _seg(packed, "q_starts"),
                "last_rows": _seg(packed, "last_rows"),
                "temps": f32("temps"),
                "top_ps": f32("top_ps"),
                "top_ks": _seg(packed, "top_ks"),
                "min_ps": f32("min_ps"),
                "keys": jax.lax.bitcast_convert_type(
                    _seg(packed, "keys"), jnp.uint32
                ),
            }

        def step(params, kc, vc, packed, lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            pf = unpack(packed)
            seg_meta = self._rows_pf_seg_meta(
                r_pad, pf["lane_row0"], pf["lane_rows"], pf["q_starts"]
            )
            blk_seg = jnp.arange(
                r_pad // RAGGED_TQ + 1, dtype=jnp.int32
            )

            mapped = self._map_tables(kc, pf["tables"])

            def attn_fn(q, l, kcc, vcc, spec=None):
                return self._attn(
                    "ragged", q, l, kcc, vcc, pf["tables"], blk_seg,
                    seg_meta, spec=spec, mapped=mapped,
                )

            logits, kc, vc = self._forward(
                mc, params, pf["tokens"], pf["positions"], kc, vc,
                pf["write_slots"], attn_fn,
                logits_rows=pf["last_rows"],
                lora=lora, lora_slots=lora_slots,
                **self._rows_valid_kw(pf["lane_rows"] > 0),
                **self._state_kw(*self._rows_lanes(r_pad), 0),
            )
            sampled = sample_tokens(
                logits, pf["temps"], pf["top_ps"], pf["top_ks"],
                pf["keys"], min_p=pf["min_ps"],
            )
            return sampled, logits, kc, vc

        step._unpack = unpack  # the fused-round builder reuses it
        return step

    def _build_prefill_rows(self, r_pad: int, pc_pad: int):
        """Jitted ragged-rows packed prefill (kernel-mode variant of
        _build_prefill_batch; program key (r_pad, pc_pad))."""
        return jit_program(
            "prefill_rows",
            self._make_prefill_rows_step(r_pad, pc_pad),
            donate_argnums=(1, 2), **self._step_jit_kwargs(2),
        )

    @staticmethod
    def _pack_put(packed: np.ndarray, layout: dict, name: str,
                  arr: np.ndarray) -> None:
        off, shape = layout[name]
        n = int(np.prod(shape))
        packed[off:off + n] = np.asarray(arr).reshape(-1).view(np.int32)

    @staticmethod
    def _pack_seg(packed, layout: dict, name: str):
        """Device-side static-slice read of one packed-buffer field
        (the unpack mirror of _pack_put), shared by every fused-buffer
        step builder."""
        off, shape = layout[name]
        n = int(np.prod(shape))
        return packed[off:off + n].reshape(shape)

    def _fill_prefill_pack(
        self, token_ids: list[int], start_pos: int,
        block_table: list[int], total_len: int, sampling=None,
        prompt_lp_targets: list[int] | None = None,
    ) -> tuple[int, int, np.ndarray]:
        """Host-side build of the single-sequence prefill pack; returns
        (t_pad, c_pad, packed)."""
        t = len(token_ids)
        (tokens, positions_dev, write_slots, gather_slots,
         t_pad, c_pad) = self._prefill_host_prep(
            token_ids, block_table, start_pos, total_len
        )
        want_plp = prompt_lp_targets is not None
        layout, size = self._prefill_pack_layout(t_pad, c_pad, want_plp)
        packed = np.zeros((size,), np.int32)
        put = functools.partial(self._pack_put, packed, layout)
        put("tokens", tokens)
        put("positions", positions_dev)
        put("write_slots", write_slots)
        put("gather_slots", gather_slots)
        put("total_len", np.asarray([total_len], np.int32))
        put("last_row", np.asarray([t - 1], np.int32))
        temps, top_ps, top_ks, min_ps, keys = self._sampling_args(
            1, sampling
        )
        put("temps", temps)
        put("top_ps", top_ps)
        put("top_ks", top_ks)
        put("min_ps", min_ps)
        put("keys", keys)
        if want_plp:
            tg = np.full((t_pad,), -1, np.int32)
            tg[: len(prompt_lp_targets)] = prompt_lp_targets
            put("targets", tg)
        return t_pad, c_pad, packed

    def _fill_packed_prefill_pack(
        self,
        chunks: list[list[int]],
        start_positions: list[int],
        block_tables: list[list[int]],
        total_lens: list[int],
        sampling=None,
    ) -> tuple[int, int, int, np.ndarray]:
        """Host-side build of the packed cross-sequence prefill pack;
        returns (s_pad, t_pad, c_pad, packed)."""
        n = len(chunks)
        (s_pad, t_pad, c_pad, tokens, positions_dev, write_slots,
         q_starts, tl_full, tables) = self._packed_host_prep(
            chunks, start_positions, block_tables, total_lens
        )
        last_rows = np.zeros((s_pad,), dtype=np.int32)
        for s, ids in enumerate(chunks):
            last_rows[s] = s * t_pad + (len(ids) - 1)
        for s in range(n, s_pad):
            last_rows[s] = s * t_pad
        layout, size = self._packed_prefill_pack_layout(
            s_pad, t_pad, c_pad
        )
        packed = np.zeros((size,), np.int32)
        put = functools.partial(self._pack_put, packed, layout)
        put("tokens", tokens.reshape(-1))
        put("positions", positions_dev.reshape(-1))
        put("write_slots", write_slots.reshape(-1))
        put("tables", tables)
        put("q_starts", q_starts)
        put("total_lens", tl_full)
        put("last_rows", last_rows)
        temps, top_ps, top_ks, min_ps, keys = self._sampling_args(
            s_pad, sampling
        )
        put("temps", temps)
        put("top_ps", top_ps)
        put("top_ks", top_ks)
        put("min_ps", min_ps)
        put("keys", keys)
        return s_pad, t_pad, c_pad, packed

    def _build_prefill(self, t_pad: int, c_pad: int,
                       want_prompt_lp: bool = False):
        mc = self.model_config
        from production_stack_tpu.engine.sampler import (
            sample_tokens,
            token_logprobs,
        )

        attn = self._prefill_attn_closure()

        def step(params, kc, vc, tokens, positions, write_slots,
                 gather_slots, total_len, last_row, temps, top_ps,
                 top_ks, min_ps, keys, targets=None,
                 lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            attn_fn = functools.partial(
                attn,
                gather_slots=gather_slots,
                q_positions=positions,
                total_len=total_len,
                mapped=self._map_tables(kc, gather_slots),
            )
            logits, kc, vc = self._forward(
                mc, params, tokens, positions, kc, vc, write_slots,
                lambda q, l, k, v, spec=None: attn_fn(
                    q, l, k, v, spec=spec),
                # prompt-logprobs needs every row's distribution; the
                # normal path materializes only the LAST row (the first
                # generated token's) to keep the program output small
                logits_rows=(
                    jnp.arange(t_pad) if want_prompt_lp
                    else last_row[None]
                ),
                lora=lora, lora_slots=lora_slots,
                **self._state_kw(1, t_pad, 0),
            )
            last_logits = logits[last_row] if want_prompt_lp else logits[0]
            # sample the first generated token ON DEVICE: the host then
            # fetches 4 bytes instead of a (vocab,) f32 row — the logit
            # fetch was the dominant per-prompt TTFT cost through
            # remote-attached chips (the logits output stays available
            # for penalty/debug paths, unfetched)
            token = sample_tokens(last_logits[None], temps, top_ps,
                                  top_ks, keys, min_p=min_ps)[0]
            if not want_prompt_lp:
                return token, last_logits, kc, vc
            # vLLM prompt_logprobs role, computed ON DEVICE: row i's
            # distribution scores prompt token i+1 (`targets`, -1 =
            # masked padding row). The host fetches (t_pad,) chosen +
            # (t_pad, CAP) alternatives — never (t_pad, vocab) rows.
            # Same extraction as generation logprobs (sampler.
            # token_logprobs), so the two stay semantics-identical.
            chosen, top_vals, top_ids = token_logprobs(
                logits, jnp.maximum(targets, 0)
            )
            chosen = jnp.where(targets >= 0, chosen, 0.0)
            return (token, last_logits, chosen, top_vals, top_ids,
                    kc, vc)

        jit_kw = self._step_jit_kwargs(2 if not want_prompt_lp else 5)
        if not self.prefill_pipeline:
            return jit_program(
                "prefill", step, donate_argnums=(1, 2), **jit_kw)

        # pipelined variant: ONE fused i32 operand instead of ~8 small
        # h2d transfers (layout shared with the host build,
        # _prefill_pack_layout); unpack on device then run the SAME step
        layout, _size = self._prefill_pack_layout(
            t_pad, c_pad, want_prompt_lp
        )

        def _seg(packed, name, _lo=layout):
            return self._pack_seg(packed, _lo, name)

        def packed_step(params, kc, vc, packed, lora=None,
                        lora_slots=None):
            def f32(name):
                return jax.lax.bitcast_convert_type(
                    _seg(packed, name), jnp.float32
                )

            plp_kw = (
                {"targets": _seg(packed, "targets")}
                if want_prompt_lp else {}
            )
            return step(
                params, kc, vc,
                _seg(packed, "tokens"),
                _seg(packed, "positions"),
                _seg(packed, "write_slots"),
                _seg(packed, "gather_slots"),
                _seg(packed, "total_len")[0],
                _seg(packed, "last_row")[0],
                f32("temps"), f32("top_ps"),
                _seg(packed, "top_ks"), f32("min_ps"),
                jax.lax.bitcast_convert_type(
                    _seg(packed, "keys"), jnp.uint32
                ),
                lora=lora, lora_slots=lora_slots,
                **plp_kw,
            )

        return jit_program(
            "prefill", packed_step, donate_argnums=(1, 2), **jit_kw)

    def _build_verify_batch(self, s_pad: int, t_pad: int, c_pad: int):
        """Batched speculative verification: s_pad lanes' draft chunks
        [last_token, d_1..d_k] run in ONE packed prefill-shaped forward,
        and EVERY row is sampled on device with its own PRNG key.

        Because the engine's sampling keys depend only on
        (seed, generated_len) — not on sampled history — row j of a lane
        samples with the exact key autoregressive step j would have
        used, so acceptance-by-equality yields outputs bit-identical to
        sequential sampling at any temperature (greedy rows reduce to
        argmax inside sample_tokens). The host fetches (s_pad*t_pad,)
        int32 instead of per-row vocab logits."""
        mc = self.model_config
        from production_stack_tpu.engine.sampler import sample_tokens

        attn = self._packed_attn_closure(s_pad, t_pad)

        def step(params, kc, vc, tokens, positions, write_slots, tables,
                 q_starts, total_lens, temps, top_ps, top_ks, min_ps,
                 keys, lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            attn_fn = functools.partial(
                attn,
                tables=tables,
                q_starts=q_starts,
                positions2d=positions.reshape(s_pad, t_pad),
                total_lens=total_lens,
                mapped=self._map_tables(kc, tables),
            )
            logits, kc, vc = self._forward(
                mc, params, tokens, positions, kc, vc, write_slots,
                lambda q, l, k, v, spec=None: attn_fn(
                    q, l, k, v, spec=spec),
                logits_rows=jnp.arange(s_pad * t_pad),
                lora=lora, lora_slots=lora_slots,
            )
            sampled = sample_tokens(logits, temps, top_ps, top_ks, keys,
                                    min_p=min_ps)
            return sampled, kc, vc

        return jit_program("verify", step, donate_argnums=(1, 2),
                           **self._step_jit_kwargs(1))

    def verify_batch(
        self,
        chunks: list[list[int]],
        start_positions: list[int],
        block_tables: list[list[int]],
        total_lens: list[int],
        row_sampling: tuple,
        lora_slots: list[int] | None = None,
    ) -> np.ndarray:
        """Run one packed verification forward over n lanes' draft
        chunks; returns (n, t_pad) int32 — row (s, j) is the token the
        seeded sampler picks from lane s's distribution after consuming
        chunk row j. `row_sampling` = per-lane (temps, top_ps, top_ks,
        seeds, key_starts) arrays; row j of lane s samples with key
        (seeds[s], key_starts[s] + j), the key autoregressive step j
        would use. KV for every fed row is written; rejected rows'
        garbage KV sits beyond every reader's context length until real
        tokens overwrite it."""
        n = len(chunks)
        (s_pad, t_pad, c_pad, tokens, positions_dev, write_slots,
         q_starts, tl_full, tables) = self._packed_host_prep(
            chunks, start_positions, block_tables, total_lens
        )

        # per-ROW sampling arrays, padded lane-major to (s_pad * t_pad,)
        (l_temps, l_top_ps, l_top_ks, l_min_ps, l_seeds,
         l_starts) = row_sampling
        temps = np.zeros((s_pad, t_pad), np.float32)
        top_ps = np.ones((s_pad, t_pad), np.float32)
        top_ks = np.full((s_pad, t_pad), -1, np.int32)
        min_ps_g = np.zeros((s_pad, t_pad), np.float32)
        keys = np.zeros((s_pad, t_pad, 2), np.uint32)
        temps[:n] = np.asarray(l_temps, np.float32)[:, None]
        top_ps[:n] = np.asarray(l_top_ps, np.float32)[:, None]
        top_ks[:n] = np.asarray(l_top_ks, np.int32)[:, None]
        min_ps_g[:n] = np.asarray(l_min_ps, np.float32)[:, None]
        keys[:n, :, 0] = np.asarray(l_seeds, np.uint32)[:, None]
        keys[:n, :, 1] = (
            np.asarray(l_starts, np.int64)[:, None]
            + np.arange(t_pad, dtype=np.int64)[None, :]
        ).astype(np.uint32)

        key = (s_pad, t_pad, c_pad)
        build = phases.NO_SPAN
        if key not in self._verify_batch_fns:
            logger.info(
                "compiling batched verify step s=%d t=%d ctx=%d",
                s_pad, t_pad, c_pad,
            )
            build = self._note_compile("verify", key)
            self._verify_batch_fns[key] = self._build_verify_batch(
                s_pad, t_pad, c_pad
            )
        fn = self._verify_batch_fns[key]
        lora_kw = self._packed_lora_kwargs(lora_slots, n, s_pad, t_pad)
        self._note_attn_context(prefill_lens=total_lens)
        self.note_sampler(1, l_temps)
        with self.phases.span("dispatch"), build:
            sampled, self.k_cache, self.v_cache = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                jnp.asarray(tokens.reshape(-1)),
                jnp.asarray(positions_dev.reshape(-1)),
                jnp.asarray(write_slots.reshape(-1)),
                jnp.asarray(tables),
                jnp.asarray(q_starts),
                jnp.asarray(tl_full),
                jnp.asarray(temps.reshape(-1)),
                jnp.asarray(top_ps.reshape(-1)),
                jnp.asarray(top_ks.reshape(-1)),
                jnp.asarray(min_ps_g.reshape(-1)),
                jnp.asarray(keys.reshape(-1, 2)),
                **lora_kw,
            )
        with self.phases.span("fetch"):
            out = np.asarray(sampled).reshape(s_pad, t_pad)[:n]
        return out

    def _packed_host_prep(
        self,
        chunks: list[list[int]],
        start_positions: list[int],
        block_tables: list[list[int]],
        total_lens: list[int],
    ):
        """Host-side packing shared by prefill_batch and verify_batch:
        bucket n ragged chunks to (s_pad, t_pad), build per-row
        positions/write-slots (padded rows park at position 0 writing
        the trash slot) and per-lane attention tables for the active
        impl. Returns (s_pad, t_pad, c_pad, tokens, positions_dev,
        write_slots, q_starts, tl_full, tables)."""
        n = len(chunks)
        s_pad = next_pow2(max(n, 1))
        t_pad = self._prefill_bucket(max(len(c) for c in chunks))
        c_pad = max(self._ctx_bucket(tl) for tl in total_lens)

        tokens = np.zeros((s_pad, t_pad), dtype=np.int32)
        positions = np.full((s_pad, t_pad), -1, dtype=np.int32)
        write_slots = np.zeros((s_pad, t_pad), dtype=np.int32)
        q_starts = np.zeros((s_pad,), dtype=np.int32)
        tl_full = np.ones((s_pad,), dtype=np.int32)
        for s, (ids, start) in enumerate(zip(chunks, start_positions)):
            t = len(ids)
            tokens[s, :t] = ids
            positions[s, :t] = np.arange(start, start + t)
            write_slots[s] = self._slots_for_positions(
                block_tables[s], positions[s]
            )
            q_starts[s] = start
            tl_full[s] = total_lens[s]
        # padded rows/sequences: position -1 -> rope of position 0, write
        # to the trash slot; their attention output is never read
        positions_dev = np.where(positions < 0, 0, positions).astype(
            np.int32
        )
        if self.attention_impl == "pallas":
            n_pages = c_pad // self.block_size
            tables = np.stack([
                self._padded_block_table(
                    block_tables[s] if s < n else [], n_pages
                )
                for s in range(s_pad)
            ])
        else:
            tables = np.zeros((s_pad, c_pad), dtype=np.int32)
            for s in range(n):
                tables[s] = self._gather_slots_for_table(
                    block_tables[s], c_pad
                )
        return (s_pad, t_pad, c_pad, tokens, positions_dev, write_slots,
                q_starts, tl_full, tables)

    def _packed_lora_kwargs(
        self, lora_slots, n: int, s_pad: int, t_pad: int
    ) -> dict:
        """Uniform-adapter fast path vs per-token slot vector, shared by
        the packed prefill/verify entries."""
        if self.lora_manager is None:
            return {}
        slots = lora_slots if lora_slots is not None else [0] * n
        if len(set(slots)) <= 1:
            # whole group shares one adapter: uniform fast path
            slots_arg = jnp.int32(slots[0] if slots else 0)
        else:
            per_tok = np.zeros((s_pad, t_pad), dtype=np.int32)
            for s, slot in enumerate(slots):
                per_tok[s] = slot
            slots_arg = jnp.asarray(per_tok.reshape(-1))
        return {
            "lora": self.lora_manager.buffers,
            "lora_slots": slots_arg,
        }

    def _packed_attn_closure(self, s_pad: int, t_pad: int):
        """Attention over s_pad back-to-back chunks on one flat token
        axis (row s*t_pad + r is row r of chunk s) — shared by the
        packed-prefill and batched-verify builders."""
        mc = self.model_config
        scale = self._scale

        if self.attention_impl == "pallas":
            # ONE ragged-kernel launch over the whole packed token
            # axis: every block of t_pad (pow2 >= RAGGED_TQ) belongs
            # to exactly one lane, so per-block segment metadata is a
            # static lane map + the traced q_starts
            tq = RAGGED_TQ
            n_blk = (s_pad * t_pad) // tq
            lane_of = np.arange(n_blk, dtype=np.int32) * tq // t_pad
            off_in = (np.arange(n_blk, dtype=np.int32) * tq) % t_pad

            def attn(q, l, kc, vc, tables, q_starts, positions2d,
                     total_lens, spec=None, mapped=None):
                blk_seg = jnp.arange(n_blk + 1, dtype=jnp.int32)
                seg_meta = jnp.stack([
                    jnp.asarray(lane_of),
                    jnp.zeros((n_blk,), jnp.int32),
                    jnp.full((n_blk,), tq, jnp.int32),
                    q_starts[lane_of] + jnp.asarray(off_in),
                ], axis=1)
                return self._attn(
                    "ragged", q, l, kc, vc, tables, blk_seg, seg_meta,
                    spec=spec, mapped=mapped,
                )
        else:

            # tables: (s_pad, c_pad) per-sequence gather slots
            def attn(q, l, kc, vc, tables, q_starts, positions2d,
                     total_lens, spec=None, mapped=None):
                # (s, c, nkv, d)
                k_ctx, v_ctx, kw = self._xla_ctx(
                    kc, vc, l, tables, spec, mapped)
                # q's heads are the layer kind's own
                qs = q.reshape(s_pad, t_pad, *q.shape[1:])
                out = jax.vmap(
                    functools.partial(
                        xla_attn.context_attention_prefill, **kw
                    ),
                    in_axes=(0, 0, 0, 0, 0, None),
                )(qs, k_ctx, v_ctx, positions2d, total_lens, scale)
                return out.reshape(s_pad * t_pad, q.shape[1], -1)

        return attn

    def _make_prefill_batch_step(self, s_pad: int, t_pad: int):
        """The raw (un-jitted) packed cross-sequence prefill step: chunks
        from s_pad sequences run in ONE device program (one dispatch
        instead of s_pad — burst-TTFT fix; reference capability bar is
        vLLM's batched chunked prefill, reference:
        helm/templates/deployment-vllm-multi.yaml:140-146).

        The flat token axis carries the s_pad chunks back to back
        (row s*t_pad + r is row r of chunk s): the embedding, projections,
        MLP, and cache scatters are already per-token, so they batch for
        free on the MXU; only attention needs per-sequence handling. The
        Pallas path unrolls the hardware-validated single-sequence kernel
        s_pad times inside the jitted step — TPU grid programs run
        sequentially on the core anyway, so this matches a batched-grid
        kernel's schedule without forking a second Mosaic kernel.

        Shared by _build_prefill_batch (which jits it) and the ragged
        dispatch builder (which composes it with the decode scan inside
        ONE jitted round)."""
        mc = self.model_config
        from production_stack_tpu.engine.sampler import sample_tokens

        attn = self._packed_attn_closure(s_pad, t_pad)

        def step(params, kc, vc, tokens, positions, write_slots, tables,
                 q_starts, total_lens, last_rows, temps, top_ps, top_ks,
                 min_ps, keys, lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            attn_fn = functools.partial(
                attn,
                tables=tables,
                q_starts=q_starts,
                positions2d=positions.reshape(s_pad, t_pad),
                total_lens=total_lens,
                mapped=self._map_tables(kc, tables),
            )
            logits, kc, vc = self._forward(
                mc, params, tokens, positions, kc, vc, write_slots,
                lambda q, l, k, v, spec=None: attn_fn(
                    q, l, k, v, spec=spec),
                logits_rows=last_rows,
                lora=lora, lora_slots=lora_slots,
                **self._state_kw(s_pad, t_pad, 0),
            )
            # on-device first-token sampling (see _build_prefill): the
            # host fetches (s_pad,) int32, not (s_pad, vocab) f32
            sampled = sample_tokens(logits, temps, top_ps, top_ks, keys,
                                    min_p=min_ps)
            return sampled, logits, kc, vc

        return step

    def _make_prefill_batch_packed(self, s_pad: int, t_pad: int,
                                   c_pad: int):
        """Fused-buffer wrapper of _make_prefill_batch_step: one i32
        operand (layout _packed_prefill_pack_layout), unpacked on device
        (see _build_prefill). Un-jitted — _build_prefill_batch jits it,
        the ragged builder inlines it."""
        step = self._make_prefill_batch_step(s_pad, t_pad)
        layout, _size = self._packed_prefill_pack_layout(
            s_pad, t_pad, c_pad
        )

        def _seg(packed, name, _lo=layout):
            return self._pack_seg(packed, _lo, name)

        def packed_step(params, kc, vc, packed, lora=None,
                        lora_slots=None):
            def f32(name):
                return jax.lax.bitcast_convert_type(
                    _seg(packed, name), jnp.float32
                )

            return step(
                params, kc, vc,
                _seg(packed, "tokens"),
                _seg(packed, "positions"),
                _seg(packed, "write_slots"),
                _seg(packed, "tables"),
                _seg(packed, "q_starts"),
                _seg(packed, "total_lens"),
                _seg(packed, "last_rows"),
                f32("temps"), f32("top_ps"),
                _seg(packed, "top_ks"), f32("min_ps"),
                jax.lax.bitcast_convert_type(
                    _seg(packed, "keys"), jnp.uint32
                ),
                lora=lora, lora_slots=lora_slots,
            )

        return packed_step

    def _build_prefill_batch(self, s_pad: int, t_pad: int, c_pad: int):
        """Jitted packed cross-sequence prefill (raw-args variant, or
        the fused-buffer variant under the prefill pipeline)."""
        jit_kw = self._step_jit_kwargs(2)
        if not self.prefill_pipeline:
            return jit_program(
                "prefill_batch",
                self._make_prefill_batch_step(s_pad, t_pad),
                donate_argnums=(1, 2), **jit_kw,
            )
        return jit_program(
            "prefill_batch",
            self._make_prefill_batch_packed(s_pad, t_pad, c_pad),
            donate_argnums=(1, 2), **jit_kw,
        )

    def _decode_attn_closure(self):
        """The decode-shaped attention callback shared by the
        single-step, fused-K, and ragged-round builders: the ragged
        kernel in all-decode-row configuration (decode lanes are
        single-row segments of the one grid — the SAME program the
        mixed rounds launch), or the XLA gather path. `tables` =
        padded per-sequence block tables (b, pages) on the pallas
        path, per-position gather slots (b, c_pad) on the XLA path;
        `mapped` = the same as the windowed cache group holds them
        (`_map_tables`).
        `shared`: the row blocks' shared runs as the round's pack
        found them (`_shared_runs`), nothing to the XLA path."""
        scale = self._scale
        if self.attention_impl == "pallas":
            tq = RAGGED_TQ

            def attn(q, l, kc, vc, tables, context_lens, spec=None,
                     shared=None, mapped=None):
                b = q.shape[0]
                r_pad = _ceil_tq(b)
                n_blk = r_pad // tq
                qp = jnp.pad(q, ((0, r_pad - b), (0, 0), (0, 0)))
                # one segment per lane — one row where the lane holds
                # a token this step, none where it does not (context
                # length 0: the kernel walks nothing and zeroes the
                # row); blocks hold up to TQ lanes (CSR offsets clip
                # at the lane count)
                blk_seg = jnp.minimum(
                    jnp.arange(n_blk + 1, dtype=jnp.int32) * tq, b
                )
                lanes = jnp.arange(b, dtype=jnp.int32)
                seg_meta = jnp.stack([
                    lanes,
                    lanes % tq,
                    (context_lens > 0).astype(jnp.int32),
                    context_lens - 1,
                ], axis=1)
                out = self._attn(
                    "ragged", qp, l, kc, vc, tables, blk_seg, seg_meta,
                    spec=spec, shared=shared, mapped=mapped,
                )
                return out[:b]
        else:

            def attn(q, l, kc, vc, tables, context_lens, spec=None,
                     shared=None, mapped=None):
                # (b, c, nkv, d)
                k_ctx, v_ctx, kw = self._xla_ctx(
                    kc, vc, l, tables, spec, mapped)
                return xla_attn.context_attention_decode(
                    q, k_ctx, v_ctx, context_lens, scale, **kw
                )

        return attn

    def _build_decode(self, b: int, c_pad: int):
        mc = self.model_config
        attn = self._decode_attn_closure()

        def step(params, kc, vc, tokens, positions, write_slots,
                 tables, context_lens, lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            attn_fn = functools.partial(
                attn, tables=tables, context_lens=context_lens,
                mapped=self._map_tables(kc, tables),
            )
            logits, kc, vc = self._forward(
                mc, params, tokens, positions, kc, vc, write_slots,
                lambda q, l, k, v, spec=None: attn_fn(
                    q, l, k, v, spec=spec),
                logits_rows=jnp.arange(b),
                lora=lora, lora_slots=lora_slots,
                **self._state_kw(0, 0, b),
            )
            return logits, kc, vc

        return jit_program("decode", step, donate_argnums=(1, 2),
                           **self._step_jit_kwargs())

    def _decode_pack_layout(self, b: int, c_pad: int, chained: bool,
                            guided: bool = False,
                            stop_cap: int | None = None):
        """Static layout of the ONE int32 host->device buffer a
        multi-step decode dispatch ships.

        Packing the ~8 small per-dispatch arrays (tokens, positions,
        context lens, sampling params, page tables) into one transfer
        makes the h2d one buffer creation instead of eight (cost of
        either on an attached chip: not measured).
        f32/u32 fields travel bitcast as i32 and are bitcast back on
        device. Returns ({name: (offset, shape)}, total_len).

        `stop_cap` (device-side stop masks): None = the fixed-trip
        program without stop fields (--no-device-stop control); an int
        adds the per-lane EOS id, min_tokens gate, remaining-budget
        countdown, and — when > 0 — a (b, stop_cap) padded
        stop-token-id matrix."""
        n_pages = c_pad // self.block_size
        fields: list[tuple[str, tuple[int, ...]]] = []
        if not chained:
            fields.append(("tokens", (b,)))
        fields += [
            ("positions", (b,)),
            ("ctx", (b,)),
            ("temps", (b,)),
            ("top_ps", (b,)),
            ("top_ks", (b,)),
            ("min_ps", (b,)),
            ("keys", (b, 2)),
            ("page_tables", (b, n_pages)),
        ]
        if self.attention_impl == "pallas":
            # a row block's [shared_keys, lane] (`_shared_runs`)
            fields.append(("shared_run", (_ceil_tq(b) // RAGGED_TQ, 2)))
        if guided:
            # per-lane DFA state + machine row (the big tables travel
            # separately, device-cached across dispatches)
            fields += [("g_state", (b,)), ("g_lane", (b,))]
        if stop_cap is not None:
            fields += [
                ("stop_eos", (b,)),
                ("stop_min", (b,)),
                ("stop_budget", (b,)),
            ]
            if stop_cap > 0:
                fields.append(("stop_ids", (b, stop_cap)))
        if self.attention_impl != "pallas":
            fields.append(("gather_tables", (b, c_pad)))
        return self._layout_of(fields)

    def _make_decode_multi_step(self, b: int, c_pad: int, k_steps: int,
                                use_penalties: bool = False,
                                want_logprobs: bool = False,
                                chained: bool = False,
                                guided_shapes: tuple | None = None,
                                bias_cap: int = 0,
                                stop_cap: int | None = None):
        """K fused decode+sample iterations per dispatch (the raw,
        un-jitted step — _build_decode_multi jits it; the ragged
        dispatch builder composes it with the packed prefill step
        inside ONE jitted round).

        Every single step ends in a device-to-host fetch of the
        sampled token. Sampling on device and chaining K iterations
        inside one jitted scan makes that one fetch per K tokens (cost
        of the fetch on an attached chip: not measured) (vLLM's
        --num-scheduler-steps semantics; MaxText's on-device sampling
        loop is the same idea). The per-iteration sampling keys are
        (seed, generated_len + i) — bit-identical to K single steps, so
        multi-step changes throughput, never outputs.

        Host-side inputs arrive as ONE packed i32 buffer
        (`_decode_pack_layout`); `chained=True` builds the variant whose
        tokens come from the previous round's on-device output instead.

        `stop_cap` is not None => device-side stop masks (elastic
        fused decode): a per-lane done mask rides the loop carry. A
        lane is done once its per-round append count reaches its
        remaining budget (max_tokens/max_model_len countdown) or it
        samples its EOS / one of its stop_token_ids at or past its
        min_tokens gate. A done lane FREEZES — its sampled slot is
        pinned to STOP_PAD_TOKEN, its KV-slot write is redirected to
        the trash slot, its position/context stop advancing, and its
        penalty-count/guided-DFA state stops updating — so overshoot
        slots cost no cache or state corruption and the loop runs as a
        lax.while_loop that exits the whole round as soon as EVERY
        lane is done. The program then additionally returns a (b,)
        int32 per-lane VALID count (tokens sampled before freezing);
        tokens at positions >= valid[lane] are pad, never host-applied.
        Tokens below the valid count are bit-identical to the
        fixed-trip program — masking engages strictly after the stop
        token is sampled."""
        core = self._decode_round_core(
            b, c_pad, k_steps, use_penalties=use_penalties,
            want_logprobs=want_logprobs, chained=chained,
            guided_shapes=guided_shapes, bias_cap=bias_cap,
            stop_cap=stop_cap,
        )

        def step(params, kc, vc, packed, chained_tokens=None,
                 g_token_class=None, g_class_mask=None, g_class_trans=None,
                 gen_ids=None, presence=None, frequency=None,
                 repetition=None, lb_ids=None, lb_vals=None,
                 lora=None, lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            consts, carry0 = core["unpack"](
                packed, kc, chained_tokens=chained_tokens,
                g_token_class=g_token_class, g_class_mask=g_class_mask,
                g_class_trans=g_class_trans, gen_ids=gen_ids,
                presence=presence, frequency=frequency,
                repetition=repetition, lb_ids=lb_ids, lb_vals=lb_vals,
            )
            return core["run"](params, kc, vc, consts, carry0,
                               lora=lora, lora_slots=lora_slots)

        return step

    def _decode_round_core(self, b: int, c_pad: int, k_steps: int,
                           use_penalties: bool = False,
                           want_logprobs: bool = False,
                           chained: bool = False,
                           guided_shapes: tuple | None = None,
                           bias_cap: int = 0,
                           stop_cap: int | None = None):
        """Shared internals of the fused-K decode round, factored into
        unpack / forward / post-sample / loop closures so the packed
        dispatch (_make_decode_multi_step) and the fused lane-typed
        round (_build_ragged_rows — whose FIRST decode iteration's
        forward is welded to the prefill rows inside one ragged-kernel
        grid) run IDENTICAL per-step math. `run(first_logits=...)`
        consumes an externally computed step-0 logits and continues
        the loop from iteration 1; without it the loop is exactly the
        packed dispatch's scan/while_loop."""
        mc = self.model_config
        bs = self.block_size
        from production_stack_tpu.engine.sampler import (
            LOGPROB_CAP,
            STOP_PAD_TOKEN,
            apply_penalties,
            sample_tokens,
            stop_hit,
            token_logprobs,
        )

        attn = self._decode_attn_closure()
        use_pages = self.attention_impl == "pallas"
        use_stop = stop_cap is not None
        layout, _total = self._decode_pack_layout(
            b, c_pad, chained, guided=guided_shapes is not None,
            stop_cap=stop_cap,
        )

        def _seg(packed, name, _lo=layout):
            return self._pack_seg(packed, _lo, name)

        lane = jnp.arange(b)

        def unpack(packed, kc, chained_tokens=None, g_token_class=None,
                   g_class_mask=None, g_class_trans=None, gen_ids=None,
                   presence=None, frequency=None, repetition=None,
                   lb_ids=None, lb_vals=None):
            """Decode-pack fields -> (consts dict, initial carry). `kc`:
            the K side as the round was handed it (its block map: the
            windowed kind's tables are mapped here, once a round)."""
            tokens = (
                chained_tokens if chained else _seg(packed, "tokens")
            )
            positions = _seg(packed, "positions")
            context_lens = _seg(packed, "ctx")
            page_tables = _seg(packed, "page_tables")
            attn_tables = (
                page_tables if use_pages
                else _seg(packed, "gather_tables"))
            consts = {
                "temps": jax.lax.bitcast_convert_type(
                    _seg(packed, "temps"), jnp.float32
                ),
                "top_ps": jax.lax.bitcast_convert_type(
                    _seg(packed, "top_ps"), jnp.float32
                ),
                "top_ks": _seg(packed, "top_ks"),
                "min_ps": jax.lax.bitcast_convert_type(
                    _seg(packed, "min_ps"), jnp.float32
                ),
                "base_keys": jax.lax.bitcast_convert_type(
                    _seg(packed, "keys"), jnp.uint32
                ),
                "page_tables": page_tables,
                "attn_tables": attn_tables,
                "mapped_tables": self._map_tables(kc, attn_tables),
                "shared_run": (
                    _seg(packed, "shared_run") if use_pages else None),
                "presence": presence,
                "frequency": frequency,
                "repetition": repetition,
                "lb_ids": lb_ids,
                "lb_vals": lb_vals,
                "g_class_mask": g_class_mask,
                "g_class_trans": g_class_trans,
            }

            if use_penalties:
                # per-lane generated-token counts, maintained ON DEVICE
                # across the scan so penalty sampling needs no host round
                # trip (gen_ids: (b, c_pad) int32, -1 padded)
                valid = (gen_ids >= 0).astype(jnp.float32)
                counts0 = jnp.zeros(
                    (b, mc.vocab_size), jnp.float32
                ).at[lane[:, None], jnp.maximum(gen_ids, 0)].add(valid)
            else:
                counts0 = jnp.zeros((b, 1), jnp.float32)  # unused carry

            if guided_shapes is not None:
                # (b, V) class of every token for each lane's machine,
                # gathered once per dispatch outside the scan
                consts["lane_tc"] = g_token_class[_seg(packed, "g_lane")]
                g_state0 = _seg(packed, "g_state")
            else:
                consts["lane_tc"] = None
                g_state0 = jnp.zeros((b,), jnp.int32)  # unused carry

            if use_stop:
                consts["eos_ids"] = _seg(packed, "stop_eos")
                consts["min_need"] = _seg(packed, "stop_min")
                budget = _seg(packed, "stop_budget")
                consts["budget"] = budget
                consts["s_ids"] = (
                    _seg(packed, "stop_ids") if stop_cap else None
                )
                # padded lanes ship budget 0: done from iteration 0, so
                # an all-real-lanes-finished round early-exits even
                # when the static lane count exceeds the live batch
                done0 = budget <= 0
            else:
                consts["s_ids"] = None
                done0 = jnp.zeros((b,), bool)  # unused carry
            valid0 = jnp.zeros((b,), jnp.int32)
            carry0 = (tokens, positions, context_lens, counts0,
                      g_state0, done0, valid0)
            return consts, carry0

        def fwd_args(carry, consts):
            """(tokens, positions, write_slots, ctx) for one decode
            forward — shared by the in-loop forward and the fused
            round's step-0 mixed forward."""
            tokens, positions, ctx = carry[0], carry[1], carry[2]
            done = carry[5]
            # slot for each lane's current position from its block
            # table (idle lanes carry the zero table -> trash block 0;
            # K <= block_size keeps them inside it)
            write_slots = (
                consts["page_tables"][lane, positions // bs] * bs
                + positions % bs
            )
            if use_stop:
                # frozen lanes write the trash slot: a done lane's
                # overshoot KV must never land past its real end; and
                # attention gets them as it gets a lane that holds no
                # sequence, context 0: nothing to walk for the rest of
                # the round (the carry keeps the lane's real context)
                write_slots = jnp.where(done, 0, write_slots)
                ctx = jnp.where(done, 0, ctx)
            return tokens, positions, write_slots, ctx

        def fwd(params, kc, vc, carry, consts, lora, lora_slots):
            tokens, positions, write_slots, ctx = fwd_args(carry, consts)
            attn_fn = functools.partial(
                attn, tables=consts["attn_tables"], context_lens=ctx,
                shared=consts["shared_run"],
                mapped=consts["mapped_tables"],
            )
            logits, kc, vc = self._forward(
                mc, params, tokens, positions, kc, vc, write_slots,
                lambda q, l, k, v, spec=None: attn_fn(
                    q, l, k, v, spec=spec),
                logits_rows=lane,
                lora=lora, lora_slots=lora_slots,
                **self._rows_valid_kw(ctx > 0),
                **self._state_kw(0, 0, b),
            )
            return logits, kc, vc

        def post(logits, carry, i, consts):
            """Sample + stop/penalty/guided state advance for one
            iteration's logits; returns (carry', ys_i)."""
            (tokens, positions, ctx, counts, g_state, done,
             valid) = carry
            if use_penalties:
                logits = apply_penalties(
                    logits, counts > 0, counts, consts["presence"],
                    consts["frequency"], consts["repetition"],
                )
            if bias_cap:
                # OpenAI logit_bias: per-lane sparse additive bias
                # (padding adds 0.0 to token 0 — a no-op), applied
                # after penalties and before any guided mask, same
                # order as the host path (_sample)
                logits = logits.at[
                    lane[:, None], consts["lb_ids"]
                ].add(consts["lb_vals"])
            if guided_shapes is not None:
                # constraint mask from the lane's DFA state (same
                # penalties->mask->sample order as the host path)
                mask_c = consts["g_class_mask"][g_state]  # (b, C)
                allowed = jnp.take_along_axis(
                    mask_c, consts["lane_tc"], axis=1
                )                                         # (b, V)
                logits = jnp.where(allowed, logits, -jnp.inf)
            keys = consts["base_keys"].at[:, 1].add(
                jnp.asarray(i).astype(jnp.uint32)
            )
            nxt = sample_tokens(logits, consts["temps"],
                                consts["top_ps"], consts["top_ks"],
                                keys, min_p=consts["min_ps"])
            live = jnp.logical_not(done)
            if use_stop:
                # pin frozen lanes' sampled slots to the pad token
                # (the host reads only valid[lane] tokens anyway)
                nxt = jnp.where(done, STOP_PAD_TOKEN, nxt)
            if guided_shapes is not None:
                cls = jnp.take_along_axis(
                    consts["lane_tc"], nxt[:, None], axis=1
                )[:, 0]
                new_g = consts["g_class_trans"][g_state, cls]
                # a frozen lane's DFA state stops stepping (the pad
                # token is not part of its stream)
                g_state = (
                    jnp.where(done, g_state, new_g)
                    if use_stop else new_g
                )
            if use_penalties:
                # frozen lanes stop updating penalty counts: pinned
                # pad tokens are not generated output
                counts = counts.at[lane, nxt].add(
                    live.astype(jnp.float32) if use_stop else 1.0
                )
            valid = valid + live.astype(jnp.int32)
            if use_stop:
                # the sampled token is valid (the stop token itself
                # is appended, same as the host path); the lane
                # freezes FROM THE NEXT iteration. Budget first,
                # then the min_tokens-gated EOS/stop-id check —
                # check_stop's exact ordering.
                hit = stop_hit(nxt, consts["eos_ids"], consts["s_ids"])
                done = done | (valid >= consts["budget"]) | (
                    live & hit & (valid >= consts["min_need"])
                )
                adv = jnp.where(done, 0, 1)
            else:
                adv = 1
            if want_logprobs:
                # on-device logprobs ride the same single fetch —
                # (k, b) chosen + (k, b, CAP) top alternatives
                ys = (nxt, *token_logprobs(logits, nxt))
            else:
                ys = nxt
            carry = (nxt, positions + adv, ctx + adv, counts,
                     g_state, done, valid)
            return carry, ys

        def run(params, kc, vc, consts, carry0, lora=None,
                lora_slots=None, first_logits=None):
            def one(kc, vc, carry, i):
                logits, kc, vc = fwd(params, kc, vc, carry, consts,
                                     lora, lora_slots)
                carry, ys = post(logits, carry, i, consts)
                return kc, vc, carry, ys

            if not use_stop:

                def scan_one(sc, i):
                    kc, vc, c = sc
                    kc, vc, c, ys = one(kc, vc, c, i)
                    return (kc, vc, c), ys

                if first_logits is None:
                    (kc, vc, _), ys = jax.lax.scan(
                        scan_one, (kc, vc, carry0), jnp.arange(k_steps)
                    )
                    return ys, kc, vc  # ys: (k, b) toks [+ lp arrays]
                # fused lane-typed round: step 0's forward already ran
                # (welded to the prefill rows); apply its post half
                # here and scan the remaining iterations
                c, ys0 = post(first_logits, carry0, jnp.int32(0),
                              consts)
                (kc, vc, _), ys_rest = jax.lax.scan(
                    scan_one, (kc, vc, c), jnp.arange(1, k_steps)
                )
                ys = jax.tree_util.tree_map(
                    lambda a, r: jnp.concatenate([a[None], r], axis=0),
                    ys0, ys_rest,
                )
                return ys, kc, vc

            # device-stop variant: while_loop over preallocated output
            # rows so the round EXITS as soon as every lane is done —
            # an all-finished tail iteration would otherwise still pay
            # the full forward. Unwritten rows stay at the pad token;
            # the host consumes only valid[lane] tokens per lane.
            toks_buf = jnp.full((k_steps, b), STOP_PAD_TOKEN, jnp.int32)
            lp_bufs = ()
            if want_logprobs:
                lp_bufs = (
                    jnp.zeros((k_steps, b), jnp.float32),
                    jnp.zeros((k_steps, b, LOGPROB_CAP), jnp.float32),
                    jnp.zeros((k_steps, b, LOGPROB_CAP), jnp.int32),
                )

            def cond(state):
                i, c = state[0], state[3]
                done = c[5]
                return jnp.logical_and(
                    i < k_steps, jnp.logical_not(jnp.all(done))
                )

            def body(state):
                i, kc, vc, c, tb = state[:5]
                lps = list(state[5:])
                kc, vc, c, ys = one(kc, vc, c, i)
                if want_logprobs:
                    nxt, ch, tv, ti = ys
                    lps = [
                        lps[0].at[i].set(ch),
                        lps[1].at[i].set(tv),
                        lps[2].at[i].set(ti),
                    ]
                else:
                    nxt = ys
                tb = tb.at[i].set(nxt)
                return (i + 1, kc, vc, c, tb, *lps)

            c0 = carry0
            i0 = jnp.int32(0)
            if first_logits is not None:
                # fused round: seed the buffers with step 0's post
                # half, then loop from iteration 1 (the while cond
                # still early-exits once every lane is done)
                c0, ys0 = post(first_logits, carry0, jnp.int32(0),
                               consts)
                if want_logprobs:
                    nxt0, ch0, tv0, ti0 = ys0
                    lp_bufs = (
                        lp_bufs[0].at[0].set(ch0),
                        lp_bufs[1].at[0].set(tv0),
                        lp_bufs[2].at[0].set(ti0),
                    )
                else:
                    nxt0 = ys0
                toks_buf = toks_buf.at[0].set(nxt0)
                i0 = jnp.int32(1)
            state = jax.lax.while_loop(
                cond, body,
                (i0, kc, vc, c0, toks_buf, *lp_bufs),
            )
            _, kc, vc, c, tb = state[:5]
            valid = c[6]
            if want_logprobs:
                ys = (tb, *state[5:8], valid)
            else:
                ys = (tb, valid)
            return ys, kc, vc  # ys: (toks, [lp arrays,] valid)

        return {
            "layout": layout,
            "unpack": unpack,
            "fwd_args": fwd_args,
            "run": run,
            "lane": lane,
        }

    def _build_decode_multi(self, b: int, c_pad: int, k_steps: int,
                            use_penalties: bool = False,
                            want_logprobs: bool = False,
                            chained: bool = False,
                            guided_shapes: tuple | None = None,
                            bias_cap: int = 0,
                            stop_cap: int | None = None):
        """Jitted fused-K decode program (see _make_decode_multi_step)."""
        return jit_program(
            "decode_multi",
            self._make_decode_multi_step(
                b, c_pad, k_steps, use_penalties=use_penalties,
                want_logprobs=want_logprobs, chained=chained,
                guided_shapes=guided_shapes, bias_cap=bias_cap,
                stop_cap=stop_cap,
            ),
            donate_argnums=(1, 2), **self._step_jit_kwargs(),
        )

    # -- host-side helpers -------------------------------------------------
    # stackcheck: not-hot — host-side batch staging: numpy over python
    # block tables, no device arrays involved
    def _slots_for_positions(
        self, block_table: list[int], positions: np.ndarray
    ) -> np.ndarray:
        """Cache slots for absolute positions; positions beyond the table
        map to the trash slot 0."""
        bt = np.asarray(block_table, dtype=np.int32)
        max_pos = len(bt) * self.block_size
        safe = np.clip(positions, 0, max_pos - 1) if len(bt) else positions * 0
        slots = (
            bt[safe // self.block_size] * self.block_size
            + safe % self.block_size
        ).astype(np.int32)
        slots[positions >= max_pos] = 0
        slots[positions < 0] = 0
        return slots

    # stackcheck: not-hot — host-side batch staging: numpy over python
    # block tables, no device arrays involved
    def _padded_block_table(
        self, block_table: list[int], n_pages: int
    ) -> np.ndarray:
        """Block table padded/truncated to n_pages; padding pages point at
        the null block 0 (shared convention of both attention impls)."""
        bt = np.zeros((n_pages,), dtype=np.int32)
        use = min(len(block_table), n_pages)
        if use:
            bt[:use] = np.asarray(block_table[:use], dtype=np.int32)
        return bt

    def _page_table_rows(
        self, block_tables: list[list[int]], b: int, n_pages: int,
        lanes: np.ndarray | None = None,
    ) -> np.ndarray:
        """The decode lanes' tables as (b, n_pages) rows, table i at
        lane `lanes[i]` (None: i), padded like `_padded_block_table`. A
        sequence's row is KEPT from one pack to the
        next and extended by the ids its table gained, instead of being
        made from a Python list of a few hundred ids every round: while
        a sequence holds a table the block manager only appends to it (a
        new admission, or a preemption, hands the sequence a NEW list),
        so a kept row is good for as long as it is the same list and no
        shorter. Rows of tables that this pack did not see are dropped."""
        out = np.zeros((b, n_pages), dtype=np.int32)
        kept, seen = self._kept_rows, {}
        seats = _seats(lanes, len(block_tables)).tolist()
        for lane, table in zip(seats, block_tables):
            n = len(table)
            held, row, have = kept.get(id(table), (None, (), 0))
            if held is table and have <= n <= len(row):
                row[have:n] = table[have:]
            else:
                row = np.zeros((max(64, 2 * n),), dtype=np.int32)
                row[:n] = table
            seen[id(table)] = (table, row, n)
            use = min(n, n_pages)
            out[lane, :use] = row[:use]
        self._kept_rows = seen
        return out

    def _shared_runs(self, tables: np.ndarray, ctx: np.ndarray
                     ) -> np.ndarray:
        """The decode pack's shared runs (`shared_runs`), found once a
        round: the tables are grown for the round's steps before the
        pack, and contexts only grow inside it. Zeros under a mesh:
        the shard_map wrappers take no run (`_attn`), and no cell runs
        them."""
        if self.mesh is not None:
            return np.zeros((_ceil_tq(len(ctx)) // RAGGED_TQ, 2), np.int32)
        return shared_runs(tables, ctx, self.block_size)

    def decode_lanes(self, block_tables: list[list[int]]) -> np.ndarray:
        """The lanes a round's decode sequences take (`place_lanes`),
        from the first page of each one's table: a layer-group model's
        is its kind 0's, the table `shared_runs` reads. The engine asks
        once a round, hands the map to the dispatch (and to the stage
        of the next round) as `lanes=`, and reads what comes back a
        lane through it."""
        return place_lanes([t[0] for t in block_tables],
                           self.config.max_num_seqs, self._seat_least)

    def _gather_slots_for_table(
        self, block_table: list[int], c_pad: int
    ) -> np.ndarray:
        bt = self._padded_block_table(
            block_table, c_pad // self.block_size
        )
        offs = np.arange(self.block_size, dtype=np.int32)
        return (bt[:, None] * self.block_size + offs).reshape(-1)

    # -- public API --------------------------------------------------------
    @staticmethod
    # stackcheck: not-hot — host-side dispatch staging: np.asarray over
    # python sampling-param lists, no device arrays involved
    def _sampling_args(
        n: int, sampling=None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray,
               np.ndarray]:
        """Pad per-sequence sampling params to n rows (greedy defaults)."""
        temps = np.zeros((n,), np.float32)
        top_ps = np.ones((n,), np.float32)
        top_ks = np.full((n,), -1, np.int32)
        min_ps = np.zeros((n,), np.float32)
        keys = np.zeros((n, 2), np.uint32)
        if sampling is not None:
            t, p, k, mp, kd = sampling
            m = len(np.asarray(t).reshape(-1))
            temps[:m] = np.asarray(t, np.float32).reshape(-1)
            top_ps[:m] = np.asarray(p, np.float32).reshape(-1)
            top_ks[:m] = np.asarray(k, np.int32).reshape(-1)
            min_ps[:m] = np.asarray(mp, np.float32).reshape(-1)
            keys[:m] = np.asarray(kd, np.uint32).reshape(m, 2)
        return temps, top_ps, top_ks, min_ps, keys

    def prefill(
        self,
        token_ids: list[int],
        start_pos: int,
        block_table: list[int],
        total_len: int,
        lora_slot: int = 0,
        sampling=None,
        prompt_lp_targets: list[int] | None = None,
    ) -> tuple:
        """Run one prefill chunk; returns (token, logits) ON DEVICE where
        `token` is the first generated token sampled from the chunk's last
        *actual* row with `sampling` = (temps, top_ps, top_ks, keys)
        (greedy/zero-key defaults), and `logits` is that row's fp32
        (vocab,) for penalty/debug paths. K/V for the chunk is written
        into the cache.

        `prompt_lp_targets` (vLLM prompt_logprobs role): per-row NEXT
        prompt token ids (-1 = no target); selects a program variant
        that additionally returns (chosen (t_pad,) f32, top_vals
        (t_pad, CAP) f32, top_ids (t_pad, CAP) i32) device arrays —
        row i scores targets[i] under the model's distribution."""
        want_plp = prompt_lp_targets is not None
        lora_kw = {}
        if self.lora_manager is not None:
            # scalar slot: prefill is one sequence, so the whole chunk
            # shares one adapter and forward() takes the uniform fast path
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.int32(lora_slot),
            }
        if self.prefill_pipeline:
            with self.phases.span("pack"):
                t_pad, c_pad, packed = self._fill_prefill_pack(
                    token_ids, start_pos, block_table, total_len,
                    sampling=sampling,
                    prompt_lp_targets=prompt_lp_targets,
                )
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)
            fn, build = self._prefill_fn(t_pad, c_pad, want_plp)
            self._note_attn_context(prefill_lens=(total_len,))
            self.note_sampler(1, sampling and sampling[0])
            with self.phases.span("dispatch"), build:
                ys = fn(
                    self.params, self.k_cache, self.v_cache, packed_dev,
                    **lora_kw,
                )
            self.k_cache, self.v_cache = ys[-2], ys[-1]
            return ys[:-2]
        t = len(token_ids)
        with self.phases.span("pack"):
            (tokens, positions_dev, write_slots, gather_slots,
             t_pad, c_pad) = self._prefill_host_prep(
                token_ids, block_table, start_pos, total_len
            )
            temps, top_ps, top_ks, min_ps, keys = self._sampling_args(
                1, sampling
            )
            plp_kw = {}
            if want_plp:
                tg = np.full((t_pad,), -1, np.int32)
                tg[: len(prompt_lp_targets)] = prompt_lp_targets
                plp_kw = {"targets": jnp.asarray(tg)}
        with self.phases.span("h2d"):
            args = (
                jnp.asarray(tokens),
                jnp.asarray(positions_dev),
                jnp.asarray(write_slots),
                jnp.asarray(gather_slots),
                jnp.int32(total_len),
                jnp.int32(t - 1),
                jnp.asarray(temps),
                jnp.asarray(top_ps),
                jnp.asarray(top_ks),
                jnp.asarray(min_ps),
                jnp.asarray(keys),
            )
        fn, build = self._prefill_fn(t_pad, c_pad, want_plp)
        self._note_attn_context(prefill_lens=(total_len,))
        self.note_sampler(1, sampling and sampling[0])
        with self.phases.span("dispatch"), build:
            ys = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                *args,
                **plp_kw,
                **lora_kw,
            )
        self.k_cache, self.v_cache = ys[-2], ys[-1]
        return ys[:-2]

    def _prefill_fn(self, t_pad: int, c_pad: int, want_plp: bool):
        """(program, build annotation) of a single-sequence prefill."""
        key = (t_pad, c_pad, "plp") if want_plp else (t_pad, c_pad)
        build = phases.NO_SPAN
        if key not in self._prefill_fns:
            logger.info("compiling prefill step t=%d ctx=%d plp=%s",
                        t_pad, c_pad, want_plp)
            build = self._note_compile("prefill", key)
            self._prefill_fns[key] = self._build_prefill(
                t_pad, c_pad, want_prompt_lp=want_plp
            )
        return self._prefill_fns[key], build

    def prefill_batch(
        self,
        chunks: list[list[int]],
        start_positions: list[int],
        block_tables: list[list[int]],
        total_lens: list[int],
        lora_slots: list[int] | None = None,
        sampling=None,
    ) -> tuple[jax.Array, jax.Array]:
        """Run one prompt chunk for EACH of n sequences in a single packed
        dispatch; returns (tokens, logits) ON DEVICE — tokens (s_pad,)
        sampled from each chunk's last *actual* row with `sampling` =
        per-sequence (temps, top_ps, top_ks, keys), logits (s_pad, vocab)
        for penalty/debug paths (rows >= n are padding). K/V for every
        chunk is written into the cache."""
        n = len(chunks)
        if self.prefill_pipeline and self.ragged_kernel:
            # ragged-rows path: program keys on the padded ROW bucket
            # (r_pad, pc_pad), one kernel launch for any group
            with self.phases.span("pack"):
                r_pad, pc_pad, packed = self._fill_rows_prefill_pack(
                    chunks, start_positions, block_tables, total_lens,
                    sampling=sampling,
                )
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)
            key = ("rows", r_pad, pc_pad)
            build = phases.NO_SPAN
            if key not in self._prefill_batch_fns:
                logger.info(
                    "compiling ragged-rows prefill step rows=%d ctx=%d",
                    r_pad, pc_pad,
                )
                build = self._note_compile("prefill_rows", key)
                self._prefill_batch_fns[key] = self._build_prefill_rows(
                    r_pad, pc_pad
                )
            lora_kw = self._rows_lora_kwargs(lora_slots, chunks, r_pad)
            self._note_attn_context(prefill_lens=total_lens)
            self.note_sampler(1, sampling and sampling[0])
            with self.phases.span("dispatch"), build:
                sampled, logits, self.k_cache, self.v_cache = (
                    self._prefill_batch_fns[key](
                        self.params, self.k_cache, self.v_cache,
                        packed_dev, **lora_kw,
                    )
                )
            return sampled, logits
        if self.prefill_pipeline:
            with self.phases.span("pack"):
                s_pad, t_pad, c_pad, packed = (
                    self._fill_packed_prefill_pack(
                        chunks, start_positions, block_tables,
                        total_lens, sampling=sampling,
                    )
                )
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)
            fn, build = self._prefill_batch_fn(s_pad, t_pad, c_pad)
            lora_kw = self._packed_lora_kwargs(
                lora_slots, n, s_pad, t_pad
            )
            self._note_attn_context(prefill_lens=total_lens)
            self.note_sampler(1, sampling and sampling[0])
            with self.phases.span("dispatch"), build:
                sampled, logits, self.k_cache, self.v_cache = fn(
                    self.params, self.k_cache, self.v_cache,
                    packed_dev, **lora_kw,
                )
            return sampled, logits
        with self.phases.span("pack"):
            (s_pad, t_pad, c_pad, tokens, positions_dev, write_slots,
             q_starts, tl_full, tables) = self._packed_host_prep(
                chunks, start_positions, block_tables, total_lens
            )
            last_rows = np.zeros((s_pad,), dtype=np.int32)
            for s, ids in enumerate(chunks):
                last_rows[s] = s * t_pad + (len(ids) - 1)
            for s in range(n, s_pad):
                last_rows[s] = s * t_pad
            lora_kw = self._packed_lora_kwargs(lora_slots, n, s_pad, t_pad)
            temps, top_ps, top_ks, min_ps, keys = self._sampling_args(
                s_pad, sampling
            )
        with self.phases.span("h2d"):
            args = (
                jnp.asarray(tokens.reshape(-1)),
                jnp.asarray(positions_dev.reshape(-1)),
                jnp.asarray(write_slots.reshape(-1)),
                jnp.asarray(tables),
                jnp.asarray(q_starts),
                jnp.asarray(tl_full),
                jnp.asarray(last_rows),
                jnp.asarray(temps),
                jnp.asarray(top_ps),
                jnp.asarray(top_ks),
                jnp.asarray(min_ps),
                jnp.asarray(keys),
            )
        fn, build = self._prefill_batch_fn(s_pad, t_pad, c_pad)
        self._note_attn_context(prefill_lens=total_lens)
        self.note_sampler(1, sampling and sampling[0])
        with self.phases.span("dispatch"), build:
            sampled, logits, self.k_cache, self.v_cache = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                *args,
                **lora_kw,
            )
        return sampled, logits

    def _prefill_batch_fn(self, s_pad: int, t_pad: int, c_pad: int):
        """(program, build annotation) of a packed-group prefill."""
        key = (s_pad, t_pad, c_pad)
        build = phases.NO_SPAN
        if key not in self._prefill_batch_fns:
            logger.info(
                "compiling packed prefill step s=%d t=%d ctx=%d",
                s_pad, t_pad, c_pad,
            )
            build = self._note_compile("prefill_batch", key)
            self._prefill_batch_fns[key] = self._build_prefill_batch(
                s_pad, t_pad, c_pad
            )
        return self._prefill_batch_fns[key], build

    def precompile_prefill(
        self,
        singles: list[tuple[int, int]] = (),
        groups: list[tuple[int, int, int]] = (),
    ) -> int:
        """Compile prefill programs ahead of serving by executing trash
        chunks whose block tables point at the TOP of the block pool.

        `singles`: (chunk_len, total_len) pairs for the single-sequence
        path; `groups`: (group_size, chunk_len, total_len) for the packed
        path. Returns the number of dispatches executed. A compile that
        lands inside a live request costs seconds and lands straight
        in that request's TTFT/ITL, so servers call this at
        startup
        for every bucket the configured workload shape can reach —
        including the resume-tail chunk (a fully prefix-cached prompt
        re-prefills only its final token, chunk_len=1).

        The allocator hands out low block ids first; this sweep claims
        the top ids and requires, per entry, the pool to be at least
        twice the claimed range plus slack — entries too big for the pool
        are skipped individually (with a warning) rather than risk
        overwriting live cached K/V.
        """
        bs = self.block_size
        nb = self.num_blocks
        n = 0
        for chunk_len, total in singles:
            bp = (total + bs - 1) // bs
            if nb < 2 * bp + 64:
                logger.warning(
                    "prefill precompile: skipping single (%d, %d) — pool "
                    "of %d blocks too small", chunk_len, total, nb,
                )
                continue
            self.prefill(
                [1] * chunk_len,
                total - chunk_len,
                list(range(nb - bp, nb)),
                total,
            )
            n += 1
        for s, chunk_len, total in groups:
            bp = (total + bs - 1) // bs
            if nb < 2 * s * bp + 64:
                logger.warning(
                    "prefill precompile: skipping group (%d, %d, %d) — "
                    "pool of %d blocks too small", s, chunk_len, total, nb,
                )
                continue
            tabs = [
                list(range(nb - (i + 1) * bp, nb - i * bp))
                for i in range(s)
            ]
            self.prefill_batch(
                [[1] * chunk_len] * s,
                start_positions=[total - chunk_len] * s,
                block_tables=tabs,
                total_lens=[total] * s,
            )
            n += 1
        return n

    def precompile_decode(
        self, context_lens: list[int], steps: int,
        chained: bool = False,
        stop: bool = False,
    ) -> int:
        """Compile the fused-K decode program for every ctx bucket the
        given context lengths reach, against trash blocks at the top of
        the pool (same safety contract as precompile_prefill). Decode
        lanes are statically padded to max_num_seqs, so the ctx bucket is
        the only shape dimension a serving run crosses mid-stream —
        e.g. multi-round chat sessions grow past a pow2 block-count
        boundary and would otherwise pay an XLA compile inside a live
        ITL measurement. Greedy sampling arrays select the same program
        as any temperature (sampling params are runtime operands).

        `chained=True` additionally compiles the variant a staged
        round dispatches (device-array token input — a DISTINCT program
        cache key): it crosses the same ctx buckets, so an engine that
        stages its next round needs both programs warm.

        `stop=True` compiles the device-stop (elastic) program variant
        instead of the fixed-trip scan, at stop-id cap 0 — the cap only
        grows when a request ships stop_token_ids, which is
        request-dependent and out of precompile scope (same caveat as
        the penalties/logprobs variants)."""
        b = self.config.max_num_seqs
        bs = self.block_size
        nb = self.num_blocks
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        top_ks = np.full((b,), -1, np.int32)
        keys = np.zeros((b, 2), np.uint32)
        seen: set[int] = set()
        n = 0
        for cl in context_lens:
            c_pad = self._ctx_bucket(cl + max(0, steps - 1))
            if c_pad in seen:
                continue
            seen.add(c_pad)
            npages = c_pad // bs
            # same 2x-plus-slack rule as precompile_prefill: the low
            # half of the pool may already hold live/cached K/V (warmup
            # runs before precompile in server startup), and the
            # trash table must never reach down into it
            if nb < 2 * npages + 64:
                logger.warning(
                    "decode precompile: skipping ctx %d — pool of %d "
                    "blocks too small", cl, nb,
                )
                continue
            # every lane shares one trash table: decode writes land in
            # the same top-of-pool slots, never on live cached K/V
            table = list(range(nb - npages, nb))
            ctx = c_pad - max(0, steps - 1)
            if steps > 1:
                stop_kw = {}
                if stop:
                    # budget == steps: nothing freezes, the while_loop
                    # runs its full trip — the PROGRAM is identical to
                    # what a live batch with real budgets selects
                    stop_kw = {"stop": (
                        np.full((b,), -1, np.int32),
                        np.zeros((b,), np.int32),
                        np.full((b,), steps, np.int32),
                        None,
                    )}
                out = self.decode_multi(
                    [1] * b, [ctx - 1] * b, [table] * b, [ctx] * b,
                    steps, temps, top_ps, top_ks, keys, **stop_kw,
                )
                jax.block_until_ready(out)
                toks = out[0] if isinstance(out, tuple) else out
                n += 1
                if chained:
                    out = self.decode_multi(
                        toks[-1], [ctx - 1] * b, [table] * b, [ctx] * b,
                        steps, temps, top_ps, top_ks, keys, **stop_kw,
                    )
                    jax.block_until_ready(out)
                    n += 1
            else:
                out = self.decode(
                    [1] * b, [ctx - 1] * b, [table] * b, [ctx] * b
                )
                jax.block_until_ready(out)
                n += 1
        return n

    def precompile_verify(
        self, context_lens: list[int], draft_len: int, max_lanes: int
    ) -> int:
        """Compile the packed spec-decode verify programs (program key
        (s_pad, t_pad, c_pad), see verify_batch): every pow2 lane count
        up to max_lanes x the draft-chunk bucket x each ctx bucket,
        against trash blocks at the top of the pool (same safety rule
        as the other precompiles)."""
        bs = self.block_size
        nb = self.num_blocks
        lanes: list[int] = []
        s = 1
        while s <= max_lanes:
            lanes.append(s)
            s *= 2
        seen: set[tuple] = set()
        n = 0
        for cl in context_lens:
            c_pad = self._ctx_bucket(cl)
            npages = c_pad // bs
            for s in lanes:
                key = (s, self._prefill_bucket(draft_len), c_pad)
                if key in seen:
                    continue
                seen.add(key)
                if nb < 2 * s * npages + 64:
                    logger.warning(
                        "verify precompile: skipping s=%d ctx=%d — pool "
                        "of %d blocks too small", s, c_pad, nb,
                    )
                    continue
                tabs = [
                    list(range(nb - (i + 1) * npages, nb - i * npages))
                    for i in range(s)
                ]
                row_sampling = (
                    np.zeros((s,), np.float32),
                    np.ones((s,), np.float32),
                    np.full((s,), -1, np.int32),
                    np.zeros((s,), np.float32),
                    np.zeros((s,), np.uint32),
                    np.zeros((s,), np.int64),
                )
                out = self.verify_batch(
                    [[1] * draft_len] * s,
                    [c_pad - draft_len] * s,
                    tabs,
                    [c_pad] * s,
                    row_sampling,
                )
                jax.block_until_ready(out)
                n += 1
        return n

    # stackcheck: hot-path — dispatch-only: returns device logits without
    # waiting; the caller's sampler owns the one fetch per round
    def decode(
        self,
        token_ids: list[int],
        positions: list[int],
        block_tables: list[list[int]],
        context_lens: list[int],
        lora_slots: list[int] | None = None,
    ) -> jax.Array:
        """One decode step for a batch; returns fp32 logits (b, vocab) where
        rows beyond len(token_ids) are padded lanes. Sequence i is lane
        i here: this step ships no shared run (`_decode_pack_layout`
        has the field, this program's arguments do not), so where a
        sequence sits changes nothing, and the host samples from
        `logits[:len(token_ids)]`."""
        b_actual = len(token_ids)
        b = self.config.max_num_seqs
        c_pad = self._ctx_bucket(max(context_lens))

        with self.phases.span("pack"):
            tokens = np.zeros((b,), dtype=np.int32)
            tokens[:b_actual] = token_ids
            pos = np.zeros((b,), dtype=np.int32)
            pos[:b_actual] = positions
            ctx = np.zeros((b,), dtype=np.int32)  # 0: no row this step
            ctx[:b_actual] = context_lens

            write_slots = np.zeros((b,), dtype=np.int32)
            for i in range(b_actual):
                write_slots[i] = self._slots_for_positions(
                    block_tables[i], np.asarray([positions[i]])
                )[0]
            if self.attention_impl == "pallas":
                # pallas path takes padded block tables (pages), not per-token
                # gather slots
                n_pages = c_pad // self.block_size
                tables = np.stack(
                    [
                        self._padded_block_table(
                            block_tables[i] if i < b_actual else [], n_pages
                        )
                        for i in range(b)
                    ]
                )
            else:
                tables = np.zeros((b, c_pad), dtype=np.int32)
                for i in range(b_actual):
                    tables[i] = self._gather_slots_for_table(
                        block_tables[i], c_pad
                    )

        key = (b, c_pad)
        build = phases.NO_SPAN
        if key not in self._decode_fns:
            logger.info("compiling decode step b=%d ctx=%d", b, c_pad)
            build = self._note_compile("decode", key)
            self._decode_fns[key] = self._build_decode(b, c_pad)
        fn = self._decode_fns[key]
        lora_kw = {}
        if self.lora_manager is not None:
            slots = np.zeros((b,), dtype=np.int32)
            if lora_slots is not None:
                slots[:b_actual] = lora_slots
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.asarray(slots),
            }
        with self.phases.span("h2d"):
            args = (
                jnp.asarray(tokens),
                jnp.asarray(pos),
                jnp.asarray(write_slots),
                jnp.asarray(tables),
                jnp.asarray(ctx),
            )
        self._note_attn_context(context_lens, 1)
        with self.phases.span("dispatch"), build:
            logits, self.k_cache, self.v_cache = fn(
                self.params, self.k_cache, self.v_cache, *args, **lora_kw,
            )
        return logits

    def _fill_decode_pack(
        self,
        c_pad: int,
        chained: bool,
        token_ids,
        positions,
        block_tables,
        context_lens,
        temps, top_ps, top_ks, keys,
        min_ps=None,
        guided_lanes: tuple | None = None,
        stop: tuple | None = None,
        lanes: np.ndarray | None = None,
    ) -> np.ndarray:
        """Build the ONE packed int32 host buffer a fused decode
        dispatch ships (layout: _decode_pack_layout). Shared by the
        dispatch path (decode_multi) and the speculative prefetch path
        (stage_decode_multi). `stop` = (eos, min_rem, budget,
        stop_ids|None) per-lane device-stop arrays (see decode_multi);
        padded lanes ship eos -1 and budget 0 (frozen from iteration
        0, so all-real-lanes-done rounds early-exit). Every argument
        holds one entry a SEQUENCE; sequence i's go to lane `lanes[i]`
        (`decode_lanes`; None: lane i), and the lanes that nobody takes
        are the padded ones, wherever they lie."""
        b = self.config.max_num_seqs
        b_actual = len(positions)
        lanes = _seats(lanes, b_actual)
        stop_cap = None
        if stop is not None:
            stop_cap = 0 if stop[3] is None else int(stop[3].shape[1])
        layout, total = self._decode_pack_layout(
            b, c_pad, chained, guided=guided_lanes is not None,
            stop_cap=stop_cap,
        )
        packed = np.zeros((total,), np.int32)

        def put(name, arr):
            off, shape = layout[name]
            n = int(np.prod(shape))
            packed[off:off + n] = arr.reshape(-1).view(np.int32)

        if not chained:
            tokens = np.zeros((b,), dtype=np.int32)
            tokens[lanes] = token_ids
            put("tokens", tokens)
        pos = np.zeros((b,), dtype=np.int32)
        pos[lanes] = positions
        put("positions", pos)
        # a lane that holds no sequence ships context 0: the step
        # programs make it a zero-row segment of the attention walk
        ctx = np.zeros((b,), dtype=np.int32)
        ctx[lanes] = context_lens
        put("ctx", ctx)

        tables = self._page_table_rows(
            block_tables, b, c_pad // self.block_size, lanes)
        put("page_tables", tables)
        self._packed_runs = None
        if "shared_run" in layout:
            self._packed_runs = self._shared_runs(tables, ctx)
            put("shared_run", self._packed_runs)
        if self.attention_impl != "pallas":
            gather_tables = np.zeros((b, c_pad), dtype=np.int32)
            for lane, table in zip(lanes.tolist(), block_tables):
                gather_tables[lane] = self._gather_slots_for_table(
                    table, c_pad
                )
            put("gather_tables", gather_tables)

        t_full = np.zeros((b,), np.float32)
        t_full[lanes] = temps
        put("temps", t_full)
        p_full = np.ones((b,), np.float32)
        p_full[lanes] = top_ps
        put("top_ps", p_full)
        k_full = np.full((b,), -1, np.int32)
        k_full[lanes] = top_ks
        put("top_ks", k_full)
        m_full = np.zeros((b,), np.float32)
        if min_ps is not None:
            m_full[lanes] = min_ps
        put("min_ps", m_full)
        key_full = np.zeros((b, 2), np.uint32)
        key_full[lanes] = keys
        put("keys", key_full)
        if guided_lanes is not None:
            init_states, lane_map = guided_lanes
            g_state = np.zeros((b,), np.int32)
            g_state[lanes] = init_states[:b_actual]
            put("g_state", g_state)
            g_lane = np.zeros((b,), np.int32)
            g_lane[lanes] = lane_map[:b_actual]
            put("g_lane", g_lane)
        if stop is not None:
            eos, min_rem, budget, stop_ids = stop
            eos_full = np.full((b,), -1, np.int32)
            eos_full[lanes] = eos
            put("stop_eos", eos_full)
            min_full = np.zeros((b,), np.int32)
            min_full[lanes] = min_rem
            put("stop_min", min_full)
            bud_full = np.zeros((b,), np.int32)  # padded lanes: done
            bud_full[lanes] = budget
            put("stop_budget", bud_full)
            if stop_cap:
                sid_full = np.full((b, stop_cap), -1, np.int32)
                sid_full[lanes] = stop_ids
                put("stop_ids", sid_full)
        return packed

    # stackcheck: hot-path
    def stage_decode_multi(
        self, positions, block_tables, context_lens, steps,
        temps, top_ps, top_ks, keys, min_ps=None, stop=None,
        lanes=None,
    ):
        """Speculative h2d prefetch for the NEXT chained fused round:
        build the packed buffer and START its async host->device
        transfer now, so the upload overlaps the in-flight round's
        execution and token fetch instead of sitting serially between
        them (cost of the serial upload on an attached chip: not
        measured). The engine stages with PREDICTED
        state (positions/ctx/keys — and, under device stops, the
        min_rem/budget countdowns — advanced by K on the same lanes)
        and validates the prediction before dispatching on it; a stale
        stage (ctx-bucket mismatch) is ignored by decode_multi.
        Returns (c_pad, device_array, the pack's shared runs) for
        decode_multi(staged=...)."""
        with self.phases.span("pack"):
            c_pad = self._ctx_bucket(
                max(context_lens) + max(0, steps - 1)
            )
            packed = self._fill_decode_pack(
                c_pad, True, None, positions, block_tables, context_lens,
                temps, top_ps, top_ks, keys, min_ps=min_ps, stop=stop,
                lanes=lanes,
            )
        with self.phases.span("h2d"):
            handle = (c_pad, jax.device_put(packed), self._packed_runs)
        return handle

    def _decode_pen_kwargs(
        self, penalties: tuple | None, b: int, c_pad: int,
        lanes: np.ndarray,
    ) -> dict:
        """Device penalty-state args for the fused decode scan, shared
        by decode_multi and ragged_dispatch: sequence i's at lane
        `lanes[i]`."""
        if penalties is None:
            return {}
        gen_lists, presence, frequency, repetition = penalties
        # pad the generated-id history to c_pad (generated tokens are
        # part of the context, so it always fits): gen shape then
        # varies only with the existing ctx bucket — a separate pow2
        # gen bucket would multiply the compile space mid-serving
        gen_full = np.full((b, c_pad), -1, np.int32)
        for lane, g in zip(lanes.tolist(), gen_lists):
            gen_full[lane, : len(g)] = g
        pres_full = np.zeros((b,), np.float32)
        pres_full[lanes] = presence
        freq_full = np.zeros((b,), np.float32)
        freq_full[lanes] = frequency
        rep_full = np.ones((b,), np.float32)
        rep_full[lanes] = repetition
        return {
            "gen_ids": jnp.asarray(gen_full),
            "presence": jnp.asarray(pres_full),
            "frequency": jnp.asarray(freq_full),
            "repetition": jnp.asarray(rep_full),
        }

    def _decode_guided_kwargs(
        self, guided: tuple | None
    ) -> tuple[dict, tuple | None]:
        """Device TokenDFA-table args (+ static shapes) for the fused
        decode scan, shared by decode_multi and ragged_dispatch."""
        if guided is None:
            return {}, None
        # per-lane g_state/g_lane were packed by _fill_decode_pack
        (g_token, init_states, lane_map, token_class, class_mask,
         class_trans) = guided
        # device-cache the big tables across dispatches: they change
        # only when the set of live constraints changes
        cached = getattr(self, "_guided_dev", None)
        if cached is None or cached[0] != g_token:
            self._guided_dev = (
                g_token,
                jnp.asarray(token_class),
                jnp.asarray(class_mask),
                jnp.asarray(class_trans),
            )
        _, tc_dev, mask_dev, trans_dev = self._guided_dev
        guided_kw = {
            "g_token_class": tc_dev,
            "g_class_mask": mask_dev,
            "g_class_trans": trans_dev,
        }
        guided_shapes = (
            token_class.shape[0], class_mask.shape[0],
            class_mask.shape[1],
        )
        return guided_kw, guided_shapes

    def _decode_bias_kwargs(
        self, logit_bias: tuple | None, b: int, lanes: np.ndarray
    ) -> tuple[dict, int]:
        """Dense logit-bias args (+ cap) for the fused decode scan,
        shared by decode_multi and ragged_dispatch: sequence i's at
        lane `lanes[i]`."""
        if logit_bias is None:
            return {}, 0
        lb_ids, lb_vals = logit_bias  # (b_actual, cap) ndarrays
        bias_cap = int(lb_ids.shape[1])
        ids_full = np.zeros((b, bias_cap), np.int32)
        vals_full = np.zeros((b, bias_cap), np.float32)
        ids_full[lanes] = lb_ids
        vals_full[lanes] = lb_vals
        return {
            "lb_ids": jnp.asarray(ids_full),
            "lb_vals": jnp.asarray(vals_full),
        }, bias_cap

    # stackcheck: hot-path — one dispatch, one deferred fetch; a stray
    # sync forcer here costs a full RTT per decode round
    def decode_multi(
        self,
        token_ids: list[int],
        positions: list[int],
        block_tables: list[list[int]],
        context_lens: list[int],
        steps: int,
        temps: np.ndarray,      # (b_actual,) float32
        top_ps: np.ndarray,
        top_ks: np.ndarray,
        keys: np.ndarray,       # (b_actual, 2) uint32
        min_ps: np.ndarray | None = None,  # (b_actual,) f32; None => off
        lora_slots: list[int] | None = None,
        penalties: tuple | None = None,
        want_logprobs: bool = False,
        guided: tuple | None = None,
        logit_bias: tuple | None = None,  # ((b_actual, cap) i32 ids,
                                          #  (b_actual, cap) f32 vals)
        staged: tuple | None = None,  # pre-uploaded (c_pad, packed_dev)
                                      # from stage_decode_multi
        stop: tuple | None = None,  # device-side stop masks: (eos
                                    # (b_actual,) i32 — -1 = ignore,
                                    # min_rem (b_actual,) i32,
                                    # budget (b_actual,) i32,
                                    # stop_ids (b_actual, cap) i32
                                    # padded -1, or None)
        lanes: np.ndarray | None = None,  # (b_actual,) the lane each
                                          # sequence takes (decode_lanes);
                                          # None: lane i for sequence i
    ):
        """`steps` fused decode+sample iterations (one dispatch, one
        fetch); returns (steps, b) int32 sampled tokens on device — or,
        with `want_logprobs`, a tuple (tokens, chosen_lp (k, b) f32,
        top_vals (k, b, CAP) f32, top_ids (k, b, CAP) i32). With
        `stop` (device-side stop masks, see _build_decode_multi) the
        return is ALWAYS a tuple whose last element is the (b,) int32
        per-lane valid count — (tokens, valid) or (tokens, chosen_lp,
        top_vals, top_ids, valid); tokens at rows >= valid[lane] are
        pinned pad, the round early-exits once every lane is done, and
        the caller applies exactly valid[lane] tokens per lane. The
        caller must have grown each block table to cover
        context_len + steps - 1 positions (scheduler lookahead).

        `penalties`: optional (gen_ids_list, presence, frequency,
        repetition) — generated-token history per lane (list of int
        lists) + (b_actual,) penalty arrays; token counts are then
        maintained on device through the scan (sampler.apply_penalties
        semantics, bit-identical to the host single-step path).

        `token_ids` may be a full-lane (b,) DEVICE array instead of a
        host list: the staged round of the h2d prefetch chains round
        N+1 directly on round N's on-device sampled tokens.

        `guided`: optional (cache_token, init_states (b,), lane_map (b,),
        token_class (M, V), class_mask (S, C), class_trans (S, C)) —
        TokenDFA tables (engine/structured.py) evaluated INSIDE the
        fused scan so constrained lanes keep the K-step fetch
        amortization. The three big tables are uploaded once per
        `cache_token` and reused across dispatches.

        Every per-sequence argument above is in the caller's order;
        `lanes` says where each sits among the b lanes, and everything
        returned per lane ((k, b) tokens, valid, the logprob arrays) is
        read at `[..., lanes]`. A staged buffer was laid out by
        `stage_decode_multi(lanes=)`: the caller passes the same map
        with it, or does not pass the stage."""
        if steps > self.block_size:
            raise ValueError(
                f"num_scheduler_steps={steps} > block_size="
                f"{self.block_size}: idle lanes would overrun the trash "
                "block"
            )
        b = self.config.max_num_seqs
        chained = isinstance(token_ids, jax.Array)
        lanes = _seats(lanes, len(positions))
        c_pad = self._ctx_bucket(max(context_lens) + steps - 1)

        # ONE packed i32 host->device buffer per dispatch (layout shared
        # with the jitted unpack, _decode_pack_layout).
        # A valid speculative stage (stage_decode_multi) skips the build
        # AND the serial upload entirely — its transfer overlapped the
        # previous round.
        guided_lanes = None
        if guided is not None:
            guided_lanes = (guided[1], guided[2])
        stop_cap = None
        if stop is not None:
            stop_cap = 0 if stop[3] is None else int(stop[3].shape[1])
        packed_dev = None
        if (staged is not None and chained and guided is None
                and staged[0] == c_pad):
            # the staged buffer must carry the SAME field layout this
            # dispatch expects — the stop fields vary with the per-batch
            # stop-id cap, so a total-length mismatch is a stale stage
            # (rebuild + upload serially), never a dispatch error
            _, want_total = self._decode_pack_layout(
                b, c_pad, chained, guided=False, stop_cap=stop_cap,
            )
            if int(staged[1].shape[0]) == want_total:
                packed_dev, runs = staged[1:]
        if packed_dev is None:
            with self.phases.span("pack"):
                packed = self._fill_decode_pack(
                    c_pad, chained, token_ids, positions, block_tables,
                    context_lens, temps, top_ps, top_ks, keys,
                    min_ps=min_ps, guided_lanes=guided_lanes, stop=stop,
                    lanes=lanes,
                )
                runs = self._packed_runs
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)

        pen_kw = self._decode_pen_kwargs(penalties, b, c_pad, lanes)
        guided_kw, guided_shapes = self._decode_guided_kwargs(guided)
        bias_kw, bias_cap = self._decode_bias_kwargs(
            logit_bias, b, lanes
        )
        cache_key = (b, c_pad, steps, penalties is not None,
                     want_logprobs, chained, guided_shapes, bias_cap,
                     stop_cap)
        build = phases.NO_SPAN
        if cache_key not in self._decode_multi_fns:
            logger.info(
                "compiling multi-step decode b=%d ctx=%d k=%d pen=%s "
                "lp=%s chained=%s guided=%s bias=%d stop=%s",
                b, c_pad, steps, penalties is not None, want_logprobs,
                chained, guided_shapes, bias_cap, stop_cap,
            )
            build = self._note_compile("decode_multi", cache_key)
            self._decode_multi_fns[cache_key] = self._build_decode_multi(
                b, c_pad, steps, use_penalties=penalties is not None,
                want_logprobs=want_logprobs, chained=chained,
                guided_shapes=guided_shapes, bias_cap=bias_cap,
                stop_cap=stop_cap,
            )
        fn = self._decode_multi_fns[cache_key]
        lora_kw = {}
        if self.lora_manager is not None:
            slots = np.zeros((b,), dtype=np.int32)
            if lora_slots is not None:
                slots[lanes] = lora_slots
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.asarray(slots),
            }
        chained_kw = {"chained_tokens": token_ids} if chained else {}
        self._note_attn_context(context_lens, steps, runs=runs,
                                lanes=lanes)
        self.note_sampler(steps, temps)
        with self.phases.span("dispatch"), build:
            ys, self.k_cache, self.v_cache = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                packed_dev,
                **chained_kw,
                **guided_kw,
                **pen_kw,
                **bias_kw,
                **lora_kw,
            )
        return ys

    # -- unified ragged prefill+decode dispatch ----------------------------
    # ONE lane-typed engine round: a single packed h2d buffer whose lanes
    # mix prefill chunks and decode steps (Ragged Paged Attention role,
    # PAPERS.md), one jitted program that runs the prefill lanes' chunk
    # attention and the decode lanes' stop-aware scan back to back. The
    # two lane sets belong to DIFFERENT sequences with disjoint block
    # tables, so the in-program ordering cannot change any sampled value:
    # tokens are bit-identical to a split prefill round followed by a
    # decode round (tests/test_ragged_dispatch.py pins it).

    def _ragged_pack_sizes(
        self, s_pad: int, t_pad: int, pc_pad: int, b: int, c_pad: int,
        chained: bool, guided: bool = False, stop_cap: int | None = None,
    ) -> tuple[int, int, int]:
        """(meta, prefill, decode) segment lengths of the ONE packed i32
        buffer a ragged dispatch ships: a lane-meta header (per-lane
        type/length/budget — the fields extending _decode_pack_layout
        to a lane-typed round), then the packed prefill pack, then the
        decode pack, concatenated. The decode segment varies with the
        stop-id cap and guided fields exactly like _decode_pack_layout,
        so a staged buffer whose total length mismatches the dispatch's
        expectation is a STALE STAGE (counted miss), never an error."""
        meta = 3 * (s_pad + b)
        _, pf = self._packed_prefill_pack_layout(s_pad, t_pad, pc_pad)
        _, dec = self._decode_pack_layout(
            b, c_pad, chained, guided=guided, stop_cap=stop_cap
        )
        return meta, pf, dec

    # stackcheck: hot-path — host build of the ragged round's single
    # h2d buffer, shared by the dispatch and the staging prefetch; one
    # pass over the lanes, no device fetch
    def _fill_ragged_pack(
        self,
        pf_chunks: list[list[int]],
        pf_start_positions: list[int],
        pf_block_tables: list[list[int]],
        pf_total_lens: list[int],
        pf_sampling,
        c_pad: int,
        chained: bool,
        token_ids,
        positions,
        block_tables,
        context_lens,
        steps: int,
        temps, top_ps, top_ks, keys,
        min_ps=None,
        guided_lanes: tuple | None = None,
        stop: tuple | None = None,
        pf_budgets: list[int] | None = None,
        dec_budgets: list[int] | None = None,
        lanes: np.ndarray | None = None,
    ) -> tuple[int, int, int, np.ndarray]:
        """Concatenate lane-meta + prefill pack + decode pack; returns
        (s_pad, t_pad, pc_pad, packed). Lane order: prefill lanes 0..n_pf
        (padded to s_pad), then the b decode lanes, decode sequence i at
        `lanes[i]` of them (`_fill_decode_pack`). `lane_budgets` carry
        remaining prompt tokens (prefill lanes) / remaining token budget
        (decode lanes) — self-describing for debugging, and lane_types
        gates the device-side idle-lane token pinning."""
        b = self.config.max_num_seqs
        s_pad, t_pad, pc_pad, pf_packed = self._fill_packed_prefill_pack(
            pf_chunks, pf_start_positions, pf_block_tables,
            pf_total_lens, sampling=pf_sampling,
        )
        dec_packed = self._fill_decode_pack(
            c_pad, chained, token_ids, positions, block_tables,
            context_lens, temps, top_ps, top_ks, keys, min_ps=min_ps,
            guided_lanes=guided_lanes, stop=stop, lanes=lanes,
        )
        meta = self._ragged_lane_meta(
            s_pad, pf_chunks, len(positions), steps, stop, pf_budgets,
            dec_budgets, lanes)
        packed = np.concatenate([meta, pf_packed, dec_packed])
        return s_pad, t_pad, pc_pad, packed

    def _ragged_lane_meta(
        self, s_cap: int, pf_chunks, n_dec: int, steps: int, stop,
        pf_budgets, dec_budgets, lanes: np.ndarray | None,
    ) -> np.ndarray:
        """The lane-meta header of a lane-typed round's buffer (types,
        lengths, budgets): `s_cap` prefill lanes, then the b decode
        lanes, decode sequence i at `lanes[i]` of them."""
        n_pf = len(pf_chunks)
        dec = s_cap + _seats(lanes, n_dec)
        n_lanes = s_cap + self.config.max_num_seqs
        types = np.zeros((n_lanes,), np.int32)
        types[:n_pf] = RAGGED_LANE_PREFILL
        types[dec] = RAGGED_LANE_DECODE
        lens = np.zeros((n_lanes,), np.int32)
        lens[:n_pf] = [len(c) for c in pf_chunks]
        lens[dec] = steps
        budgets = np.zeros((n_lanes,), np.int32)
        if pf_budgets is not None:
            budgets[:n_pf] = pf_budgets
        if dec_budgets is not None:
            budgets[dec] = dec_budgets
        elif stop is not None:
            budgets[dec] = stop[2]
        return np.concatenate([types, lens, budgets])

    def _build_ragged(self, s_pad: int, t_pad: int, pc_pad: int,
                      b: int, c_pad: int, k_steps: int,
                      use_penalties: bool = False,
                      want_logprobs: bool = False,
                      chained: bool = False,
                      guided_shapes: tuple | None = None,
                      bias_cap: int = 0,
                      stop_cap: int | None = None):
        """ONE jitted lane-typed round: unpack the fused buffer's three
        segments, run the packed prefill step over the prefill lanes,
        then the fused decode scan over the decode lanes — one h2d
        transfer, one dispatch enqueue, and the decode half's device
        stop masks / penalties / guided tables unchanged from
        _make_decode_multi_step. Idle prefill lanes' sampled slots are
        pinned to sampler.RAGGED_IDLE_TOKEN from the lane-meta header so
        the host can assert it only consumes real lanes."""
        from production_stack_tpu.engine.sampler import RAGGED_IDLE_TOKEN

        pf_step = self._make_prefill_batch_packed(s_pad, t_pad, pc_pad)
        dec_step = self._make_decode_multi_step(
            b, c_pad, k_steps, use_penalties=use_penalties,
            want_logprobs=want_logprobs, chained=chained,
            guided_shapes=guided_shapes, bias_cap=bias_cap,
            stop_cap=stop_cap,
        )
        meta_n, pf_n, _dec_n = self._ragged_pack_sizes(
            s_pad, t_pad, pc_pad, b, c_pad, chained,
            guided=guided_shapes is not None, stop_cap=stop_cap,
        )

        def step(params, kc, vc, packed, chained_tokens=None,
                 g_token_class=None, g_class_mask=None,
                 g_class_trans=None, gen_ids=None, presence=None,
                 frequency=None, repetition=None, lb_ids=None,
                 lb_vals=None, lora=None, lora_slots=None,
                 pf_lora_slots=None):
            lane_types = packed[:s_pad + b]
            pf_packed = packed[meta_n:meta_n + pf_n]
            dec_packed = packed[meta_n + pf_n:]
            # prefill lanes first: their chunk K/V lands before the
            # decode scan runs, matching the split path's round order
            # (values are order-independent anyway — disjoint tables)
            pf_sampled, pf_logits, kc, vc = pf_step(
                params, kc, vc, pf_packed, lora=lora,
                lora_slots=pf_lora_slots,
            )
            ys, kc, vc = dec_step(
                params, kc, vc, dec_packed,
                chained_tokens=chained_tokens,
                g_token_class=g_token_class, g_class_mask=g_class_mask,
                g_class_trans=g_class_trans, gen_ids=gen_ids,
                presence=presence, frequency=frequency,
                repetition=repetition, lb_ids=lb_ids, lb_vals=lb_vals,
                lora=lora, lora_slots=lora_slots,
            )
            pf_sampled = jnp.where(
                lane_types[:s_pad] == RAGGED_LANE_PREFILL,
                pf_sampled, RAGGED_IDLE_TOKEN,
            )
            return pf_sampled, pf_logits, ys, kc, vc

        return jit_program("ragged", step, donate_argnums=(1, 2))

    # -- single-kernel ragged-rows round -----------------------------------
    def _ragged_rows_pack_sizes(
        self, r_pad: int, pc_pad: int, b: int, c_pad: int,
        chained: bool, guided: bool = False,
        stop_cap: int | None = None,
    ) -> tuple[int, int, int]:
        """(meta, prefill, decode) segment lengths of the ragged-ROWS
        round's packed buffer (kernel-mode mirror of
        _ragged_pack_sizes: the prefill segment is the ragged-rows
        pack, lane meta spans the static lane cap)."""
        meta = 3 * (self._rows_lane_cap() + b)
        _, pf = self._rows_prefill_pack_layout(r_pad, pc_pad)
        _, dec = self._decode_pack_layout(
            b, c_pad, chained, guided=guided, stop_cap=stop_cap
        )
        return meta, pf, dec

    # stackcheck: hot-path — host build of the kernel-mode round's
    # single h2d buffer (dispatch + staging prefetch); one pass over
    # the lanes, no device fetch
    def _fill_ragged_rows_pack(
        self,
        pf_chunks, pf_start_positions, pf_block_tables, pf_total_lens,
        pf_sampling, c_pad, chained, token_ids, positions,
        block_tables, context_lens, steps, temps, top_ps, top_ks,
        keys, min_ps=None, guided_lanes=None, stop=None,
        pf_budgets=None, dec_budgets=None, lanes=None,
    ) -> tuple[int, int, np.ndarray]:
        """Kernel-mode mirror of _fill_ragged_pack: lane-meta header
        (lane cap + b lanes) + the ragged-ROWS prefill pack + the
        decode pack. Returns (r_pad, pc_pad, packed)."""
        b = self.config.max_num_seqs
        s_cap = self._rows_lane_cap()
        r_pad, pc_pad, pf_packed = self._fill_rows_prefill_pack(
            pf_chunks, pf_start_positions, pf_block_tables,
            pf_total_lens, sampling=pf_sampling,
        )
        dec_packed = self._fill_decode_pack(
            c_pad, chained, token_ids, positions, block_tables,
            context_lens, temps, top_ps, top_ks, keys, min_ps=min_ps,
            guided_lanes=guided_lanes, stop=stop, lanes=lanes,
        )
        meta = self._ragged_lane_meta(
            s_cap, pf_chunks, len(positions), steps, stop, pf_budgets,
            dec_budgets, lanes)
        packed = np.concatenate([meta, pf_packed, dec_packed])
        return r_pad, pc_pad, packed

    def _build_ragged_rows(self, r_pad: int, pc_pad: int, b: int,
                           c_pad: int, k_steps: int,
                           use_penalties: bool = False,
                           want_logprobs: bool = False,
                           chained: bool = False,
                           guided_shapes: tuple | None = None,
                           bias_cap: int = 0,
                           stop_cap: int | None = None):
        """ONE jitted lane-typed round in single-kernel mode: the
        prefill lanes' chunk rows AND the decode lanes' step-0 query
        rows share one flattened row space — one forward pass whose
        per-layer attention is ONE ragged_paged_attention launch over
        the whole lane mix — then decode iterations 1..K-1 continue
        through the shared decode core (the same kernel in all-decode
        configuration). Prefill and decode lanes belong to different
        sequences with disjoint block tables, and the decode half's
        post-sample math is _decode_round_core's verbatim, so tokens
        and logical KV are bit-identical to the split path."""
        from production_stack_tpu.engine.sampler import (
            RAGGED_IDLE_TOKEN,
            sample_tokens,
        )

        mc = self.model_config
        tq = RAGGED_TQ
        bs = self.block_size
        s_cap = self._rows_lane_cap()
        b_pad = _ceil_tq(b)
        pf_step = self._make_prefill_rows_step(r_pad, pc_pad)
        pf_unpack = pf_step._unpack
        core = self._decode_round_core(
            b, c_pad, k_steps, use_penalties=use_penalties,
            want_logprobs=want_logprobs, chained=chained,
            guided_shapes=guided_shapes, bias_cap=bias_cap,
            stop_cap=stop_cap,
        )
        meta_n, pf_n, _dec_n = self._ragged_rows_pack_sizes(
            r_pad, pc_pad, b, c_pad, chained,
            guided=guided_shapes is not None, stop_cap=stop_cap,
        )
        n_pages = max(pc_pad, c_pad) // bs
        n_pf_blk = r_pad // tq
        n_dec_blk = b_pad // tq

        def step(params, kc, vc, packed, chained_tokens=None,
                 g_token_class=None, g_class_mask=None,
                 g_class_trans=None, gen_ids=None, presence=None,
                 frequency=None, repetition=None, lb_ids=None,
                 lb_vals=None, lora=None, lora_slots=None,
                 pf_lora_slots=None):
            kc, vc = self._enter_caches(kc, vc)
            lane_types = packed[:s_cap + b]
            pf_packed = packed[meta_n:meta_n + pf_n]
            dec_packed = packed[meta_n + pf_n:]
            pf = pf_unpack(pf_packed)
            consts, carry0 = core["unpack"](
                dec_packed, kc, chained_tokens=chained_tokens,
                g_token_class=g_token_class, g_class_mask=g_class_mask,
                g_class_trans=g_class_trans, gen_ids=gen_ids,
                presence=presence, frequency=frequency,
                repetition=repetition, lb_ids=lb_ids, lb_vals=lb_vals,
            )
            # fused step-0 forward over [prefill rows | decode rows]:
            # decode write slots / ctx come from the shared core so
            # frozen-lane trash redirection matches the loop's
            d_tokens, d_positions, d_ws, d_ctx = core["fwd_args"](
                carry0, consts
            )
            tokens_cat = jnp.concatenate([pf["tokens"], d_tokens])
            positions_cat = jnp.concatenate(
                [pf["positions"], d_positions]
            )
            ws_cat = jnp.concatenate([pf["write_slots"], d_ws])
            # lane tables: prefill lanes then decode lanes, padded to
            # the wider page count (pad pages point at the null block
            # and sit beyond every segment's page walk)
            def widen(tab):
                return jnp.pad(tab, ((0, 0), (0, n_pages - tab.shape[1])))

            pf_tab = widen(pf["tables"])
            tables_cat = jnp.concatenate(
                [pf_tab, widen(consts["page_tables"])], axis=0)
            # as the windowed kind walks them: the decode lanes' rows
            # are the round's, mapped once in the core's unpack (a pad
            # page is the null block, which maps to itself)
            mapped_cat = self._map_tables(kc, pf_tab)
            if mapped_cat is not None:
                mapped_cat = jnp.concatenate(
                    [mapped_cat, widen(consts["mapped_tables"])], axis=0)
            # block map: prefill blocks carry one chunk segment each;
            # decode lanes are single-row segments sharing the tail
            # blocks (q_pos = ctx-1 makes decode the degenerate causal
            # case of the one kernel body), zero-row ones where the
            # lane holds no token this step (context 0)
            pf_seg = self._rows_pf_seg_meta(
                r_pad, pf["lane_row0"], pf["lane_rows"], pf["q_starts"]
            )
            dlanes = jnp.arange(b, dtype=jnp.int32)
            dec_seg = jnp.stack([
                s_cap + dlanes,
                dlanes % tq,
                (d_ctx > 0).astype(jnp.int32),
                d_ctx - 1,
            ], axis=1)
            seg_meta = jnp.concatenate([pf_seg, dec_seg], axis=0)
            blk_seg = jnp.concatenate([
                jnp.arange(n_pf_blk + 1, dtype=jnp.int32),
                n_pf_blk + jnp.minimum(
                    (jnp.arange(n_dec_blk, dtype=jnp.int32) + 1) * tq,
                    b,
                ),
            ])

            # the decode row blocks' shared runs, their lanes counted
            # from the decode lanes' place in the tables; a prefill
            # block has none
            shared = jnp.concatenate([
                jnp.zeros((n_pf_blk, 2), jnp.int32),
                consts["shared_run"] + jnp.asarray([0, s_cap], jnp.int32),
            ])

            def attn_fn(q, l, kcc, vcc, spec=None):
                qp = jnp.pad(q, ((0, b_pad - b), (0, 0), (0, 0)))
                out = self._attn(
                    "ragged", qp, l, kcc, vcc, tables_cat, blk_seg,
                    seg_meta, spec=spec, shared=shared,
                    mapped=mapped_cat,
                )
                return out[:r_pad + b]

            lora_cat = None
            if lora is not None:
                lora_cat = jnp.concatenate([pf_lora_slots, lora_slots])
            logits_all, kc, vc = self._forward(
                mc, params, tokens_cat, positions_cat, kc, vc, ws_cat,
                attn_fn,
                logits_rows=jnp.concatenate(
                    [pf["last_rows"], r_pad + jnp.arange(b)]
                ),
                lora=lora, lora_slots=lora_cat,
                **self._rows_valid_kw(jnp.concatenate(
                    [pf["lane_rows"] > 0, d_ctx > 0])),
                **self._state_kw(*self._rows_lanes(r_pad), b),
            )
            pf_logits = logits_all[:s_cap]
            dec0_logits = logits_all[s_cap:]
            pf_sampled = sample_tokens(
                pf_logits, pf["temps"], pf["top_ps"], pf["top_ks"],
                pf["keys"], min_p=pf["min_ps"],
            )
            pf_sampled = jnp.where(
                lane_types[:s_cap] == RAGGED_LANE_PREFILL,
                pf_sampled, RAGGED_IDLE_TOKEN,
            )
            ys, kc, vc = core["run"](
                params, kc, vc, consts, carry0, lora=lora,
                lora_slots=lora_slots, first_logits=dec0_logits,
            )
            return pf_sampled, pf_logits, ys, kc, vc

        return jit_program("ragged_rows", step, donate_argnums=(1, 2))

    # stackcheck: hot-path — speculative h2d prefetch of the NEXT ragged
    # round's packed buffer: the upload overlaps the in-flight round's
    # execution and fetch (decode mirror: stage_decode_multi).
    # Enqueue-only, no device fetch.
    def stage_ragged(
        self,
        pf_chunks: list[list[int]],
        pf_start_positions: list[int],
        pf_block_tables: list[list[int]],
        pf_total_lens: list[int],
        pf_sampling,
        positions, block_tables, context_lens, steps,
        temps, top_ps, top_ks, keys,
        min_ps=None, stop=None,
        pf_budgets=None, dec_budgets=None, lanes=None,
    ) -> tuple:
        """Build + START uploading the predicted next ragged round's
        packed buffer (decode half chained: its tokens ride on device
        from the current round). Returns a handle for
        ragged_dispatch(staged=...); the caller validates its
        fingerprint — and the dispatch validates the total layout
        length — before use."""
        with self.phases.span("pack"):
            c_pad = self._ctx_bucket(
                max(context_lens) + max(0, steps - 1)
            )
            if self.ragged_kernel:
                r_pad, pc_pad, packed = self._fill_ragged_rows_pack(
                    pf_chunks, pf_start_positions, pf_block_tables,
                    pf_total_lens, pf_sampling, c_pad, True, None,
                    positions, block_tables, context_lens, steps, temps,
                    top_ps, top_ks, keys, min_ps=min_ps, stop=stop,
                    pf_budgets=pf_budgets, dec_budgets=dec_budgets,
                    lanes=lanes,
                )
                key = ("rows", r_pad, pc_pad, c_pad)
            else:
                s_pad, t_pad, pc_pad, packed = self._fill_ragged_pack(
                    pf_chunks, pf_start_positions, pf_block_tables,
                    pf_total_lens, pf_sampling, c_pad, True, None,
                    positions, block_tables, context_lens, steps, temps,
                    top_ps, top_ks, keys, min_ps=min_ps, stop=stop,
                    pf_budgets=pf_budgets, dec_budgets=dec_budgets,
                    lanes=lanes,
                )
                key = ("ragged", s_pad, t_pad, pc_pad, c_pad)
        with self.phases.span("h2d"):
            handle = (key, jax.device_put(packed), self._packed_runs)
        return handle

    # stackcheck: hot-path — ONE dispatch serves the whole lane-typed
    # round (prefill chunks + decode steps); fetches stay deferred to
    # the caller, a stray sync forcer here costs a full RTT per round
    def ragged_dispatch(
        self,
        pf_chunks: list[list[int]],
        pf_start_positions: list[int],
        pf_block_tables: list[list[int]],
        pf_total_lens: list[int],
        token_ids,
        positions: list[int],
        block_tables: list[list[int]],
        context_lens: list[int],
        steps: int,
        temps, top_ps, top_ks, keys,
        min_ps=None,
        pf_sampling=None,
        pf_lora_slots: list[int] | None = None,
        lora_slots: list[int] | None = None,
        penalties: tuple | None = None,
        want_logprobs: bool = False,
        guided: tuple | None = None,
        logit_bias: tuple | None = None,
        staged: tuple | None = None,
        stop: tuple | None = None,
        pf_budgets: list[int] | None = None,
        dec_budgets: list[int] | None = None,
        lanes: np.ndarray | None = None,
    ) -> tuple:
        """One lane-typed engine round: prefill chunk lanes + fused
        decode lanes in a single program. Returns (pf_sampled (s_pad,)
        i32 device — RAGGED_IDLE_TOKEN on non-real lanes, pf_logits
        (s_pad, vocab) device, dec_ys) where dec_ys matches
        decode_multi's return shape for the same flags. `staged` = a
        stage_ragged handle; used only when its bucket key AND total
        layout length match (a lane-mix or stop-cap drift between stage
        and dispatch rebuilds serially — a counted staging miss, never
        a dispatch error). `lanes`: where each decode sequence sits
        among the b decode lanes, as for decode_multi; the prefill
        lanes are in the caller's order."""
        if steps > self.block_size:
            raise ValueError(
                f"num_scheduler_steps={steps} > block_size="
                f"{self.block_size}: idle lanes would overrun the trash "
                "block"
            )
        if self.ragged_kernel:
            return self._ragged_rows_dispatch(
                pf_chunks, pf_start_positions, pf_block_tables,
                pf_total_lens, token_ids, positions, block_tables,
                context_lens, steps, temps, top_ps, top_ks, keys,
                min_ps=min_ps, pf_sampling=pf_sampling,
                pf_lora_slots=pf_lora_slots, lora_slots=lora_slots,
                penalties=penalties, want_logprobs=want_logprobs,
                guided=guided, logit_bias=logit_bias, staged=staged,
                stop=stop, pf_budgets=pf_budgets,
                dec_budgets=dec_budgets, lanes=lanes,
            )
        b = self.config.max_num_seqs
        chained = isinstance(token_ids, jax.Array)
        lanes = _seats(lanes, len(positions))
        c_pad = self._ctx_bucket(max(context_lens) + steps - 1)
        s_pad = next_pow2(max(len(pf_chunks), 1))
        t_pad = self._prefill_bucket(max(len(c) for c in pf_chunks))
        pc_pad = max(self._ctx_bucket(tl) for tl in pf_total_lens)
        guided_lanes = None
        if guided is not None:
            guided_lanes = (guided[1], guided[2])
        stop_cap = None
        if stop is not None:
            stop_cap = 0 if stop[3] is None else int(stop[3].shape[1])
        packed_dev = None
        if (staged is not None and chained and guided is None
                and staged[0] == ("ragged", s_pad, t_pad, pc_pad,
                                  c_pad)):
            want_total = sum(self._ragged_pack_sizes(
                s_pad, t_pad, pc_pad, b, c_pad, chained,
                guided=False, stop_cap=stop_cap,
            ))
            if int(staged[1].shape[0]) == want_total:
                packed_dev, runs = staged[1:]
        if packed_dev is None:
            with self.phases.span("pack"):
                _s, _t, _pc, packed = self._fill_ragged_pack(
                    pf_chunks, pf_start_positions, pf_block_tables,
                    pf_total_lens, pf_sampling, c_pad, chained, token_ids,
                    positions, block_tables, context_lens, steps, temps,
                    top_ps, top_ks, keys, min_ps=min_ps,
                    guided_lanes=guided_lanes, stop=stop,
                    pf_budgets=pf_budgets, dec_budgets=dec_budgets,
                    lanes=lanes,
                )
                runs = self._packed_runs
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)

        pen_kw = self._decode_pen_kwargs(penalties, b, c_pad, lanes)
        guided_kw, guided_shapes = self._decode_guided_kwargs(guided)
        bias_kw, bias_cap = self._decode_bias_kwargs(
            logit_bias, b, lanes
        )
        cache_key = (s_pad, t_pad, pc_pad, b, c_pad, steps,
                     penalties is not None, want_logprobs, chained,
                     guided_shapes, bias_cap, stop_cap)
        build = phases.NO_SPAN
        if cache_key not in self._ragged_fns:
            logger.info(
                "compiling ragged round s=%d t=%d pctx=%d b=%d ctx=%d "
                "k=%d pen=%s lp=%s chained=%s guided=%s bias=%d stop=%s",
                s_pad, t_pad, pc_pad, b, c_pad, steps,
                penalties is not None, want_logprobs, chained,
                guided_shapes, bias_cap, stop_cap,
            )
            build = self._note_compile("ragged", cache_key)
            self._ragged_fns[cache_key] = self._build_ragged(
                s_pad, t_pad, pc_pad, b, c_pad, steps,
                use_penalties=penalties is not None,
                want_logprobs=want_logprobs, chained=chained,
                guided_shapes=guided_shapes, bias_cap=bias_cap,
                stop_cap=stop_cap,
            )
        fn = self._ragged_fns[cache_key]
        lora_kw = {}
        if self.lora_manager is not None:
            slots = np.zeros((b,), dtype=np.int32)
            if lora_slots is not None:
                slots[lanes] = lora_slots
            pf_kw = self._packed_lora_kwargs(
                pf_lora_slots, len(pf_chunks), s_pad, t_pad
            )
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.asarray(slots),
                "pf_lora_slots": pf_kw["lora_slots"],
            }
        chained_kw = {"chained_tokens": token_ids} if chained else {}
        self._note_attn_context(context_lens, steps, pf_total_lens,
                                forwards=steps + 1, runs=runs,
                                lanes=lanes)
        self.note_sampler(steps, temps)
        self.note_sampler(1, pf_sampling and pf_sampling[0])
        with self.phases.span("dispatch"), build:
            pf_sampled, pf_logits, ys, self.k_cache, self.v_cache = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                packed_dev,
                **chained_kw,
                **guided_kw,
                **pen_kw,
                **bias_kw,
                **lora_kw,
            )
        return pf_sampled, pf_logits, ys

    # stackcheck: hot-path — the single-kernel lane-typed round: ONE
    # dispatch serves prefill chunks + decode steps; fetches stay
    # deferred to the caller
    def _ragged_rows_dispatch(
        self,
        pf_chunks, pf_start_positions, pf_block_tables, pf_total_lens,
        token_ids, positions, block_tables, context_lens, steps,
        temps, top_ps, top_ks, keys, min_ps=None, pf_sampling=None,
        pf_lora_slots=None, lora_slots=None, penalties=None,
        want_logprobs=False, guided=None, logit_bias=None,
        staged=None, stop=None, pf_budgets=None, dec_budgets=None,
        lanes=None,
    ) -> tuple:
        """Kernel-mode body of ragged_dispatch (same contract): the
        program keys on the padded ROW bucket + ctx buckets —
        (r_pad, pc_pad, b, c_pad, k) — so every lane mix that packs to
        the same row bucket shares one program, and the per-layer
        attention of the whole mix is one kernel launch."""
        b = self.config.max_num_seqs
        chained = isinstance(token_ids, jax.Array)
        lanes = _seats(lanes, len(positions))
        c_pad = self._ctx_bucket(max(context_lens) + steps - 1)
        r_pad, pc_pad = self._rows_dims(pf_chunks, pf_total_lens)
        guided_lanes = None
        if guided is not None:
            guided_lanes = (guided[1], guided[2])
        stop_cap = None
        if stop is not None:
            stop_cap = 0 if stop[3] is None else int(stop[3].shape[1])
        packed_dev = None
        if (staged is not None and chained and guided is None
                and staged[0] == ("rows", r_pad, pc_pad, c_pad)):
            # same stale-stage contract as the composed path: the
            # bucket key AND the total layout length must match, else
            # the dispatch rebuilds serially (a counted staging miss)
            want_total = sum(self._ragged_rows_pack_sizes(
                r_pad, pc_pad, b, c_pad, chained,
                guided=False, stop_cap=stop_cap,
            ))
            if int(staged[1].shape[0]) == want_total:
                packed_dev, runs = staged[1:]
        if packed_dev is None:
            with self.phases.span("pack"):
                _r, _pc, packed = self._fill_ragged_rows_pack(
                    pf_chunks, pf_start_positions, pf_block_tables,
                    pf_total_lens, pf_sampling, c_pad, chained, token_ids,
                    positions, block_tables, context_lens, steps, temps,
                    top_ps, top_ks, keys, min_ps=min_ps,
                    guided_lanes=guided_lanes, stop=stop,
                    pf_budgets=pf_budgets, dec_budgets=dec_budgets,
                    lanes=lanes,
                )
                runs = self._packed_runs
            with self.phases.span("h2d"):
                packed_dev = jnp.asarray(packed)

        pen_kw = self._decode_pen_kwargs(penalties, b, c_pad, lanes)
        guided_kw, guided_shapes = self._decode_guided_kwargs(guided)
        bias_kw, bias_cap = self._decode_bias_kwargs(
            logit_bias, b, lanes
        )
        cache_key = ("rows", r_pad, pc_pad, b, c_pad, steps,
                     penalties is not None, want_logprobs, chained,
                     guided_shapes, bias_cap, stop_cap)
        build = phases.NO_SPAN
        if cache_key not in self._ragged_fns:
            logger.info(
                "compiling ragged-rows round rows=%d pctx=%d b=%d "
                "ctx=%d k=%d pen=%s lp=%s chained=%s guided=%s "
                "bias=%d stop=%s",
                r_pad, pc_pad, b, c_pad, steps, penalties is not None,
                want_logprobs, chained, guided_shapes, bias_cap,
                stop_cap,
            )
            build = self._note_compile("ragged_rows", cache_key)
            self._ragged_fns[cache_key] = self._build_ragged_rows(
                r_pad, pc_pad, b, c_pad, steps,
                use_penalties=penalties is not None,
                want_logprobs=want_logprobs, chained=chained,
                guided_shapes=guided_shapes, bias_cap=bias_cap,
                stop_cap=stop_cap,
            )
        fn = self._ragged_fns[cache_key]
        lora_kw = {}
        if self.lora_manager is not None:
            slots = np.zeros((b,), dtype=np.int32)
            if lora_slots is not None:
                slots[lanes] = lora_slots
            # the fused step-0 forward concatenates prefill + decode
            # slot vectors, so the prefill side always ships per-row
            pf_rows = self._rows_slot_vector(
                pf_chunks, pf_lora_slots, r_pad
            )
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.asarray(slots),
                "pf_lora_slots": jnp.asarray(pf_rows),
            }
        chained_kw = {"chained_tokens": token_ids} if chained else {}
        self._note_attn_context(context_lens, steps, pf_total_lens,
                                runs=runs, lanes=lanes)
        self.note_sampler(steps, temps)
        self.note_sampler(1, pf_sampling and pf_sampling[0])
        with self.phases.span("dispatch"), build:
            pf_sampled, pf_logits, ys, self.k_cache, self.v_cache = fn(
                self.params,
                self.k_cache,
                self.v_cache,
                packed_dev,
                **chained_kw,
                **guided_kw,
                **pen_kw,
                **bias_kw,
                **lora_kw,
            )
        return pf_sampled, pf_logits, ys

    def precompile_ragged(
        self, context_lens: list[int], ks: list[int], max_groups: int,
        chunk_len: int, stop: bool = False, chained: bool = False,
    ) -> int:
        """Warm the ragged round's program variants: every pow2
        prefill-lane group size up to max_groups x each fused-K bucket x
        each ctx bucket, prefill lanes' context matched to the decode
        bucket (the steady-state mixed-round shape: sessions in one
        workload share a length regime). Under the single kernel the
        program keys on padded ROW-count buckets, so group sizes that
        pack to the same row bucket dedupe to ONE warm dispatch — the
        variant space shrinks from the (group, chunk) lane-mix grid to
        the row diagonal. Trash tables at the top of the pool, same
        safety contract as precompile_prefill/decode. `chained=True`
        additionally warms the staged-prefetch variant (device-array
        decode tokens — a distinct program key)."""
        b = self.config.max_num_seqs
        bs = self.block_size
        nb = self.num_blocks
        temps = np.zeros((b,), np.float32)
        top_ps = np.ones((b,), np.float32)
        top_ks = np.full((b,), -1, np.int32)
        keys = np.zeros((b, 2), np.uint32)
        groups: list[int] = []
        s = 1
        while s <= max(1, max_groups):
            groups.append(s)
            s *= 2
        seen: set[tuple] = set()
        n = 0
        for cl in context_lens:
            for k in ks:
                c_pad = self._ctx_bucket(cl + max(0, k - 1))
                ctx = c_pad - max(0, k - 1)
                clen = min(chunk_len, c_pad)
                for s in groups:
                    if self.ragged_kernel:
                        # single-kernel mode: the program keys on the
                        # padded ROW bucket, so distinct lane mixes
                        # that pack to the same row count are ONE
                        # variant — the (group, chunk) grid collapses
                        key = (
                            self._rows_bucket(s * _ceil_tq(clen)),
                            c_pad, k,
                        )
                    else:
                        key = (s, self._prefill_bucket(clen), c_pad, k)
                    if key in seen:
                        continue
                    seen.add(key)
                    npages = c_pad // bs
                    if nb < 2 * (s + 1) * npages + 64:
                        logger.warning(
                            "ragged precompile: skipping s=%d ctx=%d "
                            "k=%d — pool of %d blocks too small",
                            s, c_pad, k, nb,
                        )
                        continue
                    # decode lanes share the topmost trash table;
                    # prefill lanes stack below it, all above live KV
                    dec_table = list(range(nb - npages, nb))
                    pf_tabs = [
                        list(range(nb - (i + 2) * npages,
                                   nb - (i + 1) * npages))
                        for i in range(s)
                    ]
                    stop_kw = {}
                    if stop:
                        # budget == k: nothing freezes, full trip — the
                        # PROGRAM equals what live batches select
                        stop_kw = {"stop": (
                            np.full((b,), -1, np.int32),
                            np.zeros((b,), np.int32),
                            np.full((b,), k, np.int32),
                            None,
                        )}
                    out = self.ragged_dispatch(
                        [[1] * clen] * s, [c_pad - clen] * s, pf_tabs,
                        [c_pad] * s,
                        [1] * b, [ctx - 1] * b, [dec_table] * b,
                        [ctx] * b, k,
                        temps, top_ps, top_ks, keys, **stop_kw,
                    )
                    jax.block_until_ready(out)
                    n += 1
                    if chained and k > 1:
                        ys = out[2]
                        toks = ys[0] if isinstance(ys, tuple) else ys
                        out2 = self.ragged_dispatch(
                            [[1] * clen] * s, [c_pad - clen] * s,
                            pf_tabs, [c_pad] * s,
                            toks[-1], [ctx - 1] * b, [dec_table] * b,
                            [ctx] * b, k,
                            temps, top_ps, top_ks, keys, **stop_kw,
                        )
                        jax.block_until_ready(out2)
                        n += 1
        return n

    # -- embeddings (stateless, /v1/embeddings) ----------------------------
    def _build_embed(self, t_pad: int, c_pad: int):
        """One chunked-prefill embed step over a caller-owned scratch KV
        cache; returns (hidden-sum over valid chunk rows, kc, vc). Reuses
        llama.forward (LoRA/bias/rope can never diverge from serving) with
        the chunk x context score shape of the serving prefill path, so
        long inputs never materialize t x t attention."""
        mc = self.model_config
        scale = self._scale

        def step(params, kc, vc, toks, positions, total_len, valid_len,
                 lora=None, lora_slots=None):
            def attn(q, l, kcache, vcache):
                return xla_attn.context_attention_prefill(
                    q, kcache[l].swapaxes(0, 1), vcache[l].swapaxes(0, 1),
                    positions, total_len, scale,
                    window=self.model_config.sliding_window,
                )

            # scratch cache row == absolute position; padded chunk rows
            # carry position c_pad, landing in the extra trash row.
            # self._forward so pipeline-parallel engines stage this too
            # (a plain scan over pp-sharded params would make GSPMD
            # all-gather the full layer stack per device)
            h, kc, vc = self._forward(
                mc, params, toks, positions, kc, vc,
                write_slots=positions,
                attn_fn=attn,
                logits_rows=jnp.arange(t_pad),
                lora=lora, lora_slots=lora_slots,
                return_hidden=True,
            )  # (t_pad, hidden) f32
            keep = (positions < valid_len)[:, None].astype(jnp.float32)
            return jnp.sum(h * keep, axis=0), kc, vc

        return jit_program("embed", step, donate_argnums=(1, 2),
                           **self._step_jit_kwargs())

    def embed(self, token_ids: list[int], lora_slot: int = 0) -> np.ndarray:
        if self.model_config.layer_groups:
            raise NotImplementedError(
                "embeddings are not served for a model of layer groups "
                "(the embed program runs over a scratch cache of one kind)"
            )
        return self._embed(token_ids, lora_slot)

    def _embed(self, token_ids: list[int], lora_slot: int = 0) -> np.ndarray:
        """Mean-pooled + L2-normalised final hidden state -> (hidden,) f32
        (decoder-as-embedder, e5-mistral pattern). Inputs above
        max_model_len are rejected, never silently truncated."""
        t = len(token_ids)
        if t > self.max_model_len:
            raise ValueError(
                f"embedding input has {t} tokens, exceeds max_model_len="
                f"{self.max_model_len}"
            )
        mc = self.model_config
        c_pad = self._ctx_bucket(t)
        chunk = self.config.max_prefill_chunk
        # c_pad + 1 rows: the last row is the trash slot padded chunk rows
        # write into (they carry position c_pad)
        kc = jnp.zeros(
            (mc.cache_layers, mc.num_kv_heads, c_pad + 1, mc.head_dim),
            self.cache_dtype,
        )
        vc = jnp.zeros_like(kc)
        lora_kw = {}
        if self.lora_manager is not None:
            lora_kw = {
                "lora": self.lora_manager.buffers,
                "lora_slots": jnp.int32(lora_slot),
            }
        pooled_sum = np.zeros((mc.hidden_size,), np.float64)
        for start in range(0, t, chunk):
            ids = token_ids[start: start + chunk]
            t_pad = self._prefill_bucket(len(ids))
            toks = np.zeros((t_pad,), np.int32)
            toks[: len(ids)] = ids
            # padded rows park at position c_pad (write redirected to 0,
            # masked out of both attention and pooling)
            positions = np.full((t_pad,), c_pad, np.int32)
            positions[: len(ids)] = np.arange(start, start + len(ids))
            key = (t_pad, c_pad)
            build = phases.NO_SPAN
            if key not in self._embed_fns:
                logger.info("compiling embed step t=%d ctx=%d", t_pad,
                            c_pad)
                build = self._note_compile("embed", key)
                self._embed_fns[key] = self._build_embed(t_pad, c_pad)
            with build:
                part, kc, vc = self._embed_fns[key](
                    self.params, kc, vc, jnp.asarray(toks),
                    jnp.asarray(positions),
                    jnp.int32(start + len(ids)), jnp.int32(t), **lora_kw,
                )
            pooled_sum += np.asarray(part, np.float64)
        pooled = pooled_sum / max(t, 1)
        norm = float(np.linalg.norm(pooled))
        return (pooled / max(norm, 1e-12)).astype(np.float32)

    # -- cache import/export (KV offload + PD transfer tiers) -------------
    # stackcheck: hot-path — the deferred-export snapshot is enqueued on
    # the engine step thread right after (or between) device dispatches:
    # it may only ENQUEUE the gather; the blocking d2h materialization
    # belongs to the offload worker (materialize_export)
    def stage_export_blocks(self, block_ids: list[int]) -> tuple:
        """Enqueue the device-side snapshot of whole KV blocks.

        Returns a handle of on-device arrays. Because device ops execute
        in enqueue order, any LATER dispatch that overwrites these slots
        cannot corrupt the snapshot — the caller may release the blocks
        for reuse the moment this returns."""
        idx = jnp.asarray(
            xla_attn.block_table_slots(
                jnp.asarray(block_ids, jnp.int32), self.block_size
            )
        )
        # (L, nkv, n*bs, d) gathers; async dispatch, no host sync
        return (len(block_ids), self.k_cache[:, :, idx],
                self.v_cache[:, :, idx])

    def materialize_export(self, handle: tuple) -> np.ndarray:
        """Blocking half of the deferred export (runs on the offload
        worker thread): fetch the staged gathers and relayout to the
        wire format (2, cache_layers, n, nkv, block_size, d) — block count
        stays at dim 2, so offload/transfer consumers that slice or
        count blocks (`data[:, :, i]`, `data.shape[2]`) are
        layout-agnostic."""
        n, k, v = handle
        mc = self.model_config
        shape = (mc.cache_layers, mc.num_kv_heads, n, self.block_size,
                 mc.head_dim)
        return np.stack([
            np.asarray(k).reshape(shape).swapaxes(1, 2),
            np.asarray(v).reshape(shape).swapaxes(1, 2),
        ])

    def export_blocks(self, block_ids: list[int]) -> np.ndarray:
        """Synchronous device->host copy of whole KV blocks (PD transfer
        server + --sync-kv-offload path)."""
        return self.materialize_export(self.stage_export_blocks(block_ids))

    def _build_import(self, n_src_pad: int, n_dst_pad: int):
        """Donated in-place scatter of staged wire-format blocks into
        the KV caches: replaces the whole-cache-reallocating eager
        `.at[].set` (which copied both cache arrays per restore)."""
        mc = self.model_config
        bs = self.block_size

        def step(kc, vc, bids, cols, staged):
            kc, vc = self._enter_caches(kc, vc, counters=False)
            # staged: (2, L, n_src_pad, nkv, bs, d) wire layout
            sel = staged[:, :, cols]  # (2, L, n_dst_pad, nkv, bs, d)
            hm = jnp.swapaxes(sel, 2, 3)  # head-major
            flat = hm.reshape(
                2, mc.cache_layers, mc.num_kv_heads, n_dst_pad * bs,
                mc.head_dim,
            ).astype(self.cache_dtype)
            idx = xla_attn.block_table_slots(bids, bs)
            kc = kc.at[:, :, idx].set(flat[0])
            vc = vc.at[:, :, idx].set(flat[1])
            return kc, vc

        return jit_program("kv_import", step, donate_argnums=(0, 1),
                           **self._step_jit_kwargs(0))

    def _import_args(
        self, block_ids: list[int], src_cols: list[int], n_pad: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host args for the donated scatter, padded to `n_pad`.
        Padding rows target the null block (their writes are trash by
        design) and read staged column 0 (always present)."""
        n = len(block_ids)
        bids = np.zeros((n_pad,), np.int32)
        bids[:n] = block_ids
        cols = np.zeros((n_pad,), np.int32)
        cols[:n] = src_cols
        return bids, cols

    # stackcheck: hot-path — restore staging: pad + START the h2d
    # upload; enqueue-only (no device fetch, no tier IO)
    def stage_import_blocks(self, data: np.ndarray) -> tuple:
        """Begin the restore's host->device upload. `data` is the wire
        layout (2, L, n, nkv, bs, d); the block axis pads to pow2 so the
        donated scatter compiles one variant per bucket. Returns a
        handle for import_staged_blocks. Under a mesh the handle stays
        host-side (a committed single-device put would be resharded —
        same rule as the decode/prefill staging)."""
        n = data.shape[2]
        n_pad = next_pow2(max(n, 1))
        if n_pad != n:
            pad = np.zeros(
                data.shape[:2] + (n_pad - n,) + data.shape[3:],
                dtype=data.dtype,
            )
            data = np.concatenate([data, pad], axis=2)
        if self.mesh is not None:
            return (n, data)
        return (n, jax.device_put(data))

    # stackcheck: hot-path — the restore's device-side write on the
    # admission path: one donated-jit dispatch, no host sync
    def import_staged_blocks(
        self, block_ids: list[int], handle: tuple, src_cols: list[int],
    ) -> None:
        """In-place donated scatter of staged (already uploaded/
        uploading) blocks into the KV cache. `src_cols[i]` names the
        staged block-axis column holding block_ids[i]'s contents."""
        if not block_ids:
            return
        _, staged = handle
        # pad the DST list to the staged width: partial adoptions (full
        # HBM, broken chain) reuse the SAME compiled (n, n) variant as
        # the full restore instead of compiling an off-diagonal shape
        # inside a live admission — precompile_kv_import's diagonal is
        # then the complete variant space
        n_pad = staged.shape[2]  # already pow2 (stage_import_blocks)
        bids, cols = self._import_args(block_ids, src_cols, n_pad)
        key = (n_pad, n_pad)
        fn = self._import_fns.get(key)
        build = phases.NO_SPAN
        if fn is None:
            logger.info("compiling kv import n_src=%d n_dst=%d", *key)
            build = self._note_compile("kv_import", key)
            fn = self._import_fns[key] = self._build_import(*key)
        with build:
            self.k_cache, self.v_cache = fn(
                self.k_cache, self.v_cache, jnp.asarray(bids),
                jnp.asarray(cols), staged,
            )

    def precompile_kv_import(self, max_blocks: int) -> int:
        """Warm the donated import scatter's (n, n) pow2 diagonal up to
        max_blocks so no XLA compile lands inside a live restore. The
        diagonal IS the complete variant space: import_staged_blocks
        pads the dst list to the staged width, so partial adoptions
        never dispatch an off-diagonal shape. Writes target the null
        block (trash by design). Returns dispatches."""
        mc = self.model_config
        # the wire dtype is whatever materialize_export's np.asarray
        # yields for the cache dtype (ml_dtypes bf16 on bf16 caches) —
        # warming float32 would compile a variant live traffic never hits
        wire_dt = np.asarray(jnp.zeros((), self.cache_dtype)).dtype
        n = 0
        p = 1
        while p <= next_pow2(max(1, max_blocks)):
            data = np.zeros(
                (2, mc.cache_layers, p, mc.num_kv_heads, self.block_size,
                 mc.head_dim), wire_dt,
            )
            handle = self.stage_import_blocks(data)
            self.import_staged_blocks([0] * p, handle, list(range(p)))
            n += 1
            p *= 2
        return n

    def import_blocks(self, block_ids: list[int], data: np.ndarray) -> None:
        """Host->device restore of whole KV blocks (inverse of export).
        Routed through the staged in-place scatter — a donated update
        instead of a whole-cache-reallocating eager `.at[].set`."""
        handle = self.stage_import_blocks(np.asarray(data))
        self.import_staged_blocks(
            block_ids, handle, list(range(len(block_ids)))
        )

    # -- long-context ring prefill (engine/long_prefill.py) ----------------
    def build_long_prefiller(self):
        """Construct the ("tp", "sp") ring prefiller for the long-
        prefill lane: tp matches the serving tensor-parallel size, sp =
        EngineConfig.context_parallel_size. The ring mesh prefers
        devices PAST the serving one(s) when the host has spares, so
        ring compute does not queue behind decode dispatches on the
        serving chip; with exactly tp*sp devices it shares them. The
        prefiller holds its own (re-placed) copy of the weights — the
        memory price of running two meshes, stated in tutorial 18.
        Raises when the host lacks tp*sp devices (the engine then
        serves long prompts on the chunked path)."""
        from production_stack_tpu.parallel.long_context import (
            LongContextPrefiller,
            make_sp_mesh,
        )

        cfg = self.config
        sp = cfg.context_parallel_size
        tp = max(1, cfg.tensor_parallel_size)
        if sp <= 1:
            raise ValueError("context_parallel_size must be > 1")
        devs = jax.devices()
        need = tp * sp
        serving = self.mesh.size if self.mesh is not None else 1
        if len(devs) >= serving + need:
            pool = devs[serving: serving + need]
        elif len(devs) >= need:
            pool = devs[:need]
        else:
            raise ValueError(
                f"context_parallel_size={sp} x tp={tp} needs {need} "
                f"devices; host has {len(devs)}"
            )
        mesh = make_sp_mesh(tp, sp, devices=pool)
        return LongContextPrefiller(
            self.model_config, self.params, mesh,
            cache_dtype=self.cache_dtype,
        )
