"""Long-context prefill: one full prompt, sequence-sharded over the mesh.

Serving role (reference parity): the reference stack's long-context story
is disaggregated prefill + KV streaming (LMCache/NIXL); its prefill pod
still has to FIT the prompt on one GPU's HBM. This module removes that
ceiling the TPU way: activations and KV for a single long prompt are
sharded over an `sp` mesh axis, attention runs as a ring
(parallel/ring_attention.py), and max prompt length scales linearly with
the ring size. The output KV (layer-stacked, head-major) feeds either
the local paged cache or the disaggregated-prefill transfer chain
(kv/transfer.py) exactly like chunked-prefill KV does.

The model math is NOT re-implemented here: the forward is
models/llama.forward — the same function serving uses — with the ring
supplied through its `attn_fn` extension point and a full-sequence
"cache" (slots 0..S-1) standing in for the paged one, so every model
feature (qkv bias, MoE blocks, sliding windows, future changes) has
exactly one implementation. Only the sharding is this module's business:
the KV cache is pinned to P(None, None, sp, None) via jit out_shardings,
and the ring's shard_map in_specs re-anchor q/k/v to the sp layout at
every layer, which is what keeps XLA from gathering the sequence
anywhere.

Two entry points:

- `prefill(token_ids)`: the whole prompt in ONE jitted call (offline /
  batch use; one program variant per padded length).
- the chunked serving API (`begin_cache` / `stage_tokens` /
  `prefill_chunk`): the prompt runs as C-token ring chunks against the
  growing full-sequence cache — each chunk is one enqueue-only jitted
  dispatch, so a serving engine can keep running decode rounds for
  other users between chunks, and chunk N+1's token buffer uploads
  (staged h2d) while chunk N rings. Program variants key on
  (C, S_pad) with S_pad on a pow2-of-chunks ladder, so the jit space
  stays O(log max_len). Each chunk pays attention over the full S_pad
  rows (unwritten tail rows are causally masked), a ~2x FLOP overhead
  versus a perfect growing-window schedule — the static-shape price,
  same trade the engine's paged chunk prefill makes.

Composes with tensor parallelism on a 2D ("tp", "sp") mesh: weights stay
Megatron-sharded over tp (parallel/sharding.py), the sequence over sp,
and the ring only moves kv-head-width blocks over ICI.

Scope: Llama-family decoders (dense and MoE/Mixtral), batch=1 (a long
prompt is the whole batch), no LoRA (adapters target short interactive
traffic; chunked prefill serves them). Sliding-window models ride the
ring's window mask (HF semantics, matching ops/attention.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.models import llama
from production_stack_tpu.models.config import ModelConfig
from production_stack_tpu.parallel.ring_attention import (
    ring_attention_local,
)
from production_stack_tpu.parallel import sharding as sharding_rules

SP_AXIS = "sp"


def make_sp_mesh(tp_size: int, sp_size: int, devices=None) -> Mesh:
    """("tp", "sp") mesh: heads over tp, sequence over sp."""
    import numpy as np

    devs = devices if devices is not None else jax.devices()
    need = tp_size * sp_size
    if need > len(devs):
        raise ValueError(f"tp*sp={need} > available devices {len(devs)}")
    return Mesh(
        np.asarray(devs[:need]).reshape(tp_size, sp_size), ("tp", SP_AXIS)
    )


def _forward(cfg: ModelConfig, params: dict, token_ids: jax.Array,
             last: jax.Array, mesh: Mesh, cache_dtype=None,
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-prompt forward via llama.forward + ring attn_fn.

    token_ids: (S,), S divisible by sp size; `last` is the row of the
    final REAL token (padding sits after it). Returns (that row's logits
    (V,) f32, k (L, nkv, S, d) head-major — the engine cache layout —
    v likewise).
    """
    S = token_ids.shape[0]
    has_tp = "tp" in mesh.axis_names and mesh.shape["tp"] > 1
    spec4 = (P(None, SP_AXIS, "tp", None) if has_tp
             else P(None, SP_AXIS, None, None))
    ring = jax.shard_map(
        functools.partial(
            ring_attention_local, axis_name=SP_AXIS, causal=True,
            scale=llama.attention_scale(cfg), window=cfg.sliding_window,
        ),
        mesh=mesh, in_specs=(spec4, spec4, spec4), out_specs=spec4,
    )

    def attn_fn(q, layer, kc, vc):
        # the full-sequence cache rows ARE the sequence (head-major:
        # (nkv, S, d) per layer); the ring wants (1, S, nkv, d)
        return ring(q[None], kc[layer].swapaxes(0, 1)[None],
                    vc[layer].swapaxes(0, 1)[None])[0]

    dtype = cache_dtype if cache_dtype is not None else (
        params["embed"].dtype
    )
    kc = jnp.zeros((cfg.num_layers, cfg.num_kv_heads, S, cfg.head_dim),
                   dtype)
    positions = jnp.arange(S, dtype=jnp.int32)
    logits, kc, vc = llama.forward(
        cfg, params, token_ids, positions, kc, jnp.zeros_like(kc),
        write_slots=positions, attn_fn=attn_fn, logits_rows=last[None],
    )
    return logits[0], kc, vc


class LongContextPrefiller:
    """Jitted sequence-parallel prefill over a fixed mesh.

    Pad prompts to a multiple of the sp size (use `pad_to`); KV rows for
    the padding are garbage and must be dropped by the caller — token
    count is returned alongside so downstream paged-cache insertion
    (engine) or PD transfer (kv/transfer.py) slices `k[:, :, :n]`.

    `cache_dtype` controls the ring cache's storage dtype so serving
    callers can match the engine's paged-cache dtype exactly (the KV a
    chunked prefill would have written is quantized through the same
    cast); default = the params dtype.
    """

    def __init__(self, cfg: ModelConfig, params: dict, mesh: Mesh,
                 cache_dtype=None):
        if SP_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must carry an '{SP_AXIS}' axis")
        if "tp" in mesh.axis_names and mesh.shape["tp"] > 1:
            sharding_rules.validate_tp(cfg, mesh.shape["tp"])
            params = jax.device_put(
                params, sharding_rules.param_shardings(mesh, cfg)
            )
        else:
            params = jax.device_put(
                params,
                jax.tree.map(lambda _: NamedSharding(mesh, P()), params),
            )
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.sp = mesh.shape[SP_AXIS]
        self.window = cfg.sliding_window
        self.cache_dtype = (
            jnp.dtype(cache_dtype) if cache_dtype is not None
            else params["embed"].dtype
        )
        self.kv_spec = NamedSharding(mesh, P(None, None, SP_AXIS, None))
        self._rep = NamedSharding(mesh, P())
        self._tok_sharding = NamedSharding(mesh, P(SP_AXIS))
        self._fn = jax.jit(
            functools.partial(
                _forward, cfg, mesh=mesh, cache_dtype=self.cache_dtype
            ),
            out_shardings=(self._rep, self.kv_spec, self.kv_spec),
        )
        # chunked serving programs, keyed (C, S_pad); cache allocators
        # keyed S_pad
        self._chunk_fns: dict[tuple[int, int], object] = {}
        self._zeros_fns: dict[int, object] = {}

    def pad_to(self, n: int) -> int:
        return -(-n // self.sp) * self.sp

    def prefill(self, token_ids) -> tuple[jax.Array, jax.Array, jax.Array, int]:
        """token_ids: list/array of ints. Returns (logits, k, v, n) with
        k/v (L, nkv, S_pad, d) head-major, sp-sharded on the sequence
        dim; rows >= n are padding (slice `k[:, :, :n]`)."""
        n = len(token_ids)
        S = self.pad_to(n)
        ids = jnp.zeros((S,), jnp.int32).at[:n].set(
            jnp.asarray(token_ids, jnp.int32)
        )
        logits, k, v = self._fn(
            self.params, ids, jnp.asarray(n - 1, jnp.int32)
        )
        return logits, k, v, n

    # -- chunked serving API ------------------------------------------------
    def chunk_to(self, chunk: int, align: int = 1) -> int:
        """Round a requested chunk length UP to a multiple of the ring
        size and `align` (the engine passes its KV block size so a
        chunk-multiple sequence pad always covers whole paged blocks)."""
        m = self.sp
        while m % align:
            m += self.sp  # lcm walk: sp and align are tiny
        return -(-chunk // m) * m

    def seq_pad(self, n: int, chunk: int) -> int:
        """Padded sequence length for an n-token prompt served in
        `chunk`-token ring chunks: chunk x pow2(chunks) — the program
        variant ladder stays O(log max_len) deep."""
        c = max(1, -(-n // chunk))
        p = 1
        while p < c:
            p *= 2
        return p * chunk

    def begin_cache(self, s_pad: int) -> tuple[jax.Array, jax.Array]:
        """Fresh sp-sharded full-sequence K/V cache for one prompt
        (enqueue-only device zeros)."""
        fn = self._zeros_fns.get(s_pad)
        if fn is None:
            cfg = self.cfg
            shape = (cfg.num_layers, cfg.num_kv_heads, s_pad,
                     cfg.head_dim)
            dt = self.cache_dtype

            fn = self._zeros_fns[s_pad] = jax.jit(
                lambda: (jnp.zeros(shape, dt), jnp.zeros(shape, dt)),
                out_shardings=(self.kv_spec, self.kv_spec),
            )
        return fn()

    # stackcheck: hot-path — staged h2d of a ring chunk's token buffer:
    # one device_put enqueue, no sync (chunk N+1's upload rides out
    # chunk N's compute — the PR 1 staging pattern)
    def stage_tokens(self, ids, chunk: int) -> jax.Array:
        """Upload one chunk's token ids (padded to `chunk`, sharded
        over sp) ahead of its dispatch."""
        import numpy as np

        arr = np.zeros((chunk,), np.int32)
        arr[: len(ids)] = ids
        return jax.device_put(arr, self._tok_sharding)

    def _build_chunk(self, C: int, S: int):
        cfg = self.cfg
        mesh = self.mesh
        has_tp = "tp" in mesh.axis_names and mesh.shape["tp"] > 1
        spec4 = (P(None, SP_AXIS, "tp", None) if has_tp
                 else P(None, SP_AXIS, None, None))
        ring = jax.shard_map(
            functools.partial(
                ring_attention_local, axis_name=SP_AXIS, causal=True,
                scale=llama.attention_scale(cfg), window=self.window,
            ),
            mesh=mesh,
            in_specs=(spec4, spec4, spec4, P()),
            out_specs=spec4,
        )

        def step(params, kc, vc, tokens, start, last_row):
            positions = start + jnp.arange(C, dtype=jnp.int32)

            def attn_fn(q, layer, kcc, vcc):
                # q covers rows [start, start+C); the cache covers the
                # whole padded sequence — q_offset anchors the causal
                # mask at the chunk's global positions, and rows the
                # earlier chunks have not written yet sit ABOVE every
                # query position, so the mask already excludes them
                return ring(
                    q[None], kcc[layer].swapaxes(0, 1)[None],
                    vcc[layer].swapaxes(0, 1)[None], start,
                )[0]

            logits, kc, vc = llama.forward(
                cfg, params, tokens, positions, kc, vc,
                write_slots=positions, attn_fn=attn_fn,
                logits_rows=last_row[None],
            )
            return logits[0], kc, vc

        # the big full-sequence caches are donated: each chunk updates
        # them in place instead of holding two copies per dispatch
        return jax.jit(
            step, donate_argnums=(1, 2),
            out_shardings=(self._rep, self.kv_spec, self.kv_spec),
        )

    # stackcheck: hot-path — one enqueue-only jitted dispatch per ring
    # chunk on the engine step thread; no device fetch (the final
    # logits are pulled by the long-prefill worker, never here)
    def prefill_chunk(
        self, kc: jax.Array, vc: jax.Array, tokens: jax.Array,
        start: int, last_row: int,
    ) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Run one C-token chunk at global offset `start` against the
        full-sequence cache. `tokens` comes from stage_tokens (already
        on device). Returns (last_row's logits (V,) f32, kc, vc) — the
        caches are donated, pass the returned ones forward."""
        C = int(tokens.shape[0])
        S = int(kc.shape[2])
        fn = self._chunk_fns.get((C, S))
        if fn is None:
            fn = self._chunk_fns[(C, S)] = self._build_chunk(C, S)
        return fn(
            self.params, kc, vc, tokens,
            jnp.asarray(start, jnp.int32), jnp.asarray(last_row, jnp.int32),
        )
