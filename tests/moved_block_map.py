"""Fused decode rounds of a model with a windowed cache group between
which the block map MOVES, against the single-step path: the scenario
of tests/test_layer_groups.py and tests/test_layer_groups_laguna.py
(each hands in its own engine, `serve` and `ids`).

A fused round maps its lanes' tables into the windowed kind's pool ONCE,
where it unpacks its constants (`ModelRunner._map_tables`), with the map
its dispatch uploaded; the next round is its own program with the map as
the block manager left it in between. So between two consecutive rounds
here a lane lets window pages go behind it (unregistered: their twins
are dropped and the map's entries go to 0), takes new ones, and a
session RETURNS: its prefix hit is cut back where a twin was lost, the
blocks behind the cut are computed again and twinned anew, and it joins
the next round on a lane of its own.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np

K = 4        # steps a fused round
CHUNK = 16


def _prefill(e, tokens):
    """Chunked prefill of a prompt, nothing registered (a lane's pages
    leave the map as its window passes them) -> (table, cached tokens,
    the first token sampled)."""
    r, bm = e.runner, e.block_manager
    table, cached = bm.allocate_prompt(tokens)
    start = cached
    while start < len(tokens):
        end = min(start + CHUNK, len(tokens))
        bm.prepare_chunk(table, start, end)
        token, _ = r.prefill(tokens[start:end], start, table, end)
        start = end
    return table, cached, int(token)


def fused_rounds_across_a_moved_map(e, serve, ids):
    """Runs the scenario on `e` (the kernel path, `K` scheduler steps,
    four lanes) and asserts it; leaves the block manager with no
    sequence."""
    r, bm = e.runner, e.block_manager
    b = r.config.max_num_seqs
    # the session that will return: served, registered, gone; and the
    # window group loses the twin of its block 7 meanwhile
    past = ids(40, seed=71)
    _, _, table = serve(e, past, 40, CHUNK)
    bm.free(table)
    bm._drop_twin(table[7])

    live = []  # a sequence: tokens so far, prompt length, table, lane

    def admit(tokens, lane):
        table, cached, first = _prefill(e, tokens)
        live.append(SimpleNamespace(
            tokens=tokens + [first], n_prompt=len(tokens), table=table,
            lane=lane))
        return cached

    maps = []

    def fused_round():
        pos = [len(s.tokens) - 1 for s in live]
        for s, p in zip(live, pos):
            assert bm.ensure_capacity(p + K, s.table)
            bm.release_behind(s.table, p)
        maps.append(bm.block_map.copy())
        n = len(live)
        lanes = np.asarray([s.lane for s in live])
        # the lanes that hold no sequence ship the zero table, and the
        # null block maps to itself whatever the map holds by now
        tables = np.zeros((b, max(len(s.table) for s in live)), np.int32)
        for s in live:
            tables[s.lane, :len(s.table)] = s.table
        mapped = np.asarray(r._map_tables(r.k_cache, jnp.asarray(tables)))
        idle = np.setdiff1d(np.arange(b), lanes)
        assert len(idle) and not mapped[idle].any()
        assert not bm.block_map[0]
        assert all(mapped[s.lane, len(s.table) - 1] for s in live)
        temps, top_ps, top_ks, min_ps, keys = r._sampling_args(n)
        toks, valid = r.decode_multi(
            [s.tokens[-1] for s in live], pos, [s.table for s in live],
            [p + 1 for p in pos], K, temps, top_ps, top_ks, keys,
            min_ps=min_ps, lanes=lanes,
            stop=(np.full(n, -1, np.int32), np.zeros(n, np.int32),
                  np.full(n, K, np.int32), None))
        toks, valid = np.asarray(toks), np.asarray(valid)
        assert (valid[lanes] == K).all() and not valid[idle].any()
        for s in live:
            s.tokens += [int(t) for t in toks[:, s.lane]]

    # a lane between two sequences holds none
    assert admit(ids(41, seed=72), 0) == 0
    assert admit(ids(30, seed=73), 2) == 0
    fused_round()
    # the hit of nine blocks needs the twins of blocks 6, 7, 8 and ends
    # after block 6: blocks 7.. are computed again, and twinned
    assert admit(past[:36] + ids(9, seed=74), 3) == 28
    fused_round()
    fused_round()
    before, after = maps[0], maps[1]
    assert ((before != 0) & (after == 0)).any(), "a window page released"
    assert ((before == 0) & (after != 0)).any(), "a page twinned"
    assert all((x != y).any() for x, y in zip(maps, maps[1:]))

    for s in live:
        bm.free(s.table)
    for s, rounds in zip(live, (3, 3, 2)):
        rows, _, table = serve(e, s.tokens, s.n_prompt, CHUNK, reuse=False)
        bm.free(table)
        want = [int(np.argmax(rows[p]))
                for p in range(s.n_prompt - 1, len(s.tokens) - 1)]
        assert s.tokens[s.n_prompt:] == want, f"lane {s.lane}"
        assert len(want) == 1 + K * rounds
