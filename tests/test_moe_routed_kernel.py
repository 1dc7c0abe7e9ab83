"""`ops/expert_ffn.py`: the routed experts' one kernel, in interpret mode
on the CPU, against the plain `ragged_dot` lines and against a float32
oracle that loops over pairs; and what a routed layer's TPU path holds
at the jaxpr level."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.ops import moe
from production_stack_tpu.ops.expert_ffn import MAX_ROWS, expert_ffn

D = 256
ROUTER = 32  # the router's width in every case


def layer(case, dtype=jnp.float32):
    """x, router, bias, (stacked) weights and keywords of one case."""
    n, f = case.get("n", 24), case.get("f", 256)
    e_loc, top_k = case.get("e_loc", ROUTER), case.get("top_k", 4)
    stack, at = case.get("stack", (None, None))
    keys = jax.random.split(jax.random.key(case.get("seed", 7)), 6)
    x = jax.random.normal(keys[0], (n, D), dtype)
    router = jax.random.normal(keys[1], (D, ROUTER))
    bias = jnp.zeros((ROUTER,))
    first = case.get("first_expert", 0)
    for e in case.get("favour", ()):  # experts every row chooses
        bias = bias.at[first + e].set(20.0)
    shapes = [(e_loc, D, f), (e_loc, D, f), (e_loc, f, D)]
    ws = [(0.1 * jax.random.normal(k, s)).astype(dtype)
          for k, s in zip(keys[2:5], shapes)]
    if stack is not None:
        # every other layer of the stack holds no number at all
        ws = [jnp.full((stack, *w.shape), jnp.nan, dtype).at[at].set(w)
              for w in ws]
    valid = None
    if case.get("invalid"):
        valid = jnp.arange(n) % 3 != 0
        x = jnp.where(valid[:, None], x, jnp.nan)
    if case.get("nobody"):
        valid = jnp.zeros((n,), bool)
    kw = dict(top_k=top_k, first_expert=first, scoring="sigmoid",
              valid=valid,
              stack_index=None if stack is None else jnp.int32(at))
    return x, router, bias, ws, kw


def oracle(x, router, bias, ws, kw):
    """Pair by pair in float32 (numpy), the rounding before the down
    projection included."""
    wg, wu, wd = ws
    if kw["stack_index"] is not None:
        wg, wu, wd = (w[int(kw["stack_index"])] for w in ws)
    valid = kw["valid"]
    real = np.ones(x.shape[0], bool) if valid is None else np.asarray(valid)
    xz = jnp.where(jnp.asarray(real)[:, None], x, 0)
    idx, w = moe.route(xz, router, bias, kw["top_k"], kw["scoring"])
    idx, w = np.asarray(idx), np.asarray(w)
    x32 = np.asarray(xz, np.float32)
    wg, wu, wd = (np.asarray(a, np.float32) for a in (wg, wu, wd))
    out = np.zeros(x32.shape, np.float32)
    local = active = 0
    seen = set()
    for r in np.flatnonzero(real):
        for e, wt in zip(idx[r] - kw["first_expert"], w[r]):
            if not 0 <= e < wg.shape[0]:
                continue
            g, u = x32[r] @ wg[e], x32[r] @ wu[e]
            a = np.asarray(jnp.asarray(
                g / (1 + np.exp(-g)) * u).astype(x.dtype), np.float32)
            out[r] += wt * (a @ wd[e])
            local += 1
            if e not in seen:
                seen.add(e)
                active += 1
    return out, [int(real.sum()) * kw["top_k"], local, active]


CASES = {
    # f 384: three tiles of 128; 320: no tile divides it, one of 320
    "several f tiles": dict(f=384),
    "f no tile divides": dict(f=320),
    "no expert with rows": dict(nobody=True),
    "one expert with every row": dict(top_k=1, favour=(5,), n=40),
    "groups of one": dict(n=1, top_k=4),
    "a row tile exactly": dict(n=128, top_k=1, favour=(9,)),
    "a row tile plus one": dict(n=129, top_k=1, favour=(9,)),
    "two row tiles, groups astride": dict(n=64, top_k=4),
    # e_loc 4 of 32: m is 128 + 128; every row on the four: 4 n pairs
    "skewed past m": dict(n=96, e_loc=4, first_expert=8,
                          favour=(0, 1, 2, 3)),
    "more pairs than the kernel holds": dict(n=MAX_ROWS // 4 + 40),
    "stack index 0": dict(stack=(3, 0)),
    "stack index middle": dict(stack=(3, 1), f=384),
    "stack index last": dict(stack=(3, 2), e_loc=4, first_expert=28),
    "an expert-parallel slice": dict(e_loc=4, first_expert=12, n=64),
    "invalid rows hold NaN": dict(invalid=True, n=48),
    "bfloat16": dict(dtype=jnp.bfloat16, n=64, f=384),
}


@pytest.mark.parametrize("name", CASES)
def test_the_kernel_equals_the_plain_lines_and_the_oracle(name):
    case = CASES[name]
    dtype = case.get("dtype", jnp.float32)
    x, router, bias, ws, kw = layer(case, dtype)
    # (the CPU's ragged_dot multiplies every group and masks: the plain
    # lines get the other layers' weights as zeros)
    plain, stats_plain = moe.routed_experts(
        x, router, bias, *(jnp.nan_to_num(w) for w in ws), **kw)
    kernel, stats = moe.routed_experts(
        x, router, bias, *ws, **kw, interpret=True)
    want, want_stats = oracle(x, router, bias, ws, kw)
    # the three stats are what they were, whatever runs the experts
    assert [int(v) for v in stats] == want_stats
    assert [int(v) for v in stats_plain] == want_stats
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    scale = max(1.0, float(np.abs(want).max()))
    assert np.isfinite(np.asarray(kernel)).all()
    np.testing.assert_allclose(np.asarray(kernel), want,
                               rtol=tol, atol=tol * scale)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(plain),
                               rtol=tol, atol=tol * scale)
    if kw["valid"] is not None:
        assert not np.asarray(kernel)[~np.asarray(kw["valid"])].any()


def test_the_cases_reach_what_they_name():
    """The routing of the parametrised cases does what their names say
    (a case that stopped doing so would test nothing)."""
    def sizes(name):
        x, router, bias, ws, kw = layer(CASES[name])
        idx, _ = moe.route(x, router, bias, kw["top_k"], kw["scoring"])
        local = np.asarray(idx) - kw["first_expert"]
        e_loc = ws[0].shape[-3]
        return np.bincount(local[(local >= 0) & (local < e_loc)],
                           minlength=e_loc)

    assert sorted(sizes("one expert with every row"))[-2:] == [0, 40]
    assert sizes("groups of one").max() == 1
    assert sizes("a row tile exactly").max() == 128
    assert sizes("a row tile plus one").max() == 129
    assert sizes("skewed past m").tolist() == [96] * 4   # 384 > m = 256
    assert sizes("more pairs than the kernel holds").sum() > MAX_ROWS
    assert 0 < sizes("an expert-parallel slice").sum() < 64 * 4


def test_rows_that_are_nobodys_come_out_zero_and_skip_is_honoured():
    """`expert_ffn` alone: `skip` rows, the experts' rows back to back,
    then rows left over; an expert of the stack that has no rows is
    never read into a result (its weights are NaN)."""
    keys = jax.random.split(jax.random.key(3), 4)
    m, f = 64, 256
    xs = jax.random.normal(keys[0], (m, D), jnp.bfloat16)
    ws = [(0.1 * jax.random.normal(k, s)).astype(jnp.bfloat16)
          for k, s in zip(keys[1:], [(8, D, f), (8, D, f), (8, f, D)])]
    sizes = jnp.array([5, 0, 17, 0], jnp.int32)
    ws = [w.at[:4].set(jnp.nan).at[5].set(jnp.nan).at[7].set(jnp.nan)
          for w in ws]
    for skip in (0, 7):
        plain = expert_ffn(
            xs, *(jnp.nan_to_num(w) for w in ws), sizes, skip, 4)
        got = expert_ffn(xs, *ws, sizes, skip, 4, interpret=True)
        owned = (np.arange(m) >= skip) & (np.arange(m) < skip + 22)
        assert np.isfinite(np.asarray(got)).all()
        assert np.asarray(got)[owned].any(axis=1).all()
        assert not np.asarray(got)[~owned].any()
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain),
                                   rtol=2e-2, atol=2e-2)


def test_more_rows_than_the_kernel_holds_are_refused():
    xs = jnp.zeros((MAX_ROWS + 16, D), jnp.bfloat16)
    w = jnp.zeros((2, D, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="at most"):
        expert_ffn(xs, w, w, w.swapaxes(1, 2), jnp.array([1, 1]),
                   interpret=True)


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _routed_layer_jaxpr(monkeypatch, backend):
    """The jaxpr of a routed layer in a scanned stack, traced as on
    `backend` (the choice is `jax.default_backend()`'s, read at trace
    time)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    x, router, bias, ws, kw = layer(dict(stack=(3, 1), f=384))
    kw.pop("stack_index")

    def stack(x, router, bias, *ws):
        def body(h, c):
            y, _ = moe.routed_experts(
                h, router, bias, *ws, stack_index=c, **kw)
            return h + y, None
        return jax.lax.scan(body, x, jnp.arange(3))[0]

    return ws, _primitives(
        jax.make_jaxpr(stack)(x, router, bias, *ws).jaxpr, [])


def test_the_tpu_path_is_one_kernel_over_the_whole_stacks(monkeypatch):
    ws, eqns = _routed_layer_jaxpr(monkeypatch, "tpu")
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 1
    assert "ragged_dot" not in names and "ragged_dot_general" not in names
    call = eqns[names.index("pallas_call")]
    assert call.params["name"] == "expert_ffn"
    whole = {(w.shape[0] * w.shape[1], *w.shape[2:]) for w in ws}
    shapes = [v.aval.shape for v in call.invars]
    assert sum(s in whole for s in shapes) == 3
    # no slice of a weight stack anywhere: XLA would copy it
    for e in eqns:
        if e.primitive.name in ("dynamic_slice", "gather", "slice"):
            assert e.invars[0].aval.ndim < 3, e


def test_off_the_tpu_the_path_is_the_plain_one(monkeypatch):
    _, eqns = _routed_layer_jaxpr(monkeypatch, "cpu")
    names = [e.primitive.name for e in eqns]
    assert "pallas_call" not in names
    assert sum(n.startswith("ragged_dot") for n in names) == 3
