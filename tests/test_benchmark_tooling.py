"""Benchmark tooling tests: ShareGPT workload mode, sweep table, plot
(reference: benchmarks/multi-round-qa/{plot.py,prepare_sharegpt_data.sh}
and run.sh sweep loop — round-1 verdict item 8)."""

import importlib.util
import json
import os
import subprocess
import sys

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..",
                         "benchmarks", "multi-round-qa")


def load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH_DIR, f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses resolves annotations via this
    sys.path.insert(0, BENCH_DIR)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path.pop(0)
    return mod


def make_sharegpt(tmp_path, n=4):
    out = tmp_path / "sharegpt.json"
    subprocess.run(
        ["bash", os.path.join(BENCH_DIR, "prepare_sharegpt_data.sh"),
         "--synthetic", str(out), str(n)],
        check=True, capture_output=True,
    )
    return out


def test_synthetic_sharegpt_loads(tmp_path):
    mqa = load("multi_round_qa")
    path = make_sharegpt(tmp_path, n=5)
    convs = mqa.load_sharegpt(str(path))
    assert len(convs) == 5
    for conv in convs:
        assert conv[0]["role"] == "user"
        roles = {m["role"] for m in conv}
        assert roles <= {"user", "assistant"}


def test_sharegpt_session_builds_real_turns(tmp_path):
    mqa = load("multi_round_qa")
    path = make_sharegpt(tmp_path)
    convs = mqa.load_sharegpt(str(path))
    args = mqa.parse_args(
        ["--model", "m", "--sharegpt-path", str(path)]
    )
    sess = mqa.UserSession(0, args)
    sess.sharegpt_conv = convs[0]
    msgs = sess.build_messages()
    assert msgs[0]["role"] == "system"
    assert msgs[-1]["role"] == "user"
    assert msgs[-1]["content"] == convs[0][0]["content"]


def test_sharegpt_normalizes_messy_dump(tmp_path):
    mqa = load("multi_round_qa")
    path = tmp_path / "messy.json"
    path.write_text(json.dumps([
        {"conversations": [
            {"from": "gpt", "value": "leading assistant dropped"},
            {"from": "human", "value": "q1"},
            {"from": "human", "value": "q1b"},  # merged into q1
            {"from": "gpt", "value": "a1"},
        ]},
        {"conversations": [{"from": "human", "value": "only one"}]},
    ]))
    convs = mqa.load_sharegpt(str(path))
    assert len(convs) == 1
    assert convs[0][0] == {"role": "user", "content": "q1\nq1b"}
    assert convs[0][1] == {"role": "assistant", "content": "a1"}


def test_sweep_table_format():
    sweep = load("sweep")
    rows = [
        (1.0, {"qps": 0.98, "requests_completed": 50, "errors": 0,
               "prompt_throughput_tok_s": 1000.0,
               "generation_throughput_tok_s": 99.0,
               "avg_ttft_s": 0.5, "p50_ttft_s": 0.4, "p99_ttft_s": 1.2,
               "p50_itl_s": 0.02, "p99_itl_s": 0.09}),
        (2.0, {"qps": 1.9}),  # sparse row: missing keys render as "-"
    ]
    table = sweep.to_table(rows)
    lines = table.splitlines()
    assert lines[0].startswith("| offered QPS |")
    assert "| 1.0 | 0.98 | 50 | 0 |" in lines[2]
    assert lines[3].count("-") >= 9


def test_plot_writes_png(tmp_path):
    for qps in (1, 2):
        (tmp_path / f"summary_qps{qps}.json").write_text(json.dumps({
            "qps": qps * 0.9, "p50_ttft_s": 0.1 * qps,
            "generation_throughput_tok_s": 100.0 * qps,
            "p50_itl_s": 0.01 * qps,
        }))
    plot = load("plot")
    out = tmp_path / "sweep.png"
    plot.main([str(tmp_path / "summary_qps1.json"),
               str(tmp_path / "summary_qps2.json"), "-o", str(out)])
    assert out.exists() and out.stat().st_size > 1000


def test_itl_percentiles_in_summary():
    mqa = load("multi_round_qa")
    args = mqa.parse_args(["--model", "m"])
    b = mqa.Benchmark(args)
    r = mqa.RequestRecord(start=0.0, first_token=0.1, end=1.0, ok=True)
    r.itls = [0.01, 0.02, 0.03]
    r.prompt_tokens, r.completion_tokens = 10, 4
    b.records.append(r)
    s = b.summary(elapsed=1.0, launched=1)
    assert s["p50_itl_s"] == 0.02
    assert s["p99_itl_s"] == 0.03


def test_ramp_up_staggers_admission():
    """--ramp-up-time: users enter the free queue staggered over the
    window, not as a thundering herd at t=0 (reference ramp-up,
    multi-round-qa.py:386)."""
    import asyncio
    import time

    mqa = load("multi_round_qa")
    args = mqa.parse_args([
        "--model", "m", "--num-users", "4", "--ramp-up-time", "0.4",
    ])
    b = mqa.Benchmark(args)

    async def scenario():
        t0 = time.time()
        await b._admit_sessions(t0)
        return time.time() - t0

    took = asyncio.new_event_loop().run_until_complete(scenario())
    assert b.free_sessions.qsize() == 4
    assert took >= 0.25  # staggered, not instantaneous

    # ramp 0 = all admitted immediately
    args0 = mqa.parse_args(["--model", "m", "--num-users", "4"])
    b0 = mqa.Benchmark(args0)

    async def scenario0():
        t0 = time.time()
        await b0._admit_sessions(t0)
        return time.time() - t0

    took0 = asyncio.new_event_loop().run_until_complete(scenario0())
    assert b0.free_sessions.qsize() == 4 and took0 < 0.1


def test_recycle_holds_concurrency(tmp_path):
    """--recycle: a finished user is replaced by a FRESH session with a
    new id so concurrency stays constant (reference session recycling,
    multi-round-qa.py:407)."""
    import asyncio

    mqa = load("multi_round_qa")
    args = mqa.parse_args([
        "--model", "m", "--num-users", "2", "--num-rounds", "1",
        "--recycle",
    ])
    b = mqa.Benchmark(args)
    sess = b.sessions[0]
    sess.rounds_done = 1  # finished its rounds

    class _FakeHTTP:
        def post(self, *a, **kw):
            raise RuntimeError("no network in this test")

    async def scenario():
        # run_request errors out (fake http), but the finally-block
        # bookkeeping must still recycle the finished session
        import contextlib

        with contextlib.suppress(RuntimeError):
            await b.run_request(sess, _FakeHTTP())

    asyncio.new_event_loop().run_until_complete(scenario())
    assert b.sessions_completed == 1
    assert b.free_sessions.qsize() == 1  # concurrency held
    fresh = b.free_sessions.get_nowait()
    assert fresh.user_id == 2  # new identity, fresh history
    assert fresh.history == [] and fresh.rounds_done == 0
    # finished sessions are NOT retained: their chat history would
    # otherwise accumulate for the whole run
    assert len(b.sessions) == 2
