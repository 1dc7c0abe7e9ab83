"""The benchmark's one traffic generator and its arithmetic.

Two halves, both driven only by a traffic file's parameters:

* `build_plan(traffic, seed, seconds)` makes every request of a run from
  the seed BEFORE anything is sent: arrival instants, prompt texts,
  output lengths, session material. Every seed gets the SAME multiset of
  lengths and inter-arrival gaps (the quantile midpoints of the traffic
  file's distributions), in another order, so two seeds differ in order
  and text, never in the amount of work. Where the traffic file has
  `stratify_seconds`, the order is a stratified one (`stratified_order`):
  every stretch of that many seconds holds a systematic sample of the
  gaps and of the sizes, so no seed piles the long answers or the short
  gaps into one part of the window.
* `Driver(plan, ...)` sends the plan through the router from one asyncio
  thread, open loop (on the schedule, whether or not earlier requests
  have finished) or closed loop (each client sends its next request when
  its last one completed), and returns one record per request.

Times in a record run on `time.monotonic()`. An open-loop latency is
taken from the instant a request was DUE, not from when it was sent, so
a stall charges the requests that had to wait behind it; `late_ms` says
how late the generator itself ran.

The arithmetic on records (`percentile`, `ttft_ms`, `request_ms`,
`norm_latency_ms`, `tpot_ms`, `token_gaps_ms`, `tokens_in_window`) is
here too, so that no later PR can change how a number is made from the
records.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import time
from dataclasses import dataclass, field

# the byte tokenizer's chat template (engine/tokenizer.py ByteTokenizer.
# apply_chat_template), restated to count prompt tokens before sending:
# BOS + "<|role|>\n{content}\n" per message + "<|assistant|>\n"
_ASSISTANT_TAG = "<|assistant|>\n"


def message_tokens(role: str, content: str) -> int:
    return len(f"<|{role}|>\n") + len(content) + 1


def prompt_tokens(messages: list[dict]) -> int:
    """Tokens the engine will count for a chat prompt under the byte
    tokenizer (one per ASCII character; one per rendered output id)."""
    return 1 + sum(message_tokens(m["role"], m["content"])
                   for m in messages) + len(_ASSISTANT_TAG)


# -- distributions -----------------------------------------------------------
def _norm_ppf(p: float) -> float:
    return statistics.NormalDist().inv_cdf(p)


def quantile_midpoints(dist: dict, n: int) -> list[float]:
    """The n quantile midpoints ((i + 0.5) / n) of a traffic file's
    distribution: the same n values for every seed."""
    kind = dist["dist"]
    out = []
    for i in range(n):
        p = (i + 0.5) / n
        if kind == "fixed":
            v = float(dist["n"])
        elif kind == "uniform":
            v = dist["min"] + p * (dist["max"] - dist["min"])
        elif kind == "lognormal":
            v = dist["median"] * math.exp(dist["sigma"] * _norm_ppf(p))
        elif kind == "exponential":
            v = -math.log(1.0 - p) * dist["mean"]
        elif kind == "gamma":
            # shape k = 1/cv^2 by Wilson-Hilferty (cv 1 is exponential)
            k = 1.0 / (dist["cv"] ** 2)
            z = _norm_ppf(p)
            v = dist["mean"] * max(
                1e-9, (1 - 1 / (9 * k) + z / (3 * math.sqrt(k))) ** 3)
        else:
            raise ValueError(f"unknown distribution {kind!r}")
        if "min" in dist and kind != "uniform":
            v = max(v, dist["min"])
        if "max" in dist and kind != "uniform":
            v = min(v, dist["max"])
        out.append(v)
    return out


def stratified_order(vals: list, n_blocks: int,
                     rng: random.Random) -> list:
    """`vals` (sorted, as `quantile_midpoints` gives them) in a seeded
    order in which every block of len(vals) / n_blocks consecutive
    places holds a sample of the whole range: the values are dealt to
    the blocks in rows of n_blocks, forwards and backwards in turn (so
    that the blocks' sums come out alike), each block is shuffled, and
    the blocks are shuffled too. One block is a plain shuffle."""
    n_blocks = max(1, min(int(n_blocks), len(vals)))
    blocks: list[list] = [[] for _ in range(n_blocks)]
    for i, v in enumerate(vals):
        row, col = divmod(i, n_blocks)
        blocks[col if row % 2 == 0 else n_blocks - 1 - col].append(v)
    for block in blocks:
        rng.shuffle(block)
    rng.shuffle(blocks)
    return [v for block in blocks for v in block]


def shuffled_lengths(dist: dict, n: int, rng: random.Random,
                     n_blocks: int = 1) -> list[int]:
    vals = [int(round(v)) for v in quantile_midpoints(dist, n)]
    return stratified_order(vals, n_blocks, rng)


def words(n_chars: int, rng: random.Random) -> str:
    """Seeded random lower-case words, exactly n_chars characters (one
    token each under the byte tokenizer)."""
    out: list[str] = []
    size = 0
    while size < n_chars:
        w = "".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                    for _ in range(rng.randint(2, 9)))
        out.append(w)
        size += len(w) + 1
    return " ".join(out)[:n_chars].ljust(n_chars, "x")


# -- the plan ----------------------------------------------------------------
@dataclass
class Turn:
    """One request as planned. `due_s` is relative to the start of the
    timed schedule (None in a closed loop or in set-up)."""
    idx: int
    phase: str                 # "setup" | "warm" | "window"
    due_s: float | None
    user_text: str
    max_tokens: int
    pick: int                  # seeded tie-breaker for the session choice
    client: int | None = None  # closed loop: which client sends it


@dataclass
class Session:
    sid: int
    messages: list[dict]
    in_flight: bool = False
    turns: int = 0


@dataclass
class Plan:
    traffic: dict
    seed: int
    seconds: float
    warm_seconds: float
    sessions: list[Session] = field(default_factory=list)
    spares: list[Session] = field(default_factory=list)
    prefixes: list[str] = field(default_factory=list)
    setup_turns: list[Turn] = field(default_factory=list)
    turns: list[Turn] = field(default_factory=list)

    def fingerprint(self) -> list:
        """What two plans must share to be the same requests."""
        return [
            [(t.phase, t.due_s, t.user_text, t.max_tokens, t.client)
             for t in self.setup_turns + self.turns],
            [s.messages for s in self.sessions + self.spares],
            self.prefixes,
        ]


def _make_session(sid: int, traffic: dict, prefixes: list[str],
                  initial_tokens: int, rng: random.Random) -> Session:
    messages: list[dict] = []
    if prefixes:
        messages.append(
            {"role": "system", "content": prefixes[sid % len(prefixes)]})
    if initial_tokens > 0:
        # an imported conversation: one earlier exchange of that size
        half = max(8, initial_tokens // 2)
        messages.append({"role": "user", "content": words(half, rng)})
        messages.append(
            {"role": "assistant", "content": words(half, rng)})
    return Session(sid=sid, messages=messages)


def build_plan(traffic: dict, seed: int, seconds: float) -> Plan:
    """Every request of one run, from the seed alone."""
    rng = random.Random(seed)
    setup = traffic.get("setup", {})
    warm_s = float(setup.get("warm_seconds", 0))
    plan = Plan(traffic=traffic, seed=seed, seconds=float(seconds),
                warm_seconds=warm_s)
    hist = traffic.get("history", {})
    pool = int(traffic.get("session_pool", 0))
    prefixes = [
        words(int(traffic["shared_prefix_tokens"]), rng)
        for _ in range(int(traffic.get("prefix_variants", 0)))
    ]
    plan.prefixes = prefixes
    stratify_s = float(traffic.get("stratify_seconds", 0))

    def n_blocks(span: float) -> int:
        return int(round(span / stratify_s)) if stratify_s > 0 else 1

    if traffic["loop"] == "open":
        rate = float(traffic["rate_rps"])
        gap_dist = {"dist": "exponential", "mean": 1.0 / rate}
        if traffic.get("arrival") == "gamma":
            gap_dist = {"dist": "gamma", "mean": 1.0 / rate,
                        "cv": float(traffic["cv"])}

        def schedule(span: float, offset: float) -> list[float]:
            """round(rate x span) arrivals inside [offset, offset +
            span): the same gaps for every seed, in a seeded order,
            scaled so that the last one lands half a mean gap before
            the end."""
            n = int(round(rate * span))
            if n <= 0:
                return []
            gaps = stratified_order(
                quantile_midpoints(gap_dist, n), n_blocks(span), rng)
            scale = (span * (n - 0.5) / n) / sum(gaps)
            out, t = [], offset
            for g in gaps:
                t += g * scale
                out.append(t)
            return out

        # the warm phase and the window are scheduled apart, so that
        # every seed puts the same number of requests into the window
        dues = schedule(warm_s, 0.0) + schedule(seconds, warm_s)
        n_total = len(dues)
        clients = [None] * n_total
    else:
        n_clients = int(traffic["clients"])
        # enough for any window: a request is never shorter than its
        # smallest output at the fastest step anyone will build
        per_client = max(4, int(math.ceil((warm_s + seconds) / 2.0)))
        n_total = n_clients * per_client
        dues = [None] * n_total
        clients = [i % n_clients for i in range(n_total)]

    # sizes are dealt to the warm phase and to the window apart, so that
    # every seed's window holds the same multiset of sizes
    n_warm = sum(1 for d in dues if d is not None and d < warm_s)
    p_lens, o_lens = [], []
    for n, span in ((n_warm, warm_s), (n_total - n_warm, seconds)):
        blocks = n_blocks(span) if traffic["loop"] == "open" else 1
        p_lens += shuffled_lengths(traffic["prompt_tokens"], n, rng, blocks)
        o_lens += shuffled_lengths(traffic["output_tokens"], n, rng, blocks)
    for i in range(n_total):
        due = dues[i]
        phase = "window"
        if due is not None and due < warm_s:
            phase = "warm"
        plan.turns.append(Turn(
            idx=i, phase=phase, due_s=due,
            user_text=words(p_lens[i], rng), max_tokens=o_lens[i],
            pick=rng.randrange(1 << 30), client=clients[i],
        ))
    if traffic["loop"] == "closed" and setup.get("stagger_first"):
        # spread the clients over the phases of a request, so that the
        # window does not open on 48 requests in lockstep
        n_clients = int(traffic["clients"])
        for c in range(n_clients):
            first = plan.turns[c]
            first.max_tokens = max(
                8, int(first.max_tokens * (c + 0.5) / n_clients))

    if hist.get("enabled"):
        init = hist["initial_tokens"]
        n_spare = max(pool, len(plan.turns))
        # the pool and the spares are dealt apart, so that every seed's
        # pool starts with the same multiset of histories (the same
        # tokens of context to attend over), and every eight spares in
        # the order they are taken hold a sample of the whole range
        inits = shuffled_lengths(init, pool, rng) + shuffled_lengths(
            init, n_spare, rng, n_spare // 8)
        for sid in range(pool + n_spare):
            s = _make_session(sid, traffic, prefixes, inits[sid], rng)
            (plan.sessions if sid < pool else plan.spares).append(s)
        # turn 0 of every session goes out in set-up: it puts the
        # histories into the prefix cache, which is what a deployment
        # that has been up for an hour looks like
        t0_out = int(setup.get("turn0_output_tokens", 16))
        t0_lens = shuffled_lengths(traffic["prompt_tokens"], pool, rng)
        for sid in range(pool):
            plan.setup_turns.append(Turn(
                idx=-1 - sid, phase="setup", due_s=None,
                user_text=words(min(t0_lens[sid], 256), rng),
                max_tokens=t0_out, pick=sid,
            ))
    return plan


# -- records -----------------------------------------------------------------
@dataclass
class Record:
    idx: int
    phase: str
    due: float | None = None        # monotonic instants
    sent: float | None = None
    first: float | None = None      # first streamed token
    end: float | None = None
    events: list = field(default_factory=list)   # (instant, n_tokens)
    tokens: int = 0
    max_tokens: int = 0
    prompt_tokens: int | None = None
    usage_completion: int | None = None
    finish_reason: str | None = None
    done: bool = False              # saw [DONE]
    status: int | None = None
    error: str | None = None
    cut: bool = False               # cancelled by the harness at the end
    text: str = ""

    def ok(self) -> bool:
        """Well-formed: 200, [DONE], the tokens asked for, and the
        stream's own count equal to the engine's usage."""
        return (self.status == 200 and self.done and self.error is None
                and self.tokens == self.max_tokens
                and self.usage_completion == self.max_tokens
                and self.finish_reason == "length")

    def as_json(self) -> dict:
        return {k: getattr(self, k) for k in (
            "idx", "phase", "due", "sent", "first", "end", "tokens",
            "max_tokens", "prompt_tokens", "usage_completion",
            "finish_reason", "done", "status", "error", "cut")} | {
            "events": self.events}


def percentile(values: list[float], p: float) -> float | None:
    """p in [0, 100]; linear interpolation between closest ranks (the
    rule numpy's default uses). None for no values."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def supported_percentile(n: int) -> float:
    """The highest percentile a sample of n supports with ten samples
    beyond it (choosing-metrics section 1)."""
    return 0.0 if n <= 10 else 100.0 * (1.0 - 10.0 / n)


def ttft_ms(rec: Record) -> float | None:
    """Due instant -> first streamed token (sent instant where the
    request had no due time: closed loop)."""
    start = rec.due if rec.due is not None else rec.sent
    if rec.first is None or start is None:
        return None
    return (rec.first - start) * 1e3


def request_ms(rec: Record) -> float | None:
    """Due (or sent) instant -> last streamed token."""
    start = rec.due if rec.due is not None else rec.sent
    if not rec.events or start is None:
        return None
    return (rec.events[-1][0] - start) * 1e3


def norm_latency_ms(rec: Record) -> float | None:
    """Normalized latency of one request (Orca, vLLM): due (or sent)
    instant -> last streamed token, over the tokens it got. A short
    answer is mostly its wait for the first token, a long one mostly
    its time per token, and every request counts once."""
    total = request_ms(rec)
    if total is None or rec.tokens < 1:
        return None
    return total / rec.tokens


def tpot_ms(rec: Record) -> float | None:
    """Time per output token of one request: first streamed token ->
    last, over the tokens after the first."""
    if rec.tokens < 2 or rec.first is None:
        return None
    return (rec.events[-1][0] - rec.first) * 1e3 / (rec.tokens - 1)


def mean(values: list[float]) -> float | None:
    return sum(values) / len(values) if values else None


def token_gaps_ms(rec: Record) -> list[float]:
    """Gaps between consecutive streamed tokens of one request. Tokens
    that arrive in one event have a gap of zero; the first token of the
    stream has none (it is the TTFT)."""
    gaps: list[float] = []
    prev = None
    for t, n in rec.events:
        if n <= 0:
            continue
        if prev is not None:
            gaps.append((t - prev) * 1e3)
            gaps.extend([0.0] * (n - 1))
        else:
            gaps.extend([0.0] * (n - 1))
        prev = t
    return gaps


def tokens_in_window(records: list[Record], t0: float, t1: float) -> int:
    """Tokens whose stream events arrived in [t0, t1)."""
    return sum(n for r in records for t, n in r.events if t0 <= t < t1)


def late_ms(rec: Record) -> float | None:
    if rec.due is None or rec.sent is None:
        return None
    return (rec.sent - rec.due) * 1e3


# -- the driver ----------------------------------------------------------------
class Driver:
    """Sends a plan through one router port. One event loop, one thread."""

    def __init__(self, plan: Plan, port: int, model: str,
                 request_timeout_s: float = 600.0):
        self.plan = plan
        self.port = port
        self.model = model
        self.timeout_s = request_timeout_s
        self.records: list[Record] = []
        self._spare = 0
        self._retire_at = int(
            plan.traffic.get("history", {}).get(
                "retire_context_tokens", 1 << 30))

    # the session a turn lands on: a uniformly chosen one with nothing
    # in flight; one whose context would pass the retire length is
    # replaced by a fresh session first
    def _take_session(self, turn: Turn) -> Session | None:
        sessions = self.plan.sessions
        if not sessions:
            return None
        free = [s for s in sessions if not s.in_flight]
        if not free:
            return None
        s = free[turn.pick % len(free)]
        need = (prompt_tokens(s.messages)
                + message_tokens("user", turn.user_text) + turn.max_tokens)
        if need > self._retire_at and self.plan.spares:
            fresh = self.plan.spares[self._spare % len(self.plan.spares)]
            self._spare += 1
            fresh = Session(sid=fresh.sid, messages=list(fresh.messages))
            sessions[sessions.index(s)] = fresh
            s = fresh
        s.in_flight = True
        return s

    async def _send(self, http, turn: Turn, session: Session | None,
                    due: float | None) -> Record:
        rec = Record(idx=turn.idx, phase=turn.phase, due=due,
                     max_tokens=turn.max_tokens)
        self.records.append(rec)
        user = {"role": "user", "content": turn.user_text}
        if session is not None:
            messages = session.messages + [user]
        else:
            # no history: the shared prefix (a few-shot prompt) and the
            # request's own item
            pre = self.plan.prefixes
            messages = ([{"role": "system",
                          "content": pre[turn.pick % len(pre)]}]
                        if pre else []) + [user]
        body = {
            "model": self.model, "messages": messages,
            "max_tokens": turn.max_tokens, "temperature": 0,
            "ignore_eos": True, "stream": True,
            "stream_options": {"include_usage": True},
        }
        rec.prompt_tokens = prompt_tokens(messages)
        try:
            rec.sent = time.monotonic()
            async with http.post(
                f"http://127.0.0.1:{self.port}"
                + self.plan.traffic.get("endpoint", "/v1/chat/completions"),
                json=body,
            ) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (await resp.text())[:300]
                    return rec
                async for raw in resp.content:
                    now = time.monotonic()
                    line = raw.strip()
                    if not line.startswith(b"data:"):
                        continue
                    payload = line[5:].strip()
                    if payload == b"[DONE]":
                        rec.done = True
                        break
                    event = json.loads(payload)
                    if "error" in event:
                        rec.error = json.dumps(event["error"])[:300]
                        continue
                    for choice in event.get("choices", ()):
                        text = (choice.get("delta") or {}).get("content")
                        if text:
                            # the stand-in tokenizer renders every id as
                            # ONE character, so an event's length is
                            # its token count (checked against usage)
                            n = len(text)
                            rec.text += text
                            rec.tokens += n
                            rec.events.append((now, n))
                            if rec.first is None:
                                rec.first = now
                        if choice.get("finish_reason"):
                            rec.finish_reason = choice["finish_reason"]
                    if event.get("usage"):
                        rec.usage_completion = (
                            event["usage"]["completion_tokens"])
                        if (event["usage"].get("prompt_tokens")
                                != rec.prompt_tokens):
                            rec.error = (
                                "prompt_tokens "
                                f"{event['usage'].get('prompt_tokens')} != "
                                f"planned {rec.prompt_tokens}")
        except asyncio.CancelledError:
            rec.cut = True
            raise
        except Exception as e:  # noqa: BLE001 - a failed request is a result
            rec.error = repr(e)[:300]
        finally:
            rec.end = time.monotonic()
            if session is not None:
                session.in_flight = False
                if rec.ok():
                    session.messages = messages + [
                        {"role": "assistant", "content": rec.text}]
                    session.turns += 1
        return rec

    def _client(self):
        import aiohttp

        return aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=self.timeout_s),
            connector=aiohttp.TCPConnector(limit=0),
        )

    async def run_setup(self, concurrency: int = 32) -> list[Record]:
        """Each shared prefix alone, one after the other (so that the
        only prefill that starts at token 0 runs by itself, through a
        fixed set of programs), then turn 0 of every session,
        `concurrency` at a time."""
        before = len(self.records)
        sem = asyncio.Semaphore(concurrency)
        async with self._client() as http:
            for i, pre in enumerate(self.plan.prefixes):
                prime = Session(sid=-1, messages=[
                    {"role": "system", "content": pre}])
                await self._send(http, Turn(
                    idx=-1000 - i, phase="setup", due_s=None,
                    user_text="hello", max_tokens=1, pick=i), prime, None)
            async def one(turn: Turn, session: Session):
                async with sem:
                    session.in_flight = True
                    await self._send(http, turn, session, None)
            await asyncio.gather(*(
                one(t, self.plan.sessions[t.pick])
                for t in self.plan.setup_turns))
        return self.records[before:]

    async def run_timed(self, on_window_start=None,
                        on_window_end=None) -> tuple[float, float]:
        """The warm phase and then the window, back to back with no
        pause between them. Returns the window's (t0, t1) on
        `time.monotonic()`. `on_window_start` / `on_window_end` are
        awaited at those instants (metrics scrapes, the trace)."""
        plan = self.plan
        start = time.monotonic() + 0.2
        t0 = start + plan.warm_seconds
        t1 = t0 + plan.seconds
        tasks: list[asyncio.Task] = []
        hooks: list[asyncio.Task] = []

        async def at(instant: float, hook):
            await asyncio.sleep(max(0.0, instant - time.monotonic()))
            if hook is not None:
                await hook()

        hooks.append(asyncio.create_task(at(t0, on_window_start)))
        hooks.append(asyncio.create_task(at(t1, on_window_end)))
        async with self._client() as http:
            if plan.traffic["loop"] == "open":
                for turn in plan.turns:
                    due = start + turn.due_s
                    delay = due - time.monotonic()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    session = self._take_session(turn)
                    if plan.sessions and session is None:
                        # every session busy: the arrival is dropped
                        # and counted as failed
                        rec = Record(idx=turn.idx, phase=turn.phase,
                                     due=due, max_tokens=turn.max_tokens,
                                     error="no free session")
                        self.records.append(rec)
                        continue
                    tasks.append(asyncio.create_task(
                        self._send(http, turn, session, due)))
                drain = float(plan.traffic.get("drain_seconds", 30))
                if tasks:
                    await asyncio.wait(
                        tasks, timeout=max(0.0, t1 + drain
                                           - time.monotonic()))
            else:
                async def client(c: int):
                    mine = [t for t in plan.turns if t.client == c]
                    for turn in mine:
                        if time.monotonic() >= t1:
                            return
                        turn.phase = ("warm" if time.monotonic() < t0
                                      else "window")
                        await self._send(http, turn, None, None)
                tasks = [asyncio.create_task(client(c))
                         for c in range(int(plan.traffic["clients"]))]
                await asyncio.sleep(max(0.0, t1 - time.monotonic()))
            for t in tasks:
                if not t.done():
                    t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)
        await asyncio.gather(*hooks)
        return t0, t1
