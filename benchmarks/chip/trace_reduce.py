"""From a profiler trace to the numbers the benchmark reports.

Input is anything shaped like `jax.profiler.ProfileData`: `.planes`,
each with `.name` and `.lines`; each line with `.name` and `.events`;
each event with `.name`, `.start_ns` and `.duration_ns`. The tests hand
in a hand-built object of that shape; `load` reads a real `.xplane.pb`.

What is a device: a plane whose name starts with `/device:TPU:` (see
DEVICE_PLANE_PREFIXES). On it, the line `XLA Ops` holds one event per
operation the chip ran and `XLA Modules` one per program execution.

* busy: the UNION of the `XLA Ops` intervals of a chip (nested or
  overlapping events count once), per chip, then the mean over chips;
* window: first event start to last event end over all planes, host
  planes included, so that an idle chip at either end still counts;
* idle share = 1 - busy / window;
* ops: summed duration and count by operation, over all chips divided by
  the number of chips. The trace names an operation by its whole HLO
  text; the key kept here is its name and result shape
  (`fusion.548 bf16[32,4096]`), with the first 400 characters of the
  text beside it for readers to match on. `while`, `conditional` and
  `call` operations WRAP other operations of the same line, so they are
  kept apart (`wrapper: true`) and left out of every sum of leaves;
* device_ops: the ten leaves with the most time;
* idle gaps: every maximal interval in which chip 0 ran nothing, named
  by the innermost host event that covers its midpoint
  (`unattributed` where no host event does), summed by name.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE_PREFIXES = ("/device:TPU:",)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10
_MODULE_ID = re.compile(r"\(\d+\)$")
_OP = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
TEXT_KEPT = 400


def op_key(text: str) -> str:
    """`%fusion.5 = bf16[32,4096]{...} fusion(...)` -> `fusion.5
    bf16[32,4096]`; a name that is no HLO text is kept as it is."""
    m = _OP.match(text)
    if not m:
        return text[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def is_wrapper(text: str) -> bool:
    head = text[:4000]
    return bool(re.search(r"[\)\}] (while|conditional|call)\(", head))


def load(trace_dir: str):
    """The newest `.xplane.pb` under a `jax.profiler` log directory."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(paths[-1])


def _intervals(line) -> list[tuple[int, int, str]]:
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def module_name(name: str) -> str:
    """`jit_step(123456)` -> `jit_step`: the id changes per compile."""
    return _MODULE_ID.sub("", name)


def reduce(profile) -> dict:
    devices = []
    host_events: list[tuple[int, int, str]] = []
    lo, hi = None, None
    for plane in profile.planes:
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIXES)
        lines = {}
        for line in plane.lines:
            ivs = _intervals(line)
            if ivs:
                s0 = min(s for s, _, _ in ivs)
                e1 = max(e for _, e, _ in ivs)
                lo = s0 if lo is None else min(lo, s0)
                hi = e1 if hi is None else max(hi, e1)
            if is_dev:
                lines[line.name] = ivs
            elif plane.name.startswith("/host:"):
                host_events.extend(ivs)
        if is_dev:
            devices.append((plane.name, lines))
    devices.sort(key=lambda d: d[0])
    if not devices or lo is None:
        return {"chips": 0, "window_s": 0.0, "busy_s": 0.0,
                "busy_s_per_chip": [], "device_ops": [], "idle_gaps": [],
                "ops": {}, "modules": {}}
    n = len(devices)
    window_ns = hi - lo
    busy, ops_by_key, modules = [], {}, {}
    first_busy_union = None
    for _, lines in devices:
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = union([(s, e) for s, e, _ in ops])
        if first_busy_union is None:
            first_busy_union = merged
        busy.append(sum(e - s for s, e in merged))
        for s, e, name in ops:
            key = op_key(name)
            o = ops_by_key.get(key)
            if o is None:
                o = ops_by_key[key] = {
                    "s": 0.0, "n": 0.0, "wrapper": is_wrapper(name),
                    "text": name[:TEXT_KEPT]}
            o["s"] += (e - s) / 1e9 / n
            o["n"] += 1.0 / n
        for s, e, name in lines.get(MODULES_LINE, []):
            m = modules.setdefault(
                module_name(name), {"count": 0, "total_s": 0.0})
            m["count"] += 1
            m["total_s"] += (e - s) / 1e9
    for m in modules.values():          # mean over chips
        m["count"] /= n
        m["total_s"] /= n
    # idle gaps of chip 0, named by what the host was doing
    gaps: dict[str, int] = {}
    host_events.sort()
    starts = [s for s, _, _ in host_events]
    edges = [lo] + [x for iv in first_busy_union for x in iv] + [hi]
    for i in range(0, len(edges), 2):
        g0, g1 = edges[i], edges[i + 1]
        if g1 <= g0:
            continue
        mid = (g0 + g1) // 2
        name, best = "unattributed", None
        j = bisect.bisect_right(starts, mid)
        # innermost = the shortest host event covering the midpoint
        for s, e, nm in host_events[max(0, j - 256):j]:
            if s <= mid < e and (best is None or e - s < best):
                name, best = nm, e - s
        gaps[name] = gaps.get(name, 0) + (g1 - g0)
    leaves = {k: o["s"] for k, o in ops_by_key.items() if not o["wrapper"]}
    top = lambda d: [  # noqa: E731
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "chips": n,
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "busy_s_per_chip": [b / 1e9 for b in busy],
        "device_ops": top(leaves),
        "idle_gaps": top({k: v / 1e9 for k, v in gaps.items()}),
        "ops": ops_by_key,
        "modules": modules,
    }
