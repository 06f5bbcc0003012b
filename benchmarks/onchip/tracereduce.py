"""From the JAX profiler's trace to the numbers the per-layer metrics
read: device busy time (the union of operation intervals), idle share,
all-to-all time and the part of it no other operation overlaps, the
operations that took most time, and the longest idle gaps named by the
host span they fall in.

``load`` turns an ``.xplane.pb`` into plain records; ``reduce`` works on
those records alone, so it is tested on a small recorded trace.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

HOST_SPANS = ("upload", "step", "metrics", "bucket")
COLLECTIVE = re.compile(r"^(all-to-all|all-reduce|all-gather|reduce-scatter"
                        r"|collective-permute)")
Interval = Tuple[int, int]


def op_label(text: str) -> str:
    """``"%fusion.58 = bf16[8,1024]{1,0} fusion(...)"`` ->
    ``"fusion.58 bf16[8,1024]"``: the HLO instruction name and the head
    of its result type (the trace names an operation by its whole HLO
    text, operands included)."""
    m = re.match(r"%?([\w.\-]+) = (\(?[a-z0-9]+\[[0-9,]*\])?", text)
    if not m:
        return text[:60]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def is_collective(label: str) -> bool:
    return bool(COLLECTIVE.match(label))


def mark_leaves(events: List[list]) -> List[list]:
    """``[start, end, label]`` -> ``[start, end, label, leaf]``, where an
    event that encloses another (a while loop around its body) is not a
    leaf."""
    out = sorted(([int(s), int(e), n, True] for s, e, n in events),
                 key=lambda x: (x[0], -x[1]))
    stack: List[list] = []
    for ev in out:
        while stack and stack[-1][1] <= ev[0]:
            stack.pop()
        if stack and ev[1] <= stack[-1][1]:
            stack[-1][3] = False
        stack.append(ev)
    return out


def load(log_dir: Path) -> dict:
    """Records from the newest ``.xplane.pb`` under ``log_dir``:
    ``{"devices": {id: [[start_ns, end_ns, label, leaf], ...]},
    "host": [[start_ns, end_ns, name], ...]}``. Device operations are the
    events of each TPU plane's "XLA Ops" line, and the collectives of its
    "Async XLA Ops" line; host spans are the benchmark's annotations."""
    import jax
    files = sorted(Path(log_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = jax.profiler.ProfileData.from_file(str(files[-1]))
    devices: Dict[int, list] = {}
    host: list = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name not in ("XLA Ops", "Async XLA Ops"):
                    continue
                for ev in line.events:
                    label = op_label(ev.name)
                    if line.name == "XLA Ops" or is_collective(label):
                        ops.append([ev.start_ns, ev.start_ns + ev.duration_ns,
                                    label])
            devices[int(m.group(1))] = mark_leaves(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [[ev.start_ns, ev.start_ns + ev.duration_ns, ev.name]
                         for ev in line.events if ev.name in HOST_SPANS]
    return {"devices": devices, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> int:
    """Length of ``a`` not covered by ``b`` (both merged)."""
    covered = 0
    j = 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return length(a) - covered


def reduce(rec: dict, window: Tuple[int, int], top: int = 10) -> dict:
    """Numbers of the traced window ``[start_ns, end_ns)``: per device
    (by integer id) busy seconds (union of all operations), all-to-all
    seconds and the part of them that no other leaf operation overlaps;
    the window's length; the ``top`` leaf operations by device time (mean
    seconds per device) and the ``top`` longest idle gaps of the first
    device, named by the host span they fall in."""
    lo, hi = window
    busy, a2a, exposed = {}, {}, {}
    op_time: Dict[str, float] = defaultdict(float)
    keys = sorted(rec["devices"], key=int)
    ids = [int(k) for k in keys]
    for key, dev in zip(keys, ids):
        ops = [(int(ev[0]), int(ev[1]), ev[2], ev[3])
               for ev in rec["devices"][key]
               if min(int(ev[1]), hi) > max(int(ev[0]), lo)]
        all_iv = union(clip([(s, e) for s, e, _, _ in ops], lo, hi))
        busy[dev] = length(all_iv) / 1e9
        coll = union(clip([(s, e) for s, e, n, _ in ops
                           if n.startswith("all-to-all")], lo, hi))
        other = union(clip([(s, e) for s, e, n, leaf in ops
                            if leaf and not is_collective(n)], lo, hi))
        a2a[dev] = length(coll) / 1e9
        exposed[dev] = subtract(coll, other) / 1e9
        for s, e, n, leaf in ops:
            if leaf:
                op_time[n] += (min(e, hi) - max(s, lo)) / 1e9 / len(ids)
    gaps = []
    if ids:
        iv = union(clip([(int(ev[0]), int(ev[1]))
                         for ev in rec["devices"][keys[0]]], lo, hi))
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        host = [(int(s), int(e), n) for s, e, n in rec["host"]]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            where: Dict[str, int] = defaultdict(int)
            for s, e, n in host:
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    where[n] += ov
            name = max(where, key=where.get) if where else "no span"
            gaps.append([name, (g1 - g0) / 1e9])
        gaps.sort(key=lambda g: -g[1])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy, "a2a_s": a2a,
            "a2a_exposed_s": exposed,
            "device_ops": [[n, t] for n, t in ops_top],
            "idle_gaps": gaps[:top]}


def window_of(rec: dict) -> Tuple[int, int]:
    """The traced window: from the first host span's start to the last
    host span's end."""
    spans = rec["host"]
    if not spans:
        raise ValueError("trace holds none of the benchmark's host spans")
    return (min(int(s) for s, _, _ in spans), max(int(e) for _, e, _ in spans))
