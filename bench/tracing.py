"""From a profiler trace to the numbers the per-layer metrics read.

``collect`` reads the ``.xplane.pb`` the JAX profiler writes and keeps three
lists of ``(name, start_ns, dur_ns)`` events: the device's programs (line
"XLA Modules"), its operations (line "XLA Ops") and the benchmark's own host
spans (``bench.*`` ``TraceAnnotation``s).  Everything below works on those
lists alone, so events made by hand check it in a test.

Busy time is the union over every chip's operations: right for the
one-chip cells of this benchmark.
"""

from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]  # (name, start_ns, dur_ns)

HOST_PREFIX = "bench."
_SUFFIX = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r"(?<![\w.])([a-z][a-z0-9_-]*)\(")


def program_name(name: str) -> str:
    """``jit_window(123)`` -> ``jit_window``."""

    return _SUFFIX.sub("", name)


def op_name(text: str, marks: Sequence[str] = ()) -> str:
    """A device operation's short name from its HLO text: the instruction
    and its opcode (``fusion.12 fusion``), and any of ``marks`` the text
    holds (the kernel's name, so that it is still found)."""

    if " = " not in text:
        return text
    lhs, rhs = text.split(" = ", 1)
    m = _OPCODE.search(rhs)
    out = lhs.lstrip("%") + (" " + m.group(1) if m else "")
    return " ".join([out] + [k for k in marks if k in text])


def collect(trace_dir: str, marks: Sequence[str] = ()) -> Dict[str, object]:
    """Device programs and operations and host spans of the newest trace;
    operations by ``op_name`` with ``marks``."""

    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    modules: List[Event] = []
    ops: List[Event] = []
    host: List[Event] = []
    chips = 0
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            chips += 1
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(program_name(e.name), int(e.start_ns), int(e.duration_ns))
                                for e in line.events]
                elif line.name == "XLA Ops":
                    ops += [(op_name(e.name, marks), int(e.start_ns), int(e.duration_ns))
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.duration_ns))
                         for e in line.events if e.name.startswith(HOST_PREFIX)]
    key = lambda e: e[1]
    return {"chips": chips, "modules": sorted(modules, key=key),
            "ops": sorted(ops, key=key), "host": sorted(host, key=key)}


def window_of(host: Sequence[Event], span: str = HOST_PREFIX + "window") -> Tuple[int, int]:
    """The traced window: the benchmark's ``bench.window`` host span."""

    w = [e for e in host if e[0] == span]
    if not w:
        raise ValueError(f"no {span} span in the trace")
    return w[0][1], w[0][1] + w[0][2]


def clip(events: Iterable[Event], t0: int, t1: int) -> List[Event]:
    """Events cut to ``[t0, t1)``; those wholly outside are dropped."""

    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[int, int]]:
    """Merged ``[start, end)`` intervals covered by ``events``."""

    merged: List[List[int]] = []
    for _, s, d in sorted(events, key=lambda e: e[1]):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(a, b) for a, b in merged]


def busy_ns(events: Iterable[Event]) -> int:
    return sum(b - a for a, b in union(events))


def durations(events: Iterable[Event], names: Sequence[str]) -> List[int]:
    """Durations of the events whose name is one of ``names``."""

    keep = set(names)
    return [d for n, _, d in events if n in keep]


def matching(events: Iterable[Event], pattern: str) -> List[Event]:
    """Events whose name matches the regular expression ``pattern``."""

    rx = re.compile(pattern)
    return [e for e in events if rx.search(e[0])]


def window_groups(modules: Sequence[Event], names: Sequence[str]) -> List[Tuple[int, int]]:
    """``(start, end)`` of each scan window's programs.

    Each window boundary dispatches each decode program at most once, so a
    program seen again opens the next window.
    """

    groups: List[List[int]] = []
    seen: set = set()
    keep = set(names)
    for n, s, d in modules:
        if n not in keep:
            continue
        if n in seen or not groups:
            groups.append([s, s + d])
            seen = set()
        seen.add(n)
        groups[-1][1] = max(groups[-1][1], s + d)
    return [(a, b) for a, b in groups]


def gaps_between(groups: Sequence[Tuple[int, int]]) -> List[int]:
    """Idle time from the end of one window to the start of the next."""

    return [max(b[0] - a[1], 0) for a, b in zip(groups, groups[1:])]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The operations that hold no other: a loop's event spans the
    operations of its body, which the trace also lists."""

    ops = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= e[1] + e[2]]


def top_ops(ops: Iterable[Event], n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` device operations that took most time, in seconds."""

    tot: Dict[str, int] = {}
    for name, _, d in leaves(list(ops)):
        tot[name] = tot.get(name, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(ops: Iterable[Event], host: Sequence[Event], t0: int, t1: int,
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the device in ``[t0, t1)``, each named
    by the benchmark host span that overlaps it most."""

    busy = union(ops)
    gaps, prev = [], t0
    for a, b in busy:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    if t1 > prev:
        gaps.append((prev, t1))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    out = []
    for a, b in gaps:
        best, over = "none", 0
        for name, s, d in host:
            if name == HOST_PREFIX + "window":
                continue
            o = min(b, s + d) - max(a, s)
            if o > over:
                best, over = name[len(HOST_PREFIX):], o
        out.append([best, (b - a) * 1e-9])
    return out
