"""From a profiler trace (``.xplane.pb``) to busy and idle time, the device
operations that took most time, collective time, and the idle gaps named
by what the host was doing.

The window is the host event ``chipbench.traced``, which the harness opens
right after the profiler starts and closes right before it stops.  A
device is a plane ``/device:TPU:<i>``; its operations are the events of
its ``XLA Ops`` line.  An event's name is the HLO instruction's text;
an operation is named by its instruction name and, for a fusion, its
kind (``fusion.4 kCustom``).  Operations nest (a
``while`` holds its body's operations), so busy time is the union of the
intervals inside the window, and each operation's time in ``top_ops`` is
its self time: its duration less that of the operations it holds.
Collective time is the self time of operations whose opcode or
instruction name is a collective.  Each idle gap of the first device is
named by the shortest host event that covers its midpoint (the innermost
thing the host was doing), ``(host idle)`` where none does.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

WINDOW_EVENT = "chipbench.traced"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"all-reduce|collective-permute|all-gather|reduce-scatter|all-to-all")
INSTR = re.compile(r"^%?([^\s=]+) = ")
OPCODE = re.compile(r"\b([a-z][a-z0-9-]*)\(")
KIND = re.compile(r"kind=(k\w+)")
GAPS_NAMED = 2000        # longest idle gaps that get a host name


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: Path, n_devices: int) -> dict | None:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(str(find_xplane(trace_dir))),
                  n_devices)


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (start, end) rows into disjoint sorted intervals."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def op_name(text: str) -> tuple[str, bool]:
    """(short name, is a collective) of one ``XLA Ops`` event's text."""
    m = INSTR.match(text)
    if m is None:
        return text[:80], bool(COLLECTIVE.search(text[:80]))
    name = m.group(1)
    rest = text[m.end():]
    op = OPCODE.search(rest)
    opcode = op.group(1) if op else ""
    kind = KIND.search(rest) if opcode == "fusion" else None
    short = f"{name} {kind.group(1)}" if kind else name
    return short, bool(COLLECTIVE.search(name) or COLLECTIVE.search(opcode))


def _self_times(ops: list) -> list:
    """(name, start, end, self seconds) of nested events on one line: each
    event's duration less that of the events directly inside it."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    out, stack = [], []
    for name, s, e in ops:
        while stack and stack[-1][2] <= s:
            out.append(stack.pop())
        rec = [name, s, e, e - s]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        stack.append(rec)
    out.extend(stack)
    return [(n, s, e, max(t, 0.0) * 1e-9) for n, s, e, t in out]


def reduce(pd, n_devices: int) -> dict | None:
    """Reduce a ``jax.profiler.ProfileData``; None where the trace holds no
    window event or no device operation in the window."""
    host = [pl for pl in pd.planes if pl.name == HOST_PLANE]
    host_ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for pl in host for ln in pl.lines for e in ln.events]
    win = [(s, e) for name, s, e in host_ev if name == WINDOW_EVENT]
    if not win:
        return None
    lo, hi = win[0]
    devices = {}
    for pl in pd.planes:
        m = DEVICE_PLANE.match(pl.name)
        if m is None or int(m.group(1)) >= n_devices:
            continue
        devices[int(m.group(1))] = [
            (e.name, e.start_ns, e.start_ns + e.duration_ns)
            for ln in pl.lines if ln.name == OPS_LINE for e in ln.events
            if e.start_ns < hi and e.start_ns + e.duration_ns > lo]
    if not any(devices.values()):
        return None
    busy, coll, per_op = [], [], {}
    unions = {}
    for d, ops in sorted(devices.items()):
        iv = np.asarray([(max(s, lo), min(e, hi)) for _, s, e in ops],
                        np.float64).reshape(-1, 2)
        u = _union(iv)
        unions[d] = u
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        c = 0.0
        for text, s, e, t in _self_times(ops):
            name, is_coll = op_name(text)
            per_op[name] = per_op.get(name, 0.0) + t
            c += t if is_coll else 0.0
        coll.append(c)
    k = len(devices)
    top = sorted(((n, t / k) for n, t in per_op.items()),
                 key=lambda p: -p[1])
    first = unions[min(unions)]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / k,
        "busy_s_per_device": busy,
        "collective_s": sum(coll) / k,
        "devices": k,
        "top_ops": [[n, t] for n, t in top],
        "idle_gaps": _name_gaps(first, lo, hi, host_ev),
    }


def _name_gaps(u: np.ndarray, lo: float, hi: float, host_ev: list) -> list:
    starts = np.concatenate([[lo], u[:, 1]])
    ends = np.concatenate([u[:, 0], [hi]])
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    order = np.argsort(starts - ends)[:GAPS_NAMED]      # longest first
    ev = [(n, s, e) for n, s, e in host_ev if n != WINDOW_EVENT and e > s]
    names = np.asarray([n for n, _, _ in ev], object)
    es = np.asarray([s for _, s, _ in ev], np.float64)
    ee = np.asarray([e for _, _, e in ev], np.float64)
    total: dict[str, float] = {}
    for g in order:
        mid = 0.5 * (starts[g] + ends[g])
        cover = np.nonzero((es <= mid) & (ee >= mid))[0]
        name = ("(host idle)" if cover.size == 0
                else str(names[cover[np.argmin(ee[cover] - es[cover])]]))
        total[name] = total.get(name, 0.0) + (ends[g] - starts[g]) * 1e-9
    return [[n, t] for n, t in sorted(total.items(), key=lambda p: -p[1])]
