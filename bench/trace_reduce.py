"""Reduction of a profiler trace to device busy time, idle gaps, kernel
time by name and attribution of kernels to the program that ran them.

A trace is first brought to a plain form (``from_xplane``)::

    {"devices": [{"ops": [[name, start_ns, dur_ns], ...],
                  "modules": [[name, start_ns, dur_ns], ...]}, ...],
     "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's XLA operations by short HLO name (a Pallas kernel
is one op, named after the jitted function that calls it: ``bdmm.3``,
``paged_attention.6``; a scan over layers is one ``while`` op that holds
the ops of its body), ``modules`` the compiled programs they ran in,
``host`` the spans of the thread that drives the engine. Everything below
works on that form, so ``bench/tests`` checks it on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Interval = Tuple[int, int]


def _events(line) -> List[list]:
    return [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
            for ev in line.events]


def op_name(text: str) -> str:
    """A device op's short name: the HLO instruction name at the head of
    the event's text (``%paged_attention.6 = bf16[...] custom-call(...)``
    gives ``paged_attention.6``). A Pallas kernel's instruction is named
    after the jitted function that calls it."""
    m = re.match(r"%?([^\s=]+)\s*=", text)
    return m.group(1) if m else text


# ops that hold other ops (a scan over layers is one ``while`` op whose body
# ops are traced too): they count toward busy time, not toward op totals
CONTAINERS = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def from_xplane(path: str, host_names) -> Dict:
    """Plain form of the ``.xplane.pb`` under ``path`` (a file or the
    profiler's output directory), keeping the host events named in
    ``host_names`` (the harness's own spans)."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = sorted(found)[-1]
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = ([[op_name(ev.name), int(ev.start_ns), int(ev.duration_ns)]
                    for ev in lines["XLA Ops"].events]
                   if "XLA Ops" in lines else [])
            mods = (_events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
            devices.append({"plane": plane.name, "ops": ops,
                            "modules": mods})
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [ev for ev in _events(ln) if ev[0] in host_names]
    return {"devices": devices, "host": host}


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(start: int, dur: int, lo: int, hi: int) -> Optional[Interval]:
    s, e = max(start, lo), min(start + dur, hi)
    return (s, e) if e > s else None


def reduce(trace: Dict, lo: int, hi: int) -> Dict:
    """Numbers of the slice ``[lo, hi)`` (ns): per device busy union, idle
    gaps, kernel time by op name, and each program execution that lies
    wholly inside the slice with the ops it holds."""
    devs = []
    for dev in trace["devices"]:
        clipped = [(n, iv) for n, s, d in dev["ops"]
                   if (iv := _clip(s, d, lo, hi)) is not None]
        busy = union([iv for _, iv in clipped])
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s - t))
            t = e
        if hi > t:
            gaps.append((t, hi - t))
        by_name: Dict[str, int] = {}
        for n, (s, e) in clipped:
            if not CONTAINERS.match(n):
                by_name[n] = by_name.get(n, 0) + (e - s)
        execs = []
        ops_sorted = sorted(dev["ops"], key=lambda o: o[1])
        for name, s, d in dev["modules"]:
            if s < lo or s + d > hi:
                continue
            inside = [(n, od) for n, os_, od in ops_sorted
                      if os_ >= s and os_ + od <= s + d
                      and not CONTAINERS.match(n)]
            execs.append({"name": name, "start": s, "dur": d, "ops": inside})
        devs.append({"busy_ns": sum(e - s for s, e in busy), "gaps": gaps,
                     "op_ns": by_name, "execs": execs})
    return {"lo": lo, "hi": hi, "devices": devs, "host": trace["host"]}


def busy_s(red: Dict) -> float:
    """Device busy seconds, averaged over the devices traced."""
    devs = red["devices"]
    return sum(d["busy_ns"] for d in devs) / len(devs) / 1e9 if devs else 0.0


def window_s(red: Dict) -> float:
    return (red["hi"] - red["lo"]) / 1e9


def kernel_ns(red: Dict, pattern: str) -> int:
    """Summed device time of ops whose name matches ``pattern`` (regex)."""
    rx = re.compile(pattern)
    return sum(ns for d in red["devices"] for n, ns in d["op_ns"].items()
               if rx.search(n))


def executions(red: Dict, pattern: str) -> List[Dict]:
    rx = re.compile(pattern)
    return [e for d in red["devices"] for e in d["execs"]
            if rx.search(e["name"])]


def host_label(host: List[list], t: int) -> str:
    """The innermost host span covering time ``t``, or ``none``."""
    best = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "none"


def breakdown(red: Dict, top: int = 10) -> Dict:
    """The device ops that took most time and the longest idle gaps, each
    gap named by what the host was doing at its middle (seconds)."""
    totals: Dict[str, int] = {}
    for d in red["devices"]:
        for n, ns in d["op_ns"].items():
            key = re.sub(r"[.\d]+$", "", n)
            totals[key] = totals.get(key, 0) + ns
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted((g for d in red["devices"] for g in d["gaps"]),
                  key=lambda g: -g[1])[:top]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [[host_label(red["host"], s + d // 2), d / 1e9]
                          for s, d in gaps]}
