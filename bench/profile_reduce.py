"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and returns
the device's operation events and the benchmark's own host spans, each as
``(name, start_ns, duration_ns)`` on the profiler's one clock. The other
functions are pure arithmetic over such events, so the tests can check
them on a handful of hand-written ones.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
DEVICE_OP_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
# A TPU op event is named by its HLO text: "%hop_adc.6 = f32[...] custom-call(...)"
_HLO = re.compile(r"%?([^\s=]+) = .*? ([a-z][\w-]*)\(")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(xplane_path: str):
    """(device op events per device plane, host spans, plane summary)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    devices, host, summary = {}, [], []
    for plane in pd.planes:
        lines = list(plane.lines)
        summary.append((plane.name, [ln.name for ln in lines]))
        on_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for ln in lines:
            events = [(ev.name, ev.start_ns, ev.duration_ns)
                      for ev in ln.events]
            if on_device and ln.name.startswith(DEVICE_OP_LINE):
                devices.setdefault(plane.name, []).extend(events)
            host.extend(e for e in events
                        if e[0].startswith(HOST_SPAN_PREFIX))
    return devices, host, summary


def clip(events, t0: float, t1: float):
    """Events overlapping [t0, t1), cut to it."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events):
    """Merged (start, end) intervals covered by the events."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    merged = []
    for a, b in iv:
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events) -> float:
    return float(sum(b - a for a, b in union(events)))


def op_name(event_name: str) -> str:
    """"hop_adc.6 custom-call" for an HLO-text event name; other names as
    they are, cut to 80 characters."""
    m = _HLO.match(event_name)
    return f"{m[1]} {m[2]}" if m else event_name[:80]


def op_base(event_name: str) -> str:
    """The HLO instruction's name without its ".N" suffix ("hop_adc")."""
    m = _HLO.match(event_name)
    name = m[1] if m else event_name
    return re.sub(r"\.\d+$", "", name)


def top_ops(events, n: int = 10):
    """[[op, seconds], ...]: the n operations with most summed time."""
    tot = {}
    for name, _, d in events:
        key = op_name(name)
        tot[key] = tot.get(key, 0.0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def idle_gaps(events, host, t0: float, t1: float, n: int = 10):
    """[[host activity, seconds], ...]: the n longest stretches of
    [t0, t1) in which no device operation ran, each named by the host span
    that overlaps it most ("untraced host work" where none does)."""
    gaps, cur = [], t0
    for a, b in union(events):
        if a > cur:
            gaps.append((cur, min(a, t1)))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    gaps = sorted((g for g in gaps if g[1] > g[0]),
                  key=lambda g: -(g[1] - g[0]))[:n]
    out = []
    for a, b in gaps:
        best, best_ov = "untraced host work", 0.0
        for name, s, d in host:
            ov = min(b, s + d) - max(a, s)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append([best, (b - a) / 1e9])
    return out


def matching(events, names):
    """Events whose HLO instruction is named one of ``names`` (a Pallas
    kernel's call is named after its jitted entry, "hop_adc.6")."""
    return [ev for ev in events if op_base(ev[0]) in names]
