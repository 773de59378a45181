"""From a profiler trace to device metrics, in two steps.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps a
compact record: per device the intervals of its programs (the "XLA Modules"
line; the per-operation line holds some 180,000 events a second at these
sizes and is not read), and the host's ``span:`` annotations
(``bench/obs.py``).  ``reduce`` turns that
record into the busy time, idle share, time per program and idle gaps named
by what the host was doing; it is plain Python over the record, so a test
checks it on a small recorded one.

Times are nanoseconds on the profiler's clock, which the host annotations and
the device events share.
"""

from __future__ import annotations

import bisect
import collections
import glob
import re

from bench.obs import SPAN_PREFIX

WINDOW = SPAN_PREFIX + "bench.window"
_RUN_ID = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """``jit_verify(17)`` -> ``jit_verify``."""
    return _RUN_ID.sub("", module)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> dict:
    """The compact record of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    planes = list(data.planes)
    for plane in planes:
        if plane.name.startswith("/device:"):
            mods = [[program_name(e.name), e.start_ns, e.duration_ns]
                    for line in plane.lines if line.name == "XLA Modules" for e in line.events]
            if mods:
                devices[plane.name] = {"modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host.append([e.name[len(SPAN_PREFIX):], e.start_ns, e.duration_ns])
    if not devices:
        # a CPU backend runs XLA programs on host threads: collect them as
        # the device of a CPU run
        for plane in planes:
            if plane.name == "/host:CPU":
                cpu = [[program_name(mod), e.start_ns, e.duration_ns]
                       for line in plane.lines for e in line.events
                       if (mod := _hlo_module(e)) is not None]
                if cpu:
                    devices["/host:CPU"] = {"modules": cpu}
    for dev in devices.values():
        dev["modules"].sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    win = [e for e in host if e[0] == "bench.window"]
    t0, t1 = (win[0][1], win[0][1] + win[0][2]) if win else (None, None)
    return {"devices": devices, "host": host, "t0": t0, "t1": t1}


def _hlo_module(event):
    for k, v in event.stats:
        if k == "hlo_module":
            return str(v)
    return None


def _union(intervals, t0, t1):
    """Merged [start, end) intervals clipped to [t0, t1], from (start, dur)."""
    out = []
    for s, d in sorted(intervals):
        s, e = max(s, t0), min(s + d, t1)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(dev: dict, t0, t1):
    return _union([(e[1], e[2]) for e in dev["modules"]], t0, t1)


def _innermost(host, starts, t, reach: int = 256):
    """Name of the innermost host span that covers time ``t`` (or "none"):
    spans of one thread nest, so it is the covering span that began last."""
    i = bisect.bisect_right(starts, t)
    for j in range(i - 1, max(-1, i - 1 - reach), -1):
        name, s, d = host[j]
        if s + d > t and name != "bench.window":
            return name
    return "none"


def reduce(rec: dict, device: str | None = None, top: int = 10) -> dict:
    """Busy seconds, idle share, seconds and calls per program, the programs
    that took most time and the idle time by host activity, on ``device``
    (default: the one that ran ``jit_verify``, else the first)."""
    devs = rec["devices"]
    if not devs:
        return {}
    if device is None:
        device = next((k for k, v in sorted(devs.items())
                       if any(m[0] == "jit_verify" for m in v["modules"])), sorted(devs)[0])
    dev = devs[device]
    t0, t1 = rec["t0"], rec["t1"]
    if t0 is None:
        evs = dev["modules"]
        t0, t1 = evs[0][1], max(e[1] + e[2] for e in evs)
    window = t1 - t0
    busy = busy_intervals(dev, t0, t1)
    busy_ns = sum(e - s for s, e in busy)
    per_prog: dict = collections.defaultdict(lambda: [0, 0])
    for name, s, d in dev["modules"]:
        if t0 <= s < t1:
            per_prog[name][0] += d
            per_prog[name][1] += 1
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    if busy:
        gaps = [(t0, busy[0][0])] + gaps + [(busy[-1][1], t1)]
    host = rec["host"]
    by_host: dict = collections.defaultdict(int)
    starts = [h[1] for h in host]
    for s, e in gaps:
        if e <= s:
            continue
        by_host[_innermost(host, starts, (s + e) / 2)] += e - s
    progs = {k: {"s": v[0] / 1e9, "calls": v[1]} for k, v in per_prog.items()}
    return {
        "device": device,
        "window_s": window / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / window if window > 0 else None,
        "programs": progs,
        "device_ops": sorted(([k, v["s"]] for k, v in progs.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / 1e9] for k, v in by_host.items()), key=lambda kv: -kv[1])[:top],
    }


def busy_by_device(rec: dict) -> dict:
    """Busy seconds inside the window of each device that ran a program in
    it (a plane whose programs all lie outside the window is no chip the
    window used)."""
    if rec["t0"] is None:
        return {}
    t0, t1 = rec["t0"], rec["t1"]
    busy = {k: sum(e - s for s, e in busy_intervals(d, t0, t1)) / 1e9
            for k, d in sorted(rec["devices"].items())}
    return {k: v for k, v in busy.items() if v > 0}


def mean_busy(rec: dict) -> float:
    """Busy seconds inside the window, averaged over the devices it used."""
    busy = busy_by_device(rec)
    return sum(busy.values()) / len(busy) if busy else 0.0
