"""From a profiler trace to numbers: device busy union, idle share,
kernel time by name, the operations that took most time, and the idle
gaps by what the host was doing in them.

``load_events`` turns an ``.xplane.pb`` into plain lists (what the small
recorded fixture under ``tests/data`` holds); ``reduce_events`` is pure
arithmetic on those lists, so the same code reads the chip's trace and
the fixture."""

from __future__ import annotations

import bisect
import glob
import os
import re

#: the device plane's line that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: host annotations worth keeping (the program's and the benchmark's)
HOST_PREFIXES = ("serve/", "trainer/", "bench/", "train/")
#: a cap on what one trace may keep of them
MAX_HOST_EVENTS = 200_000


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def compact(text: str) -> tuple[str, str]:
    """A device operation's trace name is its whole HLO line.  Returns the
    short name (``fusion.20``) and a one-line tag: ``pallas`` for a Mosaic
    kernel (``tpu_custom_call``), the operation kind, and the result's
    shape without layouts."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text.lstrip("%")[:120], ""
    name = head.lstrip("%")
    shape_part, _, tail = rest.partition(") ") if rest.startswith("(") else rest.partition(" ")
    if rest.startswith("("):
        shape_part += ")"
    shape = re.sub(r"\{[^}]*\}", "", shape_part)[:100]
    kind = tail.split("(", 1)[0].strip()[:24]
    tag = "pallas " if 'custom_call_target="tpu_custom_call"' in rest else ""
    return name, f"{tag}{kind} {shape}"


def base_name(name: str) -> str:
    """``tdx_flash_forward.38`` -> ``tdx_flash_forward``."""
    return re.sub(r"[.\d]+$", "", name)


def load_events(xplane_path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, dur_ns, meta], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name, tag = compact(e.name)
                    ops.append([name, int(e.start_ns), int(e.duration_ns), tag])
            devices[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIXES) and len(host) < MAX_HOST_EVENTS:
                        host.append([e.name, int(e.start_ns), int(e.duration_ns)])
    return {"devices": devices, "host": host}


def union_intervals(intervals):
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def label_of(name: str, tag: str) -> str:
    """What the breakdown calls an operation: its name without the
    number, its kind and its result's shape, so that the same operation
    of every layer adds up under one label (``pallas:`` marks a Mosaic
    kernel)."""
    if tag.startswith("pallas"):
        return "pallas:" + base_name(name)
    base = base_name(name)
    kind, _, shape = tag.partition(" ")
    return (f"{base} {shape}" if kind in base else f"{base} {tag}").strip()


def self_times(ops):
    """Per label, the time no nested operation covers: an operation that
    wraps others (a loop, a call) keeps only its own."""
    by_name = {}
    stack = []  # (end, name, child_time)
    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, start, child = stack.pop()
            own = max(0, (end - start) - child)
            by_name[name] = by_name.get(name, 0) + own
            if stack:
                stack[-1][3] += end - start
    for name, start, dur, tag in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(start)
        stack.append([start + dur, label_of(name, tag), start, 0])
    close(float("inf"))
    return by_name


def kernel_seconds(ops, match) -> tuple[float, int]:
    """Summed device time and count of the operations ``match(name,
    meta)`` accepts."""
    total, n = 0, 0
    for name, _start, dur, meta in ops:
        if match(name, meta):
            total += dur
            n += 1
    return total / 1e9, n


def reduce_events(events: dict, window_s: float, chips: int) -> dict:
    """Busy seconds (averaged over the chips used), the traced window,
    the idle share of the fullest-loaded device's complement, and the
    breakdown.  ``window_s`` is the host's clock over the traced part;
    where the device's own events span more, that span is the window."""
    planes = sorted(events["devices"])[:chips]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy, merged_of = {}, {}
    span = 0.0
    for p in planes:
        merged = union_intervals(
            [(s, s + d) for _n, s, d, _m in events["devices"][p] if d > 0])
        merged_of[p] = merged
        busy[p] = sum(e - s for s, e in merged) / 1e9
        if merged:
            span = max(span, (merged[-1][1] - merged[0][0]) / 1e9)
    window = max(window_s, span)
    fullest = max(planes, key=lambda p: busy[p])
    ops, merged = events["devices"][fullest], merged_of[fullest]
    top = sorted(self_times(ops).items(), key=lambda kv: -kv[1])[:10]
    gaps = [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    by_label = {}
    host = sorted(events["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]
    for s, e in gaps:
        mid = (s + e) // 2
        label = "host (no annotation)"
        i = bisect.bisect_right(starts, mid)
        for name, hs, hd in reversed(host[max(0, i - 8):i]):
            if hs <= mid < hs + hd:
                label = name
                break
        by_label[label] = by_label.get(label, 0) + (e - s)
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:10]
    mean_busy = sum(busy.values()) / len(planes)
    return {
        "busy_s": mean_busy,
        "window_s": window,
        "idle_pct": 100.0 * (1.0 - busy[fullest] / window),
        "device": fullest,
        "ops": ops,
        "breakdown": {
            "device_ops": [[n, t / 1e9] for n, t in top],
            "idle_gaps": [[n, t / 1e9] for n, t in idle],
        },
    }


def reduce_dir(trace_dir: str, window_s: float, chips: int) -> dict:
    return reduce_events(load_events(find_xplane(trace_dir)), window_s, chips)
