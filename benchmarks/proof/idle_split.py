"""Whose host time the chip's idle gaps are, split and not voted.

``harness.tracered`` puts a whole idle gap under the one host span that
holds its midpoint.  Between two decode steps the device waits through
several phases in a row (``serve/harvest``, the caller, ``serve/schedule``,
``serve/decode_args``, the dispatch inside ``serve/decode``): one gap, so
one label takes all of it.  This cuts every gap at the borders of the
host spans that overlap it and puts each piece under the innermost span
that holds it.  The profile's host and device clocks are out by a
millisecond or two, so read the result beside ``step_host_time.py``.

``innermost`` repeats the look-back of ``tracered.reduce_events`` (which
has it inline): a copy that stands only until a ``benchmark`` issue moves
the split into the reduction (PERF.md §7).

    python benchmarks/proof/idle_split.py <file.xplane.pb>
"""

from __future__ import annotations

import bisect
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import tracered  # noqa: E402

NO_ANNOTATION = "host (no annotation)"


def split_gaps(events: dict) -> dict:
    """``{"window_s", "busy_s", "gaps", "idle_s": {label: seconds}}`` for
    the first device plane of ``events``."""
    plane = sorted(events["devices"])[0]
    merged = tracered.union_intervals(
        [(s, s + d) for _n, s, d, _m in events["devices"][plane] if d > 0])
    host = sorted(events["host"], key=lambda h: h[1])
    starts = [h[1] for h in host]

    def innermost(t):
        i = bisect.bisect_right(starts, t)
        for name, hs, hd in reversed(host[max(0, i - 8):i]):
            if hs <= t < hs + hd:
                return name
        return NO_ANNOTATION

    split = {}
    for (_s0, s), (e, _e1) in zip(merged, merged[1:]):
        lo = bisect.bisect_left(starts, s - 10**9)   # spans up to 1 s long
        cuts = {s, e}
        for _name, hs, hd in host[lo:bisect.bisect_right(starts, e)]:
            cuts.update(t for t in (hs, hs + hd) if s < t < e)
        cuts = sorted(cuts)
        for a, b in zip(cuts, cuts[1:]):
            label = innermost((a + b) // 2)
            split[label] = split.get(label, 0) + (b - a)
    window = (merged[-1][1] - merged[0][0]) / 1e9
    return {
        "window_s": window,
        "busy_s": sum(e - s for s, e in merged) / 1e9,
        "gaps": len(merged) - 1,
        "idle_s": {k: v / 1e9 for k, v in sorted(split.items(), key=lambda kv: -kv[1])},
    }


def main(argv):
    out = split_gaps(tracered.load_events(argv[0]))
    out["idle_pct"] = {k: 100.0 * v / out["window_s"] for k, v in out["idle_s"].items()}
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main(sys.argv[1:])
