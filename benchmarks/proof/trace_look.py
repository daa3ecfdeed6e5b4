"""Look at one trace by hand: planes, lines, what the operations are
called and which stats they carry.  ``--cut N`` also writes the first N
milliseconds of device operations (and the host annotations over them)
as the compact JSON the self-checks read.

    python benchmarks/proof/trace_look.py <file.xplane.pb> [--cut 200 out.json.gz]
"""

from __future__ import annotations

import collections
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import tracered  # noqa: E402


def main(argv):
    import jax

    path = argv[0]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            total = collections.Counter()
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            print(f"  LINE {line.name!r}: {len(events)} events")
            if not plane.name.startswith("/device:") and not line.name.startswith("python"):
                keep = [n for n in total if n.startswith(tracered.HOST_PREFIXES)]
                for n in keep[:20]:
                    print(f"      {n}: {count[n]} x, {total[n] / 1e6:.3f} ms")
                continue
            for name, ns in total.most_common(40):
                print(f"      {ns / 1e6:10.3f} ms {count[name]:6d} x  {name[:120]}")
            shown = 0
            for e in events:
                if "custom" in e.name or "kernel" in e.name:
                    print("      STATS", e.name, {k: (v if not isinstance(v, str) else v[:300])
                                                  for k, v in e.stats})
                    shown += 1
                    if shown >= 6:
                        break
    if "--cut" in argv:
        i = argv.index("--cut")
        ms, out = float(argv[i + 1]), argv[i + 2]
        events = tracered.load_events(path)
        cut = {"devices": {}, "host": []}
        t0 = min(o[1] for ops in events["devices"].values() for o in ops)
        t1 = t0 + int(ms * 1e6)
        for plane, ops in events["devices"].items():
            cut["devices"][plane] = [[n, s - t0, d, m] for n, s, d, m in ops if s < t1]
        cut["host"] = [[n, s - t0, d] for n, s, d in events["host"] if t0 <= s < t1]
        with gzip.open(out, "wt") as f:
            json.dump(cut, f)
        print("cut", out, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1:])
