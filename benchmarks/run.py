"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process each time.  It builds the program from the seed, warms up
every shape the cell's traffic uses (all of that is ``setup_s``),
measures for ``--seconds``, then frees the program and checks what the
timed path produced against the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (``breakdown`` when traced), and the
numbers compared beside their limits under ``compared``, last.

It fails (exit code 2, no result) when JAX finds no TPU or fewer chips
than the cell asks for.  ``--rehearsal`` is the one exception: it takes
nothing but the tiny files under ``benchmarks/rehearsal/``, names the
CPU in ``device`` and prints counts, never a time, a rate or a share.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()  # the process's start, as near as Python gives it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from harness import check, compiles, loader, peaks, tracered  # noqa: E402


class Context:
    """What a driver and the metric readers share in one run."""

    def __init__(self, cell, seed: int, seconds: float, trace: bool):
        self.cell, self.seed, self.seconds, self.trace = cell, seed, seconds, trace
        self.spans = {}       # set-up phases, seconds each
        self.counters = {}    # what the driver counted in the window
        self.compiles = compiles.CompileCounter()
        self.setup_s = None
        self.window_t0 = None
        self.trace_dir = None
        self.trace_t0 = self.trace_t1 = None
        self.trace_seconds = float(cell.traffic.get("trace_seconds", 4.0))
        self.reduction = None
        self.keep_trace = None
        self.device_kind = None
        self.chips = cell.chips

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.spans[name] = self.spans.get(name, 0.0) + time.monotonic() - t0

    def family(self, *needs: str):
        """The configuration's family: everything that depends on the
        architecture (``constructor``, ``reference``, ``counts``);
        ``needs`` as in ``loader.load_family``.  A driver asks once."""
        return loader.load_family(self.cell.config["family"], needs)

    def log(self, record: dict) -> None:
        print(json.dumps(record), file=sys.stderr, flush=True)

    def window_opened(self, t0: float) -> None:
        self.window_t0 = t0
        self.setup_s = t0 - _T_START
        self.setup_mark = self.compiles.mark()

    def tick(self, now: float) -> None:
        """Called by a driver inside its window loop: the traced run
        starts its trace for the window's last ``trace_seconds``."""
        if (self.trace and self.trace_dir is None and self.window_t0 is not None
                and now >= self.window_t0 + self.seconds - self.trace_seconds):
            import jax

            self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.trace_t0 = time.monotonic()

    def stop_trace(self) -> None:
        if self.trace_dir is None:
            return
        import jax

        self.trace_t1 = time.monotonic()
        jax.profiler.stop_trace()
        try:
            if self.keep_trace:
                os.makedirs(self.keep_trace, exist_ok=True)
                shutil.copy(tracered.find_xplane(self.trace_dir), self.keep_trace)
            self.reduction = tracered.reduce_dir(
                self.trace_dir, window_s=self.trace_t1 - self.trace_t0,
                chips=self.chips)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def configure_cache() -> None:
    """The persistent compilation cache where the program keeps it (inside
    the checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says), and small
    programs too: every program is in the cache after the first run."""
    import jax

    from torchdistx_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def make_driver(cell, seed: int, seconds: float, trace: bool = False):
    """The run's context and the cell's driver (also what ``proof/`` uses)."""
    import jax

    ctx = Context(cell, seed, seconds, trace)
    ctx.device_kind = jax.devices()[0].device_kind
    return ctx, loader.load_driver(cell.driver_kind).Driver(ctx)


def fail(message: str, code: int = 2) -> int:
    print(f"benchmarks/run.py: {message}", file=sys.stderr)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's .xplane.pb here (to look at "
                    "by hand, or to cut a fixture from)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny rehearsal files only, counts only")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "torchdistx_tpu")):
        return fail("the program (torchdistx_tpu/) is not in this checkout")
    sys.path.insert(0, REPO)
    try:
        cell = loader.load_cell(args.workload, rehearsal=args.rehearsal)
    except loader.BenchmarkError as e:
        return fail(str(e))

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearsal:
        if platform == "tpu":
            return fail("--rehearsal is for the CPU")
    elif platform != "tpu":
        return fail(f"no TPU (jax.devices()[0].platform == {platform!r}); "
                    "nothing was run")
    if len(devices) < cell.chips:
        return fail(f"cell {cell.name} needs {cell.chips} chips, JAX finds "
                    f"{len(devices)}")
    if not args.rehearsal:
        peaks.peaks(devices[0].device_kind)  # an unlisted kind is an error

    configure_cache()
    ctx, driver = make_driver(cell, args.seed, args.seconds, bool(args.trace))
    # interpreter start, imports of jax and the program, the runtime's
    # first touch of the chip
    ctx.spans["start_imports_devices"] = time.monotonic() - _T_START
    ctx.keep_trace = args.keep_trace
    driver.setup()
    out = driver.window(args.seconds)
    ctx.stop_trace()
    window_compiles = ctx.compiles.total - ctx.setup_mark[0]
    used = devices[: cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    if hasattr(driver, "after_window"):
        driver.after_window()
    driver.free()

    verdict = check.Verdict()
    verdict.add("compiles_in_window", window_compiles, 0)
    verdict.add("failed", out["failed"], 0)
    driver.check(verdict)

    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    e2e = dict(out["end_to_end"], setup_s=ctx.setup_s)
    ctx.counters.update({
        "setup.compile_s": ctx.setup_mark[1], "setup.compiles": ctx.setup_mark[0],
        "setup.cache_hits": ctx.setup_mark[2], "setup.cache_misses": ctx.setup_mark[3],
    })
    metrics = {}
    if args.rehearsal:
        pass  # counts only, below: never a time, a rate or a share
    elif args.trace:
        for m in cell.per_layer:
            value = m.reader(ctx)
            if value is not None:
                metrics[m.name] = {"value": float(value), "unit": m.unit}
        red = ctx.reduction
        device["busy_s"], device["window_s"] = red["busy_s"], red["window_s"]
    else:
        for m in cell.end_to_end:
            metrics[m.name] = {"value": float(e2e[m.name]), "unit": m.unit}
    result = {"correct": verdict.correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if args.trace and not args.rehearsal and ctx.reduction is not None:
        result["breakdown"] = ctx.reduction["breakdown"]
    if args.rehearsal:
        result["counts"] = {k: v for k, v in ctx.counters.items()
                            if isinstance(v, int) and not isinstance(v, bool)}
    else:
        result["setup_split_s"] = {k: round(v, 3) for k, v in ctx.spans.items()}
        if out.get("look"):   # where the window's time went: shown, never compared
            result["window_look"] = out["look"]
    result["read_not_compared"] = {n: v for n, v, _ in verdict.read}
    result["compared"] = verdict.as_dict()
    sys.stdout.flush()
    for line in verdict.lines():
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
