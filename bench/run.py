"""Run one benchmark cell once, on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration, traffic mix and metric readers are found
by name in ``BENCHMARK.json``.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiler
trace of the window.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``:
each number compared for ``correct`` beside its limit, which also close
standard error.

Without a TPU, with fewer chips than the cell asks for, or on a chip
whose kind has no published peaks, it exits non-zero and prints no
result.  ``--control 1`` also reports the int8 control's gap (for
setting the limit; not used by the benchmark's runs).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".jax_cache")
TRACES = os.path.join(ROOT, "bench_traces")


def fail(msg: str) -> None:
    print(f"bench: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def enable_cache(jax) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache/`` at the checkout root; every
    program is kept, however quickly it compiled."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    import harness
    import peaks

    cell = harness.resolve(args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"no TPU: JAX sees {devices[0].platform} devices")
    if len(devices) < cell.chips:
        fail(f"{args.workload} needs {cell.chips} chips, have "
             f"{len(devices)}")
    try:
        chip_peaks = peaks.peak(devices[0].device_kind)
    except KeyError as e:
        fail(str(e))
    print(f"bench: {args.workload} seed={args.seed} seconds={args.seconds} "
          f"trace={args.trace} jax={jax.__version__} "
          f"kind={devices[0].device_kind} cache={enable_cache(jax)}",
          file=sys.stderr)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(TRACES, args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, peaks=chip_peaks, t_start=T_START,
        trace_dir=trace_dir, control=bool(args.control),
        log=lambda s: print(s, file=sys.stderr))
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    d = devices[0]
    result["device"] = dict(platform=d.platform, kind=d.device_kind,
                            count=cell.chips, **result["device"])
    result["checks"] = result.pop("checks")
    for name, chk in result["checks"].items():
        print(f"check {name} value={chk['value']} limit={chk['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
