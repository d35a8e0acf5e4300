"""Run one benchmark cell on the chip and print its result as one JSON line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace 0|1

Everything is looked up by name in ``BENCHMARK.json``: the cell's
configuration file and traffic mix, and one reader per metric under
``bench/metrics``.  With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler trace
of part of the window.  The numbers compared to decide ``correct`` are printed
beside their limits as the last lines of standard error and under ``checks``,
the last key of the result.

Without an accelerator, or with fewer chips than the cell asks for, this exits
with status 2 and prints no result.  JAX's persistent compilation cache lives
in ``$JAX_COMPILATION_CACHE_DIR`` if that is set, else in ``.jax_cache`` at the
root of the checkout.

For measuring the correctness limits: ``--seeds a,b,...`` runs each seed in
turn in this one process, and ``--control`` puts the lower-precision control
in the program's place: ``correct`` then judges the control's picks (it has to
come out false), and ``program_mean_logit_gap`` gives the program's own reading
of the same run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds, run in turn in one process")
    ap.add_argument("--control", action="store_true",
                    help="judge the lower-precision control in the "
                         "program's place")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace into this directory")
    args = ap.parse_args()

    from benchlib import spec
    cell = spec.load_cell(args.workload)

    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} accelerator chip(s); "
              f"JAX sees {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        sys.exit(2)

    from benchlib import harness
    seeds = [int(s) for s in args.seeds.split(",")] if args.seeds \
        else [args.seed]
    for seed in seeds:
        out = harness.run(cell, seed, args.seconds, bool(args.trace),
                          devs[:cell.chips], T_START, control=args.control,
                          keep_trace=args.keep_trace)
        for name, c in out["checks"].items():
            print(f"check {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
