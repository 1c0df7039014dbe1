"""Benchmark driver: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick]

Emits ``name,us_per_call,derived`` CSV rows (also collected in
``benchmarks.common.ROWS``).  Every entry runs even when one fails; the
exit status is non-zero when any entry errored.
"""
import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer training steps (CI mode)")
    args, _ = ap.parse_known_args()
    steps = 60 if args.quick else 200

    from . import (beyond_formats, fig1_expdist, fig2_underflow, fig4_tiling,
                   fig7_energy, kernel_bench, roofline, table1_mse,
                   table2_directcast, table3_training)
    from .common import emit

    t0 = time.time()
    failed = []
    for name, fn in [
        ("table1_mse", lambda: table1_mse.run(steps=min(steps, 120))),
        ("fig1_expdist", lambda: fig1_expdist.run(steps=min(steps, 120))),
        ("table2_directcast", lambda: table2_directcast.run(steps=steps)),
        ("table3_training", lambda: table3_training.run(steps=max(steps, 150))),
        ("fig2_underflow", lambda: fig2_underflow.run(steps=min(steps, 100))),
        ("fig4_tiling", fig4_tiling.run),
        ("fig7_energy", fig7_energy.run),
        ("kernel_bench", kernel_bench.run),
        ("beyond_formats", lambda: beyond_formats.run(steps=min(steps, 100))),
        ("roofline", roofline.run),
    ]:
        t = time.time()
        print(f"--- {name} ---", flush=True)
        try:
            fn()
        except Exception as e:  # pragma: no cover
            emit(f"{name}_ERROR", 0.0, repr(e)[:120])
            failed.append(name)
        emit(f"{name}_wall", (time.time() - t) * 1e6, "")
    emit("benchmarks_total_wall", (time.time() - t0) * 1e6, "")
    if failed:
        print(f"errored: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
