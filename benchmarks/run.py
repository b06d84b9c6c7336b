"""Benchmark harness entry point — one module per paper table/figure plus
framework-path benches.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only paper|codec|roofline] [--smoke]
"""
import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=[None, "paper", "codec",
                                                     "roofline"])
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized codec pass (10k elements, no model benches)")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_checkout_cache

    use_checkout_cache()
    rows = []
    if args.only in (None, "paper"):
        from benchmarks import bench_paper
        bench_paper.run(rows)
    if args.only in (None, "codec"):
        from benchmarks import bench_codec
        bench_codec.run(rows, smoke=args.smoke)
    if args.only in (None, "roofline"):
        from benchmarks import roofline
        roofline.run(rows)
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == '__main__':
    main()
