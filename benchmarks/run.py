"""Benchmark driver: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run prime      # substring filter
"""
import sys
import time


def main() -> None:
    from repro import compile_cache

    compile_cache.enable()
    from benchmarks import (
        latency_model,
        lm_step,
        polymul_e2e,
        postprocess,
        preprocess,
        prime_search,
        roofline,
    )

    suites = [
        ("prime_search(Table III)", prime_search.run),
        ("latency_model(Fig 17)", latency_model.run),
        ("preprocess(Table IV)", preprocess.run),
        ("postprocess(Table V)", postprocess.run),
        ("polymul_e2e(Tables VI/VII)", polymul_e2e.run),
        ("lm_step(framework)", lm_step.run),
        ("roofline(dry-run artifacts)", roofline.run),
    ]
    flt = sys.argv[1] if len(sys.argv) > 1 else ""
    print("name,us_per_call,derived")
    for title, fn in suites:
        if flt and flt not in title:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # keep the driver robust: report and continue
            print(f"{title},0.0,SUITE ERROR {type(e).__name__}: {e}")
            continue
        for name, us, derived in rows:
            print(f'{name},{us:.1f},"{derived}"')
        print(f"# {title} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
