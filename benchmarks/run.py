"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (plus human-readable sections).

  bench_latency   Table 1   miss-latency scenarios
  bench_tables    Tables 2-4  accuracy vs throughput at c={.75,.5,.375}
  bench_skew      Fig. 6    uneven expert activation
  bench_coact     Figs. 7/9 co-activation structure + CFT compactness
  bench_pcie      Fig. 8    PCIe bytes: base vs BuddyMoE
  bench_kernels   (impl)    Pallas kernel microbenches
  bench_roofline  §Roofline dry-run derived terms
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_ablation, bench_coact, bench_kernels,
                            bench_latency, bench_pcie, bench_roofline,
                            bench_skew, bench_tables)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sections = [
        ("Table 1 (latency scenarios)", bench_latency),
        ("Fig. 6 (activation skew)", bench_skew),
        ("Figs. 7/9 (co-activation)", bench_coact),
        ("Fig. 8 (PCIe bytes)", bench_pcie),
        ("Tables 2-4 (accuracy vs throughput)", bench_tables),
        ("Ablations (gates / prefetchers)", bench_ablation),
        ("Kernels", bench_kernels),
        ("Roofline (dry-run)", bench_roofline),
    ]
    rows = []
    failed = []
    for title, mod in sections:
        print(f"\n=== {title} ===")
        try:
            mod.run(rows)
        except Exception as e:  # noqa: BLE001
            failed.append((title, repr(e)))
            traceback.print_exc(limit=4)
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        print(f"\nFAILED sections: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
