"""Benchmark harness: one function per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [names...]`` prints each
benchmark's rows as CSV-ish lines: name,key=value,...
"""
from __future__ import annotations

import sys
import time


def _all_benchmarks():
    from benchmarks import (
        faults_bench,
        kernels_bench,
        paper_tables,
        policy_switch_bench,
        rank_death_bench,
        roofline_table,
        serving_bench,
        syncfree_bench,
    )

    return {
        "fig1_sync_overhead": paper_tables.bench_fig1_sync_overhead,
        "fig3_roofline": paper_tables.bench_fig3_roofline,
        "table1_breakdown": paper_tables.bench_table1_breakdown,
        "table2_contention": paper_tables.bench_table2_contention,
        "table3_ablations": paper_tables.bench_table3_ablations,
        "table4_tdm": paper_tables.bench_table4_tdm,
        "table5_e2e": paper_tables.bench_table5_e2e,
        "table6_ttft": paper_tables.bench_table6_ttft,
        "placement": paper_tables.bench_placement,
        "policy_auto": paper_tables.bench_policy_auto,
        "kernels": kernels_bench.bench_kernels,
        "split_moe": kernels_bench.bench_split_moe,
        "split_attn": kernels_bench.bench_split_attn,
        "demand_moe": kernels_bench.bench_demand_moe,
        "demand_predict": kernels_bench.bench_demand_predict,
        "fault_degradation": faults_bench.bench_fault_degradation,
        "syncfree": syncfree_bench.bench_syncfree_decode,
        "policy_switch": policy_switch_bench.bench_policy_switch,
        "serving_sweep": serving_bench.bench_serving_sweep,
        "rank_death": rank_death_bench.bench_rank_death,
        "dryrun_roofline": roofline_table.bench_dryrun_roofline,
    }


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    argv = argv if argv is not None else sys.argv[1:]
    benches = _all_benchmarks()
    names = argv or list(benches)
    for name in names:
        fn = benches[name]
        t0 = time.perf_counter()
        rows = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"== {name} ({us/1e6:.1f}s) ==")
        for r in rows:
            kv = ",".join(f"{k}={v}" for k, v in r.items())
            print(f"{name},{kv}")
        print()


if __name__ == "__main__":
    main()
