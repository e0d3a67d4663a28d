# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: paper Tables 1-3 + memory + beyond-paper rows.

    PYTHONPATH=src python -m benchmarks.run

Roofline analysis (reads the dry-run artifacts) is separate:
    PYTHONPATH=src python -m benchmarks.roofline
"""
from __future__ import annotations


def main() -> None:
    import json
    import os

    from benchmarks import (grad_compress_bytes, table1_matmul, table2_mlp,
                            table3_cnn)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    mods = [table1_matmul, table2_mlp, table3_cnn, grad_compress_bytes]
    all_rows = []
    for mod in mods:
        for name, us, note in mod.rows():
            print(f"{name},{us:.1f},{note}")
            all_rows.append({"name": name, "value": us, "note": note})
    os.makedirs("experiments", exist_ok=True)
    with open("experiments/BENCH_run.json", "w") as f:
        json.dump(all_rows, f, indent=1)
    print("wrote experiments/BENCH_run.json")


if __name__ == "__main__":
    main()
