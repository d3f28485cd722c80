"""Benchmark harness entrypoint: one function per paper table/figure.

  * table1    — the paper's Table 1 (cumulative optimization speedups)
  * roofline  — §Roofline terms per (arch x shape) from the dry-run
  * kernels   — hot-path microbenchmarks (CPU reference numbers)

Prints ``name,us_per_call,derived`` style CSV sections.
"""
from __future__ import annotations


def main() -> None:
    """Runs every section in turn; a section that raises stops the run
    with its traceback and a non-zero exit."""
    print("== table1: paper Table-1 cumulative speedups ==")
    from benchmarks import table1
    table1.main()

    print("\n== kernels: hot-path microbenchmarks ==")
    from benchmarks import kernels_bench
    kernels_bench.main()

    print("\n== roofline: per (arch x shape) terms from dry-run ==")
    from benchmarks import roofline
    if not roofline.main():
        print("(no dry-run artifacts found — run "
              "`python -m repro.launch.dryrun --all` first)")


if __name__ == "__main__":
    main()
