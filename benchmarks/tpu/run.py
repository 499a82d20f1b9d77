"""Command line of the benchmark: one run of one cell.

    python3 benchmarks/tpu/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero, with no result line,
where JAX finds no TPU or fewer chips than the cell asks for, or where
the program under test (``src/``) is missing.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    root = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(root), str(root / "src")]
    from benchmarks.tpu import harness

    try:
        harness.main(sys.argv[1:], t_start=T_START)
    except harness.NoChip as e:
        print(e, file=sys.stderr, flush=True)
        sys.exit(1)
