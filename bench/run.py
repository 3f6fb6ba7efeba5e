"""Benchmark entry point: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with as many TPU chips as
the cell asks for. The run sets up the cell (frozen statistics, the
detector, warm-up of every program the window runs), measures for
``--seconds``, checks the window's output against the plain reference
and prints one JSON object as the last line of standard output: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics read from spans and a profiler trace of the window.
Without a TPU it exits non-zero and prints no result; it never falls
back to the CPU. ``--precision high`` runs the fingerprint chain one
precision below the configuration's (the control of the check).
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _prepare_environment(argv: list[str]) -> None:
    """Before JAX starts: a one-chip cell sees one chip whatever the host
    holds, and the host CPU backend stays available beside the TPU (the
    detector is assembled in host memory)."""
    name = argv[argv.index("--workload") + 1] if "--workload" in argv else ""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {c["name"]: c["chips"] for c in spec["workloads"]}.get(name, 1)
    if chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"


if __name__ == "__main__":
    _prepare_environment(sys.argv[1:])
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
