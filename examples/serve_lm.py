"""Batched serving demo: continuous-batching engine over decode slots.

Run:  PYTHONPATH=src python examples/serve_lm.py --requests 8
"""
import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch.serve import main as serve_main


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    stats = serve_main(["--arch", "smoke", "--requests",
                        str(args.requests), "--slots", str(args.slots),
                        "--max-new", "12", "--prompt-len", "16",
                        "--max-len", "64"])
    print(f"served {stats['requests']} requests, "
          f"{stats['generated']} tokens at {stats['tokens_per_s']} tok/s "
          f"({stats['ticks']} batched decode ticks)")


if __name__ == "__main__":
    main()
