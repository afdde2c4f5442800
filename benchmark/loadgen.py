"""The client side of a run, in a process of its own that never imports
JAX (the chip belongs to the process serving the store).

Protocol on stdin/stdout, one JSON line each way:
  parent -> {"generator", "plan", "urls", "seconds"}
  child  -> "ready"           (inputs built, nothing sent yet)
  parent -> <t0>              (window start, time.monotonic(): one clock
                               across processes on Linux)
  child  -> {result}          (after the window and its drain)
"""
from __future__ import annotations

import asyncio
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import importlib

    spec = json.loads(sys.stdin.readline())
    gen = importlib.import_module(f"benchmark.generators.{spec['generator']}")
    print("ready", flush=True)
    t0 = float(sys.stdin.readline())
    result = asyncio.run(gen.drive(spec["plan"], spec["urls"], t0,
                                   float(spec["seconds"])))
    sys.stdout.write(json.dumps(result) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
