"""Traffic generators, one module per ``generator`` name that a traffic
file under ``benchmark/traffic/`` names.  A generator module provides:

* ``plan(config, traffic, seed)`` -> the deterministic inputs of one run;
* ``preload(cluster, plan)`` -> load the resident state through the
  program's own bulk calls (runs in the process that holds the chip);
* ``warm(cluster, plan)`` -> compile every shape the window will use;
* ``async drive(plan, urls, t0, seconds)`` -> the client side of the
  window (runs in benchmark/loadgen.py, a process that never imports JAX);
* ``judge(cluster, plan, client)`` -> end-to-end metrics, counts and the
  checks against the plain reference (benchmark/oracle.py).

Module level imports stay stdlib + numpy: the load generator imports
these modules and must not touch the chip.
"""
