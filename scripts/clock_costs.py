#!/usr/bin/env python3
"""What the clocks a watched leg reads cost on this machine (obs/legs.py:
a mark is ``perf_counter`` + ``thread_time``, an entry adds ``getrusage``),
in a tight loop; one JSON line.  No JAX."""

import json
import os
import resource
import time
import timeit

CALLS = {
    "perf_counter": time.perf_counter, "thread_time": time.thread_time,
    "time": time.time, "urandom8": lambda: os.urandom(8),
    "getpid": os.getpid,
    "getrusage_thread": lambda: resource.getrusage(resource.RUSAGE_THREAD)}

if __name__ == "__main__":
    n = 50_000
    out = {name: 1e6 * timeit.timeit(fn, number=n) / n
           for name, fn in CALLS.items()}
    # the thread clock's step: how far apart two unequal readings lie
    first = time.thread_time()
    while (now := time.thread_time()) == first:
        pass
    out["thread_time_step_us"] = 1e6 * (now - first)
    print(json.dumps({"clock_costs_us": out}))
