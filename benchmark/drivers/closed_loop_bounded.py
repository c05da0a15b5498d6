"""The closed loop (closed_loop.py) with set-up held to stated bounds.

The mix's ``setup_bounds`` give a deadline in seconds (from the start
of set-up) and a ceiling on the process's resident host memory in
bytes.  While set-up runs, a watchdog ends the process with exit code 1
once either is passed, after writing which one and every thread's stack
to standard error: a program that cannot run the configuration then
fails the run soon and says where, where it would hang in a compile or
be killed for memory.  Both bounds lie well above what a program that
runs the configuration needs (PERF.md).  The peak resident memory is
logged after set-up and after the window.  Everything else is
closed_loop's.
"""

import faulthandler
import os
import resource
import sys
import threading
import time

import harness

_loop = harness.plugin("drivers", "closed_loop")
work = _loop.work
release = _loop.release
check = _loop.check
control = _loop.control

#: how often the watchdog reads the resident memory, in seconds
POLL_S = 0.1


def rss_bytes() -> int:
    """The process's resident memory now."""
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")


def peak_rss_bytes() -> int:
    """The most resident memory the process has held."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _fail(why: str):
    print(f"set-up {why}: the program cannot run this configuration "
          f"within the cell's bounds; every thread's stack follows",
          file=sys.stderr, flush=True)
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
    sys.stderr.flush()
    os._exit(1)


def _watch(done: threading.Event, deadline_s: float, ceiling: float,
           t0: float):
    while not done.wait(POLL_S):
        used = rss_bytes()
        if used > ceiling:
            _fail(f"passed its host-memory ceiling ({used} B resident, "
                  f"ceiling {ceiling:.0f} B)")
        if time.perf_counter() - t0 > deadline_s:
            _fail(f"passed its deadline ({deadline_s} s)")


def setup(cell, spans, log):
    bounds = cell.traffic["setup_bounds"]
    deadline_s = float(bounds["deadline_s"])
    done = threading.Event()
    watchdog = threading.Thread(
        target=_watch, name="setup-watchdog", daemon=True,
        args=(done, deadline_s, float(bounds["rss_ceiling_bytes"]),
              time.perf_counter()))
    # a backstop that needs no interpreter lock: it fires even while
    # the main thread holds the lock in native code
    faulthandler.dump_traceback_later(deadline_s + 60, exit=True)
    watchdog.start()
    try:
        state = _loop.setup(cell, spans, log)
    finally:
        done.set()
        faulthandler.cancel_dump_traceback_later()
        watchdog.join()
    log(f"set-up peak host RSS: {peak_rss_bytes()} B")
    return state


def window(state, seconds, spans, log):
    records = _loop.window(state, seconds, spans, log)
    log(f"window peak host RSS: {peak_rss_bytes()} B")
    return records
