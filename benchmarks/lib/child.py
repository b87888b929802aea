"""The chip-owning child: the program's normal ``serve`` entry, unchanged.

``python benchmarks/lib/child.py [--bench-trace-dir DIR]
[--bench-fault NAME] -- <serve arguments>`` runs
``gyeeta_tpu.cli.main(["serve", ...])`` in the main thread. Only the
process that holds the chip can trace it, so a side thread reads commands
from stdin: ``trace <delay_s> <seconds>`` brackets that interval of the
measured window with ``jax.profiler`` (Python tracer off), marks the
interval inside the trace with a ``bench_window`` host event, and leaves
``trace_done.json`` (monotonic start and stop) in DIR when the trace is
written: the signal that the trace is done. When stdin closes the parent
is gone, and the child ends itself. ``--bench-fault`` plants one fault of
``lib/faults.py`` under the timed path; only the tests under
``benchmarks/tests`` pass it.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _stdin_thread(trace_dir) -> None:
    """Commands from the parent: ``trace <delay_s> <seconds>``, ``arm`` /
    ``disarm`` (a planted fault). End of input means the parent is gone:
    the child must not outlive it."""
    for line in sys.stdin:
        parts = line.split()
        if parts[:1] == ["arm"] or parts[:1] == ["disarm"]:
            from lib import faults
            faults.ARMED = parts[0] == "arm"
        elif len(parts) == 3 and parts[0] == "trace" and trace_dir:
            threading.Thread(target=_trace, args=(
                trace_dir, float(parts[1]), float(parts[2])),
                daemon=True).start()
    os.kill(os.getpid(), signal.SIGTERM)


def _trace(trace_dir: str, delay_s: float, seconds: float) -> None:
    time.sleep(delay_s)
    import jax
    from lib.trace_reduce import WINDOW_MARKER
    # the Python tracer records every call of the serving loop: it slowed
    # the flood tenfold and crowded the device events out of the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    t0 = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # the window on the trace's own clock: lib/trace_reduce.py clips every
    # device event to this event's interval (the profiler keeps recording
    # past t1 while stop_trace collects)
    with jax.profiler.TraceAnnotation(WINDOW_MARKER):
        time.sleep(seconds)
    t1 = time.monotonic()
    jax.profiler.stop_trace()
    tmp = os.path.join(trace_dir, "trace_done.json.tmp")
    with open(tmp, "w") as f:
        json.dump({"t_start": t0, "t_stop": t1,
                   "t_written": time.monotonic()}, f)
    os.replace(tmp, os.path.join(trace_dir, "trace_done.json"))


def main() -> None:
    argv = sys.argv[1:]
    cut = argv.index("--")
    own, serve = argv[:cut], argv[cut + 1:]
    opts = dict(zip(own[::2], own[1::2]))
    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if opts.get("--bench-fault"):
        from lib import faults
        faults.plant(opts["--bench-fault"])
    threading.Thread(target=_stdin_thread,
                     args=(opts.get("--bench-trace-dir"),),
                     daemon=True).start()
    from gyeeta_tpu import cli
    from gyeeta_tpu.utils import xlacache
    xlacache.configure()            # before jax is imported, as cli.main does
    import jax
    # every compile is named in the server's log: a program that compiles
    # inside the window can be told from the log's tail
    jax.config.update("jax_log_compiles", True)
    cli.main(["serve"] + serve)


if __name__ == "__main__":
    main()
