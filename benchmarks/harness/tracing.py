"""The profiler around part of a window, and where its trace lands.

Only the process that holds the chip can trace it, so the tracer lives
in the benchmark's one process.  The trace goes to a directory of its
own under the run's TMPDIR and is removed once it has been reduced: a
few seconds of trace are tens of MiB, and the machine's host keeps
every block that was once written."""

from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time
from typing import Optional

now = time.perf_counter


class Tracer:
    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_start = self.t_stop = None

    def start(self) -> None:
        import jax
        options = jax.profiler.ProfileOptions()
        # the device planes and the host's runtime spans; no Python
        # call stacks (they dwarf the rest and slow the host)
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.t_start = now()

    def stop(self) -> None:
        import jax
        self.t_stop = now()
        jax.profiler.stop_trace()

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def path(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None

    def remove(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
