"""Run one cell once with the program's span recorder installed, and print
what its spans say.

    python3 perfbench/spanrun.py --workload <cell> --seed <n> --seconds <s> --profile <0|1>

The cell, its set-up and its window are those of ``run.py`` (``harness.
serve_window``, untraced, so the harness adds no spans of its own), with a
``repro.serving.spans.SpanRecorder`` installed from before set-up to after
the drain.  ``--profile 0`` measures what the recorder costs:
``tokens_per_s`` here against ``run.py --trace 0`` at the same seed.
``--profile 1`` adds a profiler trace of the middle ``TRACE_S`` seconds of
the window, timed from the end of the warm-up request's span, and reports
the device's idle gaps named by the program's spans.

The last line of standard output is one JSON object: ``tokens_per_s``; the
four span metrics (``spans.py``) over the window; the decode steps' time by
child span; the longest decode steps and collections; and, traced, the
``breakdown`` with the program's span names, ``step.decode_ms`` and the
mean ``engine.decode_step`` duration over the traced stretch.  No answer is
checked: ``run.py`` does that.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from perfbench.spanreport import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
