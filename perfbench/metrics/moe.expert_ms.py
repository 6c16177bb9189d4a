"""Device time of the routed experts' grouped matrix products per decode
step, in milliseconds: the summed durations of the decode program's
operations named after XLA's grouped product on the TPU, ``ragged-dot``
(``jit_decode_step/ragged-dot-none.<n>``, the gate, up and down products,
and ``jit_decode_step/ragged-dot-metadata``, which lays out their groups),
inside the decode program's runs that lie wholly in the traced window, over
the number of those runs.  None where the trace has no such operation."""
import bisect

from perfbench.devtrace import module_events

PROGRAM, OP = "decode_step", "/ragged-dot"


def read(run):
    if run.trace is None:
        return None
    total, n = 0.0, 0
    for dev, ops in run.trace.ops.items():
        runs = module_events(run.trace.modules.get(dev, ()), run.trace.window,
                             PROGRAM)
        starts = [m.start for m in runs]
        n += len(runs)
        for o in ops:
            if OP not in o.name or PROGRAM not in o.name.split("/", 1)[0]:
                continue
            i = bisect.bisect_right(starts, o.start) - 1
            if i >= 0 and o.end <= runs[i].end:
                total += o.end - o.start
    return total / n / 1e6 if n and total else None
