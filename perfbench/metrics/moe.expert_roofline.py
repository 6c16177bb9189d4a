"""Share of its roofline that the routed experts' grouped products reach, in
percent: the least time their work needs on this chip, max(FLOPs / peak
FLOP/s, bytes / peak bandwidth), over ``moe.expert_ms``.  The work is the
configuration's ``counts`` (``references/deepseek_v2.py``,
``ExpertCounts``): ``expert_bytes``, each held expert that the step's
active tokens touch read once, and ``expert_flops``, the products of the
expected assignments to held experts.  Both are means per decode step in
the traced window.  None where the configuration counts no experts or the
trace has no grouped product."""
from perfbench.harness import HERE, reader
from perfbench.stats import mean


def read(run):
    c, pk = run.counts, run.peaks
    if run.trace is None or not pk or not hasattr(c, "expert_bytes"):
        return None
    least = [max(c.expert_flops(d["active"]) / pk["bf16_flops_per_s"],
                 c.expert_bytes(d["active"]) / pk["hbm_bytes_per_s"])
             for d in run.decodes if run.in_trace(d["start"])]
    ms = reader(HERE, "moe.expert_ms")(run)
    if not least or ms is None:
        return None
    return 100.0 * mean(least) / (ms / 1e3)
