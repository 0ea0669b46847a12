"""The whole model's share of the chip's bfloat16 peak over the window:
the operations every served request needed (bench/work.py: prompt and
fed-back tokens through the surviving weights, and attention) over the
window's length times the peak."""
from bench import work


def read(run):
    conf = run.cell.conf
    out = run.report.outputs
    ops = sum(work.request_ops(conf, len(p.prompt), len(out[p.rid]))
              for p in run.planned if p.rid in out)
    return 100.0 * ops / (run.window_s * run.peak["bf16_flops"]) if ops else None
