"""The step's share of the chips' peak (%): the operations the window's
steps require (the ``flops_per_token`` of the configuration's reference
module: ``bench/flops.py`` for ``lm``; no recomputation, only routed
experts) over the sum of each step's ``step_time_s`` times the chips it
ran on, times one chip's peak (``bench/peaks.json``).  The first step at
each new node count is left out: it also waits for the copies of the
rescale before it."""


def read(run):
    steps = [s for s in run.steps if not s.after_rescale]
    chip_s = sum(s.step_time_s * s.n_nodes for s in steps)
    if chip_s <= 0:
        return None
    work = run.flops_per_token * run.seq_len * sum(s.rows for s in steps)
    return 100.0 * work / (chip_s * run.peak_flops)
