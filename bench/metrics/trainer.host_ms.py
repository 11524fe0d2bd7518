"""Mean host time of a train step outside the step call (ms): the wall of
``ElasticTrainer.train_step`` minus its own ``step_time_s`` (which starts
at the jitted call and ends when the loss reaches the host), so the batch
build, its ``device_put`` and the learning-rate scalar."""


def read(run):
    if not run.steps:
        return None
    return 1e3 * sum((s.t1 - s.t0) - s.step_time_s
                     for s in run.steps) / len(run.steps)
