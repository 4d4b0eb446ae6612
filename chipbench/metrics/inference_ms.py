"""Mean executor stage time (StageTimes.inference: the forward, ended by
block_until_ready), in ms."""


def read(run):
    t = [d.record.times.inference for d in run.deliveries]
    return 1e3 * sum(t) / len(t) if t else None
