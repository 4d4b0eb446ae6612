"""Mean conform stage time (StageTimes.preprocessing: conform and the cast
to the policy's storage type, ended by block_until_ready), in ms."""


def read(run):
    t = [d.record.times.preprocessing for d in run.deliveries]
    return 1e3 * sum(t) / len(t) if t else None
