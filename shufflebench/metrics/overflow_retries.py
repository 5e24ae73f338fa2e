"""overflow_retries: how often per job the device path's driver re-ran
the step because a bucket overflowed its capacity, the job's
``shuffle.device.attempt`` spans less one, averaged over the traced
jobs that reached the step."""

from shufflebench import program_spans


def read(r):
    p = program_spans.of(r)
    attempts = [len(p.named("shuffle.device.attempt", lo, hi))
                for lo, hi in r.trace.jobs()]
    attempts = [n for n in attempts if n]
    if not attempts:
        return None
    return sum(n - 1 for n in attempts) / len(attempts)
