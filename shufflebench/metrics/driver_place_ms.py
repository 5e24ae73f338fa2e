"""driver_place_ms: host time per job that the device path's driver
spends preparing and placing the job's columns, averaged over the
traced jobs: the summed ``shuffle.device.pad`` and ``shuffle.device.place``
spans inside each job's span, and the wait for the copy they start.
``jax.device_put`` returns before its host-to-device copy ends, so the
copy is waited for in ``shuffle.device.sync``, before the step's first
operation: that part of each ``sync`` span counts as placement."""

from shufflebench import program_spans

SPANS = ("shuffle.device.pad", "shuffle.device.place")


def read(r):
    p = program_spans.of(r)
    per_job = p.per_job_ns(SPANS)
    if not any(per_job):
        return None
    for i, (lo, hi) in enumerate(r.trace.jobs()):
        per_job[i] += sum(p.idle_until_busy_ns(a, b)
                          for a, b, _ in p.named("shuffle.device.sync", lo, hi))
    return sum(per_job) / len(per_job) / 1e6
