"""driver_fetch_ms: host time per job that the device path's driver
spends copying the step's outputs to host memory, the summed
``shuffle.device.fetch`` spans inside each job's span, averaged over
the traced jobs."""

from shufflebench import program_spans

SPANS = ("shuffle.device.fetch",)


def read(r):
    per_job = program_spans.of(r).per_job_ns(SPANS)
    if not any(per_job):
        return None
    return sum(per_job) / len(per_job) / 1e6
