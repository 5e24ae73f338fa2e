"""driver_stitch_ms: host time per job that the device path's driver
spends assembling the result from the fetched outputs, the summed
``shuffle.device.stitch`` spans inside each job's span, averaged over
the traced jobs."""

from shufflebench import program_spans

SPANS = ("shuffle.device.stitch",)


def read(r):
    per_job = program_spans.of(r).per_job_ns(SPANS)
    if not any(per_job):
        return None
    return sum(per_job) / len(per_job) / 1e6
