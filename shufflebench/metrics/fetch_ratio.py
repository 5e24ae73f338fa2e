"""fetch_ratio: bytes the device path's driver copies from the devices
per byte it returns, over the window's ``shuffle.device.fetch`` spans:
their ``bytes`` (the fetched arrays) summed over their
``result_bytes`` (what the API call returns)."""

from shufflebench import program_spans


def read(r):
    lo, hi = r.trace.window()
    spans = program_spans.of(r).named("shuffle.device.fetch", lo, hi)
    fetched = sum(args.get("bytes", 0) for _, _, args in spans)
    returned = sum(args.get("result_bytes", 0) for _, _, args in spans)
    if not fetched or not returned:
        return None
    return fetched / returned
