"""The chip's peak memory over its ``bytes_limit``: the allocator's
``peak_bytes_in_use`` (buffers) plus its ``peak_bytes_reserved`` (the
programs' temporaries), as ``device_memory`` in
``bench/runners/fed_round.py`` reads them after the window."""


def read(run):
    peak, limit = run.memory.get("peak"), run.memory.get("limit")
    if not peak or not limit:
        return None
    return 100.0 * peak / limit
