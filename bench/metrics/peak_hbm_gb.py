"""Peak device memory on the fullest chip the cell uses, read from
``memory_stats()["peak_bytes_in_use"]`` after the window (GB)."""


def read(run):
    if not any(run.peak_bytes):
        return None
    return max(run.peak_bytes) / 1e9
