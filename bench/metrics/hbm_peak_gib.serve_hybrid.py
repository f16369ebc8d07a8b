"""Peak device memory (``peak_bytes_in_use``) after the window, GiB."""


def read(r):
    peak = r.run.memory_peak_bytes
    return None if peak is None else peak / 2**30
