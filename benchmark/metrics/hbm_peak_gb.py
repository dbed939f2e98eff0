"""Peak device memory on the fullest chip after the window:
``memory_stats()["peak_bytes_in_use"]``."""


def read(r):
    peak = r.device.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
