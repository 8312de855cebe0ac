"""Peak device memory of the run in MB (10**6 bytes), as the device's
allocator reports it (``memory_stats()["peak_bytes_in_use"]``) once the
window has closed; nothing where the backend reports none."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if peak is None else peak / 1e6
