"""Device memory the server holds while it serves: bytes in use on the
fullest chip once the window has closed and Python's garbage has been
collected, in GB (1e9 bytes): weights, the engine's split copy, the KV
cache. The peak in the window is not steady enough to bound (the same
seed reads 10.585 or 11.136 GB on internlm2-1.8b.sporadic); the device
record keeps it as `memory_peak_bytes`."""


def read(run):
    return run.hbm_in_use_bytes / 1e9 if run.hbm_in_use_bytes else None
