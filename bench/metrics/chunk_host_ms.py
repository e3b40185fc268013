"""chunk_host_ms: mean host time per chunk outside the device call — from the
moment a chunk's outputs are on the host to the return of the next chunk's
dispatch — over the traced window, by the host clock. The device has nothing
queued in that time, so it bounds events_per_s from above."""


def read(record):
    host_s = record["host_s"]
    if not host_s:
        return None
    return 1e3 * sum(host_s) / len(host_s)
