"""commit_batch_roofline: share of the roofline reached by the Pallas `commit_batch`
kernel, in %: the least time its calls could take on this chip — the bytes
each call must move (the configuration family's `kernel_bytes`) over the peak
HBM bandwidth; the kernel does a few operations per byte, so bandwidth bounds
it — over their summed device time in the trace."""
import re

#: how the kernel's calls are named in the device trace
NAME = re.compile(r"^commit_batch(\.\d+)?( |$)")


def read(record):
    per_call = record["family"].kernel_bytes(record["config"], record["traffic"]).get("commit_batch")
    secs, calls = record["trace"].kernel_time(NAME.pattern)
    if not per_call or calls == 0 or secs <= 0:
        return None
    return 100.0 * calls * per_call / record["peaks"]["hbm_bytes_per_s"] / secs
