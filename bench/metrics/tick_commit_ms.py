"""tick_commit_ms: device time per scan tick of the server rule's commit: the
aggregator step, the cache rows it writes and the fused `commit_batch` kernel
where it runs (the `afl.commit` stage), in ms: the stage's self time on device
0 over the traced window, over the window's ticks. The stage of each op is read
from the compiled chunk (`bench/tick_stages.py`)."""
import tick_stages


def read(record):
    return tick_stages.tick_ms(record, "afl.commit")
